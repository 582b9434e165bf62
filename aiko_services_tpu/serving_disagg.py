# Disaggregated prefill/decode serving plane (ISSUE 14, ROADMAP item 2).
#
# Prefill that rides between decode rounds delays every live slot's
# next token.  Production LLM serving converged on the fix (DistServe,
# Splitwise): split prefill and decode into separately-scaled pools so
# prompt bursts never dilate inter-token latency.  Every building
# block already exists in this repo — this module is the composition:
#
#   * PrefillRuntime — a role-tagged actor owning a ContinuousDecoder +
#     PrefixKVCache pair whose ONLY job is computing prompt KV: each
#     request prefills (max_new_tokens=1), the existing retire-harvest
#     drops the prompt blocks into its cache, and the chain ships to the
#     decode side as a KV-transfer envelope (transport/wire.py
#     encode_kv_transfer) over the peer data plane — int8 {"q","s"}
#     blocks cross bit-exact, so disaggregated greedy output is
#     BIT-IDENTICAL to colocated by construction;
#   * PrefillClient — the decode-side KV admit path: routes prompts to a
#     prefill runtime by remaining deadline (ops/admission.DeadlineRouter
#     — short-budget prompts to the least-loaded runtime), installs the
#     shipped chain into the decode decoder's PrefixKVCache
#     (install_chain), and submits the request — the prefix-admit scatter
#     copies the chain into the slot with NO forward pass, so the decode
#     pool's scan only ever stalls on the tiny ragged suffix.  Chains the
#     decode side already holds ship as HANDLES — the hash chain is
#     content-addressed, so only a start index crosses, never the bytes
#     (ROADMAP item 3 residue b);
#   * local-prefill fallback ladder — no pool, transfer timeout after a
#     retry, corrupt payload, or layout mismatch all degrade to the
#     decode runtime prefilling locally, counted, never a dropped
#     request (the PR 6 peer→broker ladder, one level up);
#   * two_pool_autoscalers — the PR 9 autoscaler instantiated per role:
#     the prefill pool scales on its queue depth / TTFT backlog, the
#     decode pool on fleet-merged ITL p95 / batch wait, each through its
#     own LifeCycleManager.scale_to;
#   * DisaggHarness — the CPU-runnable two-pool plane behind the
#     lat_llama_disagg_* bench rung, scripts/disagg_smoke.py, and the
#     chaos tests (registrar + peer-enabled prefill/decode runtimes over
#     one MemoryBroker and one engine).
#
# The reference has no serving at all (its LLM hop is a blocking HTTP
# call); DistServe (OSDI'24) and Splitwise (ISCA'24) are the design
# ancestors for the split itself.

from __future__ import annotations

import time
import uuid

import numpy as np

from .actor import Actor
from .observe import tracing
from .observe.metrics import MirroredStats, default_registry
from .ops.admission import DeadlineRouter
from .service import ServiceFilter, ServiceProtocol, ServiceTags
from .transport import wire
from .utils import get_logger

__all__ = ["PROTOCOL_PREFILL", "ROLE_PREFILL", "ROLE_DECODE",
           "ROLE_COLOCATED", "role_tag", "tag_role", "PrefillRuntime",
           "PrefillClient", "SessionMigrator", "two_pool_autoscalers",
           "DisaggHarness"]

PROTOCOL_PREFILL = ServiceProtocol("prefill")
ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"
ROLE_COLOCATED = "colocated"


def role_tag(role: str) -> str:
    """The discovery-record tag form of a serving role."""
    return f"role={role}"


def tag_role(service, role: str) -> None:
    """Tag a service's registrar record with its serving role and
    re-register so the changed record propagates (the registrar
    suppresses identical re-adds but forwards changed ones)."""
    service.add_tags([role_tag(role)])
    runtime = service.runtime
    if runtime.registrar is not None and runtime.message is not None:
        runtime._register_service(service)


class PrefillRuntime(Actor):
    """A prefill-pool member: computes prompt KV and ships it.

    RPC (binary envelope on {topic_path}/in):
        (prefill transfer_id reply_topic tenant have_tokens
         {"tokens": i32[*]})
    The reply is a KV-transfer envelope on `reply_topic`, carrying
    chain blocks [have_tokens/block, ...) — blocks the caller declared
    it already holds are handles (indices), not bytes.

    The decoder is an ordinary ContinuousDecoder with a bound
    PrefixKVCache: a request prefills, emits one token, retires, and
    the retire-harvest inserts its prompt blocks — repeated prefixes
    across requests (shared system prompts) prefill once here too.
    Geometry MUST match the decode pool's (same config / kv dtype /
    block size); the transfer declares the donor layout and the decode
    side refuses a mismatch."""

    def __init__(self, runtime, name: str = "prefill", *,
                 params=None, config=None, decoder=None, cache=None,
                 block_tokens: int = 32, cache_mb: int = 256,
                 max_slots: int = 8, prefill_buckets=(128,),
                 steps_per_sync: int = 1,
                 prefill_chunk: int | None = None,
                 decoder_opts: dict | None = None,
                 pump_period: float = 0.002,
                 batch_window: float = 0.0,
                 chunk_stream: bool | None = None, registry=None):
        super().__init__(runtime, name, PROTOCOL_PREFILL,
                         tags=[role_tag(ROLE_PREFILL)])
        from .serving import ContinuousDecoder, PrefixKVCache
        self.logger = get_logger(f"disagg.prefill.{name}")
        self._registry = registry or default_registry()
        if decoder is not None:
            self.cache = cache if cache is not None \
                else decoder.prefix_cache
            self.decoder = decoder
        else:
            self.cache = cache or PrefixKVCache(
                block_tokens=int(block_tokens),
                max_bytes=int(cache_mb) << 20,
                name=f"{name}.cache", registry=self._registry)
            self.decoder = ContinuousDecoder(
                params, config, max_slots=int(max_slots),
                prefill_buckets=tuple(prefill_buckets),
                steps_per_sync=int(steps_per_sync),
                # chunked prefill forced on (largest bucket) like
                # PE_LlamaAgent's prefix path: without it the decoder
                # TRUNCATES prompts to the largest bucket, the harvest
                # keys on the truncated tail, and _ship's full-prompt
                # match finds nothing — every long transfer would ship
                # zero blocks (review finding)
                prefill_chunk=int(prefill_chunk)
                if prefill_chunk else max(prefill_buckets),
                name=name, prefix_cache=self.cache,
                registry=self._registry, **(decoder_opts or {}))
        if self.cache is None:
            raise ValueError(
                "PrefillRuntime needs a decoder with a bound "
                "PrefixKVCache (the harvest IS the product)")
        # chunk streaming (ISSUE 17): when the donor prefills in
        # chunks, ship each chunk's finished blocks the moment the
        # chunk lands instead of holding the whole prompt's KV for one
        # ship-on-finish envelope — the transfer overlaps the rest of
        # the prefill compute.  Default: on whenever chunked prefill
        # is on (there is nothing to stream otherwise).
        if chunk_stream is None:
            chunk_stream = bool(self.decoder.prefill_chunk)
        self.chunk_stream = bool(chunk_stream)
        # pump_period <= 0 drives the pump flat-out (once per engine
        # step) instead of on a periodic timer — what the single-engine
        # harness uses so a busy pump cannot starve the engine's
        # message queues (see DisaggHarness)
        self._flatout = pump_period is not None and pump_period <= 0
        if self._flatout:
            runtime.event.add_flatout_handler(self.decoder.pump)
        else:
            self.decoder.attach(runtime.event, period=pump_period)
        self.stats = MirroredStats(
            {"requests": 0, "computed": 0, "blocks_shipped": 0,
             "bytes_shipped": 0, "handle_blocks": 0, "refused": 0,
             "empty_ships": 0, "envelopes": 0, "batched_envelopes": 0,
             "chunks_shipped": 0, "chunk_blocks": 0},
            metric="prefill_runtime_events_total",
            help="prefill-runtime events by kind",
            registry=self._registry, skip=("bytes_shipped",),
            labels={"runtime": name})
        # prefill-side transfer batching (ISSUE 15 satellite, PR 14
        # residue b): finished transfers to the SAME destination within
        # `batch_window` seconds coalesce into one kv_transfer_batch
        # envelope — a prompt burst's per-envelope wire cost amortizes.
        # 0 disables (ship-on-finish, the PR 14 behavior).
        self.batch_window = max(0.0, float(batch_window))
        self._ship_queue: dict[str, list] = {}
        self._ship_timers: dict[str, int] = {}
        self._batched_counter = self._registry.counter(
            "disagg_transfer_batched_total",
            "KV transfers that rode a coalesced batch envelope",
            labels={"runtime": name})
        # the prefill pool's OWN scale signal (ISSUE 14): prompts
        # waiting for KV compute — what the prefill-pool autoscaler
        # reads as TTFT backlog
        self._queue_gauge = self._registry.gauge(
            "prefill_queue_depth",
            "prompts queued or resident in the prefill runtime",
            labels={"runtime": name})

    def _publish_depth(self) -> None:
        self._queue_gauge.set(len(self.decoder._pending) +
                              self.decoder.active_count)

    # -- RPC ---------------------------------------------------------------
    def prefill(self, transfer_id, reply_topic, tenant, have_tokens,
                box) -> None:
        """Compute prompt KV for `tokens` and ship the chain blocks the
        caller does not already hold."""
        self.stats["requests"] += 1
        try:
            tokens = [int(t) for t in np.asarray(box["tokens"])]
            have = max(0, int(str(have_tokens)))
        except (TypeError, KeyError, ValueError) as exc:
            self.stats["refused"] += 1
            self.logger.warning("prefill %s: malformed request %r: %r",
                                self.name, transfer_id, exc)
            return
        # truncate EXACTLY like decoder.submit will, so the harvest,
        # the match below, and the shipped tokens all key one prompt
        tokens = tokens[-_prompt_cap(self.decoder):] or [0]
        tenant = str(tenant)
        context = tracing.current_trace()
        # chunk-stream cursor: the next chain block index to ship.
        # Shared by the per-chunk progress callback and the final ship
        # so a block crosses the wire exactly once.
        state = {"cursor": None}

        def computed(_rid, generated):
            self._publish_depth()
            with tracing.activate(context):
                self._ship(str(transfer_id), str(reply_topic), tenant,
                           have, tokens,
                           int(generated[0]) if generated else None,
                           cursor=state["cursor"])

        progress = None
        if self.chunk_stream:
            def progress(request, finished):
                if finished:
                    return   # the final ship (with first_token) owns the tail
                with tracing.activate(context):
                    self._ship_chunk(str(transfer_id), str(reply_topic),
                                     tenant, have, tokens, request, state)

        accepted = self.decoder.submit(str(transfer_id), tokens, 1,
                                       computed, tenant=tenant,
                                       progress_callback=progress)
        if not accepted:
            self.stats["refused"] += 1
        self._publish_depth()

    def _ship(self, transfer_id: str, reply_topic: str, tenant: str,
              have: int, tokens, first_token, cursor=None) -> None:
        self.stats["computed"] += 1
        cache = self.cache
        block = cache.block_tokens
        keys, hit = cache.match(tenant, tokens)
        if hit == 0 and len(tokens) >= block:
            # the computed prompt produced no cached chain (budget
            # refused every insert?): ship nothing but say so — a
            # silent empty transfer looks exactly like success
            self.stats["empty_ships"] += 1
            self.logger.warning(
                "prefill %s: transfer %s computed %d tokens but the "
                "cache holds none of its chain; shipping empty",
                self.name, transfer_id, len(tokens))
        start_block = min(have // block, hit // block)
        # handle-shipping accounting keys on the caller's holdings, not
        # on what chunk streaming already moved — count before the
        # cursor advances the window
        self.stats["handle_blocks"] += start_block
        if cursor:
            # chunk streaming already shipped blocks below the cursor;
            # the final envelope carries only the tail (plus
            # first_token, which must always cross)
            start_block = min(max(start_block, int(cursor)),
                              hit // block)
        blocks = self._wire_blocks(keys[start_block:hit // block])
        context = tracing.current_trace()
        payload = wire.encode_kv_transfer(
            transfer_id, tenant, tokens, start_block, block,
            cache.wire_layout(), blocks, first_token=first_token,
            trace=context.to_fields(self.runtime.event.clock.now())
            if context is not None else None)
        self.stats["blocks_shipped"] += len(blocks)
        self.stats["bytes_shipped"] += len(payload)
        self._post(reply_topic, payload)

    def _ship_chunk(self, transfer_id: str, reply_topic: str,
                    tenant: str, have: int, tokens, request,
                    state: dict) -> None:
        """Ship the chain blocks a finished prefill chunk just made
        durable (ISSUE 17 chunk streaming).  Runs from the decoder's
        progress callback — the request is still resident, so the rows
        are harvested into the cache first and shipped from there with
        the same block path the final ship uses."""
        cache = self.cache
        block = cache.block_tokens
        self.decoder.harvest_progress(request)
        pos = int(request.prefill_pos)
        keys, hit = cache.match(tenant, list(tokens[:pos]))
        if state["cursor"] is None:
            # blocks the caller already holds never ship, streamed or
            # not — the cursor starts at the handle boundary
            state["cursor"] = min(have // block, hit // block)
        cursor = state["cursor"]
        end = hit // block
        if end <= cursor:
            return
        blocks = self._wire_blocks(keys[cursor:end])
        context = tracing.current_trace()
        payload = wire.encode_kv_transfer(
            transfer_id, tenant, list(tokens[:end * block]), cursor,
            block, cache.wire_layout(), blocks, final=False,
            trace=context.to_fields(self.runtime.event.clock.now())
            if context is not None else None)
        state["cursor"] = end
        self.stats["chunks_shipped"] += 1
        self.stats["chunk_blocks"] += len(blocks)
        self.stats["blocks_shipped"] += len(blocks)
        self.stats["bytes_shipped"] += len(payload)
        self._post(reply_topic, payload)

    def _wire_blocks(self, keys) -> list:
        return _chain_wire_blocks(self.cache, keys)

    def _post(self, reply_topic: str, payload: bytes) -> None:
        """Ship one finished transfer: immediately, or coalesced with
        other same-destination transfers inside the batch window
        (ISSUE 15 satellite).  Either way the envelope rides the peer
        channel when the caller's reply topic is pinned, the broker
        otherwise — the PR 6 fallback ladder carries it."""
        if self.batch_window <= 0:
            self.stats["envelopes"] += 1
            self.runtime.publish(reply_topic, payload)
            return
        queue = self._ship_queue.setdefault(reply_topic, [])
        queue.append(payload)
        if reply_topic not in self._ship_timers:
            self._ship_timers[reply_topic] = \
                self.runtime.event.add_oneshot_handler(
                    lambda: self._flush_ships(reply_topic),
                    self.batch_window)

    def _flush_ships(self, reply_topic: str) -> None:
        self._ship_timers.pop(reply_topic, None)
        payloads = self._ship_queue.pop(reply_topic, None)
        if not payloads:
            return
        self.stats["envelopes"] += 1
        if len(payloads) == 1:
            self.runtime.publish(reply_topic, payloads[0])
            return
        self.stats["batched_envelopes"] += 1
        self._batched_counter.inc(len(payloads))
        self.runtime.publish(reply_topic,
                             wire.encode_kv_batch(payloads))

    def stop(self) -> None:
        for reply_topic, timer in list(self._ship_timers.items()):
            self.runtime.event.remove_timer_handler(timer)
            self._ship_timers.pop(reply_topic, None)
            self._flush_ships(reply_topic)   # owed transfers ship now
        if self._flatout:
            self.runtime.event.remove_flatout_handler(self.decoder.pump)
        else:
            self.decoder.detach(self.runtime.event)
        super().stop()


def _chain_wire_blocks(cache, keys) -> list:
    """Cached chain blocks -> wire block payloads (host ndarrays)."""
    blocks = []
    for node in cache.nodes(keys):
        # block_rows reads the node's storage home — its own rows
        # in dense mode, the block POOL in paged mode (ISSUE 15:
        # harvest left the rows in pool blocks, so shipping is the
        # first and only host copy they ever pay)
        k_rows, v_rows = cache.block_rows(node)
        layers = []
        for k_leaf, v_leaf in zip(k_rows, v_rows):
            layers.append({"k": _to_host(k_leaf),
                           "v": _to_host(v_leaf)})
        blocks.append(layers)
    return blocks


def _prompt_cap(decoder) -> int:
    """The prompt length `decoder.submit` will actually admit (its
    tail-truncation cap).  Both sides of the split truncate with THIS
    formula before keying anything, so the harvested chain, the
    shipped tokens, and the decode-side probe always agree — a
    silently truncated prompt would otherwise key a chain the other
    side never looks up (review finding)."""
    if decoder.prefill_chunk:
        return decoder.max_seq - 1
    return min(decoder.max_seq - 1, decoder.prefill_buckets[-1])


def _to_host(leaf):
    """Device rows -> host ndarrays for the wire (int8 dicts leaf-wise;
    the bytes ship exactly as the donor decoder stored them)."""
    if isinstance(leaf, dict):
        return {"q": np.asarray(leaf["q"]), "s": np.asarray(leaf["s"])}
    return np.asarray(leaf)


def _copy_host(leaf):
    """Wire ndarrays -> OWNED host arrays in the cache storage layout.
    Deliberately NOT device_put here: installing a 576-token transfer
    as ~100 per-leaf device transfers on the event loop stalled decode
    rounds measurably (found live); the prefix-admit's concat ships
    each admitted chain as ONE transfer per layer instead, and only
    for chains actually admitted.  The copy drops the wire envelope's
    zero-copy views so a cached block never pins a whole received
    payload alive."""
    if isinstance(leaf, dict):
        return {"q": np.array(leaf["q"]), "s": np.array(leaf["s"])}
    return np.array(leaf)


class PrefillClient:
    """The decode-side of the split: remote prefill with a local
    fallback ladder.

    submit() routes the prompt to a discovered prefill runtime
    (deadline-aware, least-loaded under pressure), and on the
    KV-transfer reply installs the chain into the decode decoder's
    PrefixKVCache and submits the request — the prefix-admit path
    copies the chain into the slot with one scatter, so decode-pool
    prefill work shrinks to the ragged suffix.  Failures degrade, in
    order: retry against another candidate, then LOCAL prefill on the
    decode runtime itself.  Every rung of the ladder is counted;
    no rung drops the request.

    Single-threaded on the owning runtime's event engine, like the
    decoder it feeds."""

    def __init__(self, runtime, decoder, services_cache=None,
                 name: str = "disagg",
                 transfer_timeout: float = 5.0, retries: int = 1,
                 urgent_budget_s: float = 1.0,
                 min_remote_tokens: int | None = None,
                 registry=None):
        if decoder.prefix_cache is None and \
                not getattr(decoder, "paged", False):
            raise ValueError(
                "PrefillClient needs a decoder with a bound "
                "PrefixKVCache, or a paged decoder (the shipped KV "
                "has to land somewhere: cache chain or direct "
                "slot-table install)")
        self.runtime = runtime
        decoder._require("kv_wire",
                         "the disaggregated KV wire (serving_disagg)")
        self.decoder = decoder
        # cache may be None on a paged decoder (ISSUE 15 satellite):
        # shipped KV then lands via install_shipped_blocks — pool
        # blocks aliased straight into the request's slot table, no
        # prefix cache in the loop
        self.cache = decoder.prefix_cache
        self.block_tokens = self.cache.block_tokens \
            if self.cache is not None else decoder.kv_block
        self.name = str(name)
        self.logger = get_logger(f"disagg.client.{name}")
        self.transfer_timeout = float(transfer_timeout)
        self.retries = max(0, int(retries))
        # prompts shorter than one block have nothing to ship — going
        # remote would pay a transfer RTT for zero cached tokens
        self.min_remote_tokens = int(min_remote_tokens) \
            if min_remote_tokens is not None \
            else self.block_tokens
        self._registry = registry or default_registry()
        self.router = DeadlineRouter(urgent_budget_s=urgent_budget_s,
                                     name=name,
                                     registry=self._registry)
        self.loads: dict[str, int] = {}     # topic_path -> in flight
        self._endpoints: dict[str, str | None] = {}
        self._pending: dict[str, dict] = {}
        self.reply_topic = \
            f"{runtime.topic_path}/0/kv.{uuid.uuid4().hex[:8]}"
        runtime.add_message_handler(self._on_reply, self.reply_topic,
                                    binary=True)
        self.stats = MirroredStats(
            {"transfers": 0, "transfer_bytes": 0, "installs": 0,
             "installed_blocks": 0, "handle_blocks": 0,
             "raw_blocks": 0, "retries": 0, "transfer_timeouts": 0,
             "transfer_corrupt": 0, "layout_mismatch": 0,
             "local_fallbacks": 0, "local_short": 0,
             "local_no_pool": 0, "local_cached": 0,
             "install_shed": 0, "direct_installs": 0,
             "batched_replies": 0, "chunk_installs": 0,
             "chunk_blocks": 0, "chunk_dropped": 0,
             "chunk_streamed": 0, "transfer_overlap_s": 0.0},
            metric="disagg_client_events_total",
            help="disaggregated serving client events by kind",
            registry=self._registry,
            skip=("transfer_bytes", "transfer_overlap_s"),
            labels={"client": name})
        self._transfer_seconds = self._registry.histogram(
            "disagg_transfer_seconds",
            "prefill request -> installed KV wall seconds",
            labels={"client": name})
        from collections import deque
        self.transfer_samples: deque = deque(maxlen=4096)
        self._cache_handler = None
        if services_cache is not None:
            self._services_cache = services_cache
            self._cache_handler = self._on_discovery
            # protocol AND role: a pipeline tagged role=prefill (the
            # PE role parameter tags its whole pipeline record) has no
            # `prefill` RPC — routing transfers at it would stall them
            # for a full timeout each (review finding)
            services_cache.add_handler(
                self._cache_handler,
                ServiceFilter(protocol=str(PROTOCOL_PREFILL),
                              tags=[role_tag(ROLE_PREFILL)]))

    # -- discovery ---------------------------------------------------------
    def _on_discovery(self, command, fields) -> None:
        if command == "add":
            self.loads.setdefault(fields.topic_path, 0)
            endpoint = ServiceTags.to_dict(fields.tags).get("peer")
            self._endpoints[fields.topic_path] = endpoint
            if endpoint and self.runtime.peer is not None:
                # pin the transfer path onto a direct channel: our
                # prefill requests to its /in, its KV replies to our
                # reply topic.  Broker stays the standing fallback.
                try:
                    self.runtime.peer.negotiate(
                        fields.topic_path, endpoint,
                        pin_topics=[f"{fields.topic_path}/in"],
                        reply_topics=[self.reply_topic])
                except Exception:
                    self.logger.exception(
                        "disagg %s: peer negotiation with %s failed; "
                        "broker path stays", self.name,
                        fields.topic_path)
        elif command == "remove":
            self.loads.pop(fields.topic_path, None)
            self._endpoints.pop(fields.topic_path, None)
            if self.runtime.peer is not None:
                self.runtime.peer.release(f"{fields.topic_path}/in")

    def add_candidate(self, topic_path: str,
                      endpoint: str | None = None) -> None:
        """Manual registration (tests, static fleets without a
        services cache)."""
        self.loads.setdefault(topic_path, 0)
        self._endpoints[topic_path] = endpoint

    # -- submit path -------------------------------------------------------
    def submit(self, request_id: str, prompt, max_new_tokens: int,
               callback, deadline: float | None = None,
               tenant: str | None = None, on_refused=None) -> bool:
        """Route one request through the split.  Returns True when the
        request is IN FLIGHT somewhere (remote transfer pending or
        locally submitted); False only when the decoder's own deadline
        admission refused a synchronous local submit (the caller owns
        that refusal, exactly like ContinuousDecoder.submit)."""
        # truncate with the DECODE decoder's own cap up front: the
        # probe below, the shipped tokens, and the eventual
        # decoder.submit must all key the same prompt (a decoder that
        # truncated AFTER the probe would never match the installed
        # chain)
        prompt = ([int(t) for t in prompt] or
                  [0])[-_prompt_cap(self.decoder):]
        tenant_key = str(tenant or "")
        # synchronous local rungs return the refusal to the CALLER
        # (notify=False): invoking on_refused too would signal one
        # shed twice (review finding)
        if len(prompt) < self.min_remote_tokens:
            self.stats["local_short"] += 1
            return self._local(request_id, prompt, max_new_tokens,
                               callback, deadline, tenant, on_refused,
                               notify=False)
        have = 0
        if self.cache is not None:
            _, have = self.cache.match(tenant_key, prompt)
            complete = (len(prompt) // self.block_tokens) * \
                self.block_tokens
            if have < complete and self.cache.tiered:
                # tiered KV (ISSUE 17): the routing probe doubles as
                # the promotion kick — host-resident chain blocks for
                # this prompt start re-landing while the transfer (or
                # local prefill) is still in flight
                self.cache.prefetch(tenant_key, prompt)
            if complete and have >= complete:
                # the decode side already holds the ENTIRE chain
                # (session KV, a repeated prompt): a remote hop would
                # ship zero bytes — prefix-admit locally, the cached
                # population.  A cacheless pool holds nothing between
                # requests, so have stays 0 there and every prompt
                # ships whole.
                self.stats["local_cached"] += 1
                return self._local(request_id, prompt, max_new_tokens,
                                   callback, deadline, tenant,
                                   on_refused, notify=False)
        remaining = None
        if deadline is not None:
            remaining = float(deadline) - time.monotonic()
        target = self.router.route(self.loads, remaining)
        if target is None:
            self.stats["local_no_pool"] += 1
            return self._local(request_id, prompt, max_new_tokens,
                               callback, deadline, tenant, on_refused,
                               notify=False)
        transfer_id = f"kv-{uuid.uuid4().hex[:12]}"
        entry = {
            "request_id": str(request_id), "prompt": prompt,
            "max_new": int(max_new_tokens), "callback": callback,
            "deadline": deadline, "tenant": tenant,
            "on_refused": on_refused, "attempts": 0,
            "started": time.perf_counter(),
            "trace": tracing.current_trace(), "target": target,
        }
        self._pending[transfer_id] = entry
        self._send(transfer_id, entry, target, have)
        return True

    def _send(self, transfer_id: str, entry: dict, target: str,
              have: int) -> None:
        entry["target"] = target
        entry["timer"] = self.runtime.event.add_oneshot_handler(
            lambda: self._transfer_expired(transfer_id),
            self.transfer_timeout)
        self.loads[target] = self.loads.get(target, 0) + 1
        self.stats["transfers"] += 1
        context = entry.get("trace")
        payload = wire.encode_envelope(
            "prefill",
            [transfer_id, self.reply_topic,
             str(entry["tenant"] or ""), str(int(have)),
             {"tokens": np.asarray(entry["prompt"], np.int32)}],
            trace=context.to_fields(self.runtime.event.clock.now())
            if context is not None else None)
        self.runtime.publish(f"{target}/in", payload)

    def _settle(self, transfer_id: str):
        entry = self._pending.pop(transfer_id, None)
        if entry is None:
            return None
        timer = entry.pop("timer", None)
        if timer is not None:
            self.runtime.event.remove_timer_handler(timer)
        target = entry.get("target")
        if target in self.loads:
            self.loads[target] = max(0, self.loads[target] - 1)
        return entry

    def _drop_chunks(self, entry: dict) -> None:
        """Forget a transfer's streamed-chunk progress (ISSUE 17).
        Cache-path installs stay — they are content-addressed and a
        retry's `have` probe reuses them — but direct pool blocks are
        owned by the stream and must not leak when it abandons."""
        entry.pop("chunk_next", None)
        entry.pop("chunk_first", None)
        entry.pop("chunk_base", None)
        ids = entry.pop("direct_ids", None)
        if ids:
            self.decoder.pool.release_blocks(
                ids, tenant=str(entry.get("tenant") or ""))

    # -- the fallback ladder ----------------------------------------------
    def _transfer_expired(self, transfer_id: str) -> None:
        entry = self._pending.get(transfer_id)
        if entry is None:
            return
        entry.pop("timer", None)
        target = entry.get("target")
        if target in self.loads:
            self.loads[target] = max(0, self.loads[target] - 1)
        self.stats["transfer_timeouts"] += 1
        if entry["attempts"] < self.retries:
            # rung 1: retry against ANOTHER candidate (the one that
            # timed out keeps its request dedup-able server-side; a
            # late duplicate transfer just re-confirms cached blocks)
            entry["attempts"] += 1
            others = {c: l for c, l in self.loads.items()
                      if c != target}
            remaining = None
            if entry["deadline"] is not None:
                remaining = float(entry["deadline"]) - time.monotonic()
            retry_target = self.router.route(others or self.loads,
                                             remaining)
            if retry_target is not None:
                self.stats["retries"] += 1
                self._drop_chunks(entry)   # the retry streams afresh
                have = 0
                if self.cache is not None:
                    _, have = self.cache.match(
                        str(entry["tenant"] or ""), entry["prompt"])
                self._send(transfer_id, entry, retry_target, have)
                return
        # rung 2: local prefill — counted, never dropped
        self._pending.pop(transfer_id, None)
        self._drop_chunks(entry)
        self.stats["local_fallbacks"] += 1
        self.logger.warning(
            "disagg %s: transfer %s to %s gave up after %d attempt(s); "
            "prefilling locally", self.name, transfer_id, target,
            entry["attempts"] + 1)
        self._local(entry["request_id"], entry["prompt"],
                    entry["max_new"], entry["callback"],
                    entry["deadline"], entry["tenant"],
                    entry["on_refused"])

    def _local(self, request_id, prompt, max_new, callback, deadline,
               tenant, on_refused, notify: bool = True) -> bool:
        """Local-prefill rung.  `notify` fires on_refused on a shed —
        True only on ASYNC paths (timeout fallback, reply install,
        teardown) where submit() has long returned; synchronous rungs
        return the refusal instead, so the caller is signalled exactly
        once either way."""
        accepted = self.decoder.submit(request_id, prompt, max_new,
                                       callback, deadline=deadline,
                                       tenant=tenant)
        if not accepted:
            self.stats["install_shed"] += 1
            if notify and on_refused is not None:
                on_refused(request_id)
        return accepted

    # -- KV admit (the reply path) -----------------------------------------
    def _on_reply(self, _topic, payload) -> None:
        try:
            command, params = wire.decode_envelope(payload)
        except wire.WireError as exc:
            # chaos truncation / foreign payload: drop it — the
            # transfer timer retries, then the ladder prefills locally
            self.stats["transfer_corrupt"] += 1
            self.logger.warning("disagg %s: corrupt KV transfer "
                                "dropped: %s", self.name, exc)
            return
        if command == wire.KV_BATCH_COMMAND:
            # coalesced same-destination transfers (ISSUE 15
            # satellite): unwrap and run each member through the SAME
            # validation + install path as a lone envelope — a corrupt
            # member fails alone, its siblings still land
            try:
                members = wire.kv_batch_members(command, params)
            except wire.WireError as exc:
                self.stats["transfer_corrupt"] += 1
                self.logger.warning(
                    "disagg %s: corrupt KV transfer batch dropped: %s",
                    self.name, exc)
                return
            self.stats["batched_replies"] += 1
            for member in members:
                try:
                    inner_command, inner_params = \
                        wire.decode_envelope(member)
                except wire.WireError as exc:
                    self.stats["transfer_corrupt"] += 1
                    self.logger.warning(
                        "disagg %s: corrupt batch member dropped: %s",
                        self.name, exc)
                    continue
                self._handle_transfer(member, inner_command,
                                      inner_params)
            return
        self._handle_transfer(payload, command, params)

    def _handle_transfer(self, payload, command, params) -> None:
        try:
            out = wire.validate_kv_transfer_params(command, params)
        except wire.WireError as exc:
            self.stats["transfer_corrupt"] += 1
            self.logger.warning("disagg %s: corrupt KV transfer "
                                "dropped: %s", self.name, exc)
            return
        if not out.get("final", True):
            # chunk-streamed member (ISSUE 17): install incrementally
            # WITHOUT settling — the final envelope still owes
            # first_token and the decode submit
            self._handle_chunk(payload, out)
            return
        entry = self._settle(out["transfer_id"])
        if entry is None:
            return              # late duplicate after timeout/fallback
        chunk_first = entry.get("chunk_first")
        # out["first_token"] is deliberately unused: the decode-side
        # suffix extend recomputes the first token, so greedy parity
        # never depends on donor state — the field is a wire-level
        # diagnostic (tests compare it against the local stream)
        elapsed = time.perf_counter() - entry["started"]
        self.stats["transfer_bytes"] += len(payload)
        self._transfer_seconds.observe(elapsed)
        # audited: deque(maxlen=4096) bounds this sample window
        self.transfer_samples.append(elapsed)
        tenant_key = str(entry["tenant"] or "")
        local_layout = self.cache.wire_layout() \
            if self.cache is not None else self.decoder.kv_wire_layout()
        if out["blocks"] and \
                tuple(str(f) for f in out["layout"]) != local_layout:
            self.stats["layout_mismatch"] += 1
            self.stats["local_fallbacks"] += 1
            self.logger.warning(
                "disagg %s: transfer %s layout %r does not match the "
                "decode cache %r; prefilling locally", self.name,
                out["transfer_id"], out["layout"], local_layout)
            self._drop_chunks(entry)
            self._local(entry["request_id"], entry["prompt"],
                        entry["max_new"], entry["callback"],
                        entry["deadline"], entry["tenant"],
                        entry["on_refused"])
            return
        blocks = self._landing_blocks(out["blocks"])
        direct_ids: list = []
        try:
            if self.cache is not None:
                installed = self.cache.install_chain(
                    tenant_key, out["tokens"], out["start_block"],
                    blocks)
            else:
                # direct slot-table install (ISSUE 15 satellite): the
                # cacheless decode pool lands the chain in pool blocks
                # and hands the ids to submit() for slot aliasing.
                # Streamed chunks already landed a contiguous prefix;
                # the final span must continue it exactly (ordered-
                # cursor guard) or the prefix alone is used.
                prior = entry.get("direct_ids") or []
                start = out["start_block"]
                if prior and entry.get("chunk_next") == start:
                    _, ids = self.decoder.install_shipped_blocks(
                        out["tokens"], start, blocks,
                        tenant=tenant_key)
                    direct_ids = prior + ids
                elif prior:
                    self.stats["chunk_dropped"] += 1
                    direct_ids = prior
                else:
                    if start != 0:
                        raise ValueError(
                            "direct install cannot start mid-chain "
                            f"(start_block={start}) with no streamed "
                            "prefix")
                    _, direct_ids = \
                        self.decoder.install_shipped_blocks(
                            out["tokens"], 0, blocks,
                            tenant=tenant_key)
                entry.pop("direct_ids", None)
                installed = len(direct_ids)
                self.stats["direct_installs"] += 1
        except (ValueError, TypeError, IndexError) as exc:
            # schema-legal but geometry-wrong blocks (wrong layer
            # count / head extents) are refused BEFORE any row lands —
            # a poisoned chain would wedge the decode pump at its next
            # hit.  Same ladder as a corrupt payload: prefill locally.
            self.stats["transfer_corrupt"] += 1
            self.stats["local_fallbacks"] += 1
            self.logger.warning(
                "disagg %s: transfer %s refused at install (%s); "
                "prefilling locally", self.name, out["transfer_id"],
                exc)
            self._drop_chunks(entry)
            self._local(entry["request_id"], entry["prompt"],
                        entry["max_new"], entry["callback"],
                        entry["deadline"], entry["tenant"],
                        entry["on_refused"])
            return
        self.stats["installs"] += 1
        self.stats["installed_blocks"] += installed
        # a streamed transfer's final start_block sits at the chunk
        # cursor, not the handle boundary — only blocks below the
        # stream's low-water mark crossed as handles
        handle = out["start_block"]
        base = entry.get("chunk_base")
        if base is not None:
            handle = min(handle, int(base))
        self.stats["handle_blocks"] += handle
        self.stats["raw_blocks"] += len(out["blocks"])
        if chunk_first is not None:
            # the stream began landing KV while the donor was still
            # prefilling: everything between the first chunk and this
            # final envelope was transfer time hidden behind compute
            self.stats["chunk_streamed"] += 1
            self.stats["transfer_overlap_s"] += \
                max(0.0, time.perf_counter() - chunk_first)
        trc = tracing.tracer
        if trc.enabled and entry.get("trace") is not None:
            trc.record("kv_transfer", entry["started"], elapsed,
                       context=entry["trace"], cat="disagg",
                       proc=self.name,
                       span_id=tracing.new_span_id(),
                       args={"bytes": len(payload),
                             "raw_blocks": len(out["blocks"]),
                             "handle_blocks": out["start_block"],
                             "installed": installed})
        # the decode-side submit: the prefix probe longest-matches the
        # just-installed chain (paged: ALIASES its pool blocks —
        # zero-copy), and only the ragged suffix prefills here.  A
        # cacheless pool instead hands the installed ids to the
        # request for direct slot-table aliasing.  Label "remote" so
        # TTFT sketches and journeys carry the population (ISSUE 14).
        with tracing.activate(entry.get("trace")):
            if self.cache is None:
                covered = installed * self.decoder.kv_block
                self._submit_installed(entry,
                                       kv_blocks=(covered, direct_ids))
            else:
                self._submit_installed(entry)

    def _handle_chunk(self, payload, out: dict) -> None:
        """Install one streamed chunk for a still-pending transfer
        (ISSUE 17).  Chunks are best-effort accelerant: any anomaly
        (gap, layout drift, install refusal) drops the CHUNK and lets
        the final envelope's full fallback ladder own correctness —
        a dropped chunk can shorten the streamed prefix, never poison
        the chain (cache installs are content-addressed; direct
        installs keep only a contiguous-from-zero prefix)."""
        transfer_id = out["transfer_id"]
        entry = self._pending.get(transfer_id)
        if entry is None:
            return          # late chunk after settle/timeout/fallback
        local_layout = self.cache.wire_layout() \
            if self.cache is not None else self.decoder.kv_wire_layout()
        if not out["blocks"] or \
                tuple(str(f) for f in out["layout"]) != local_layout:
            self.stats["chunk_dropped"] += 1
            return
        expected = entry.get("chunk_next")
        if expected is not None and out["start_block"] != expected:
            # ordered-cursor guard: a lost/corrupt sibling left a gap;
            # later chunks no longer extend the landed prefix
            self.stats["chunk_dropped"] += 1
            return
        if self.cache is None and expected is None and \
                out["start_block"] != 0:
            # a direct (cacheless) stream is only usable as a
            # contiguous-from-zero prefix
            self.stats["chunk_dropped"] += 1
            return
        try:
            if self.cache is not None:
                installed = self.cache.install_chain(
                    str(entry["tenant"] or ""), out["tokens"],
                    out["start_block"],
                    self._landing_blocks(out["blocks"]))
            else:
                _, ids = self.decoder.install_shipped_blocks(
                    out["tokens"], out["start_block"],
                    self._landing_blocks(out["blocks"]),
                    tenant=str(entry["tenant"] or ""))
                entry.setdefault("direct_ids", []).extend(ids)
                installed = len(ids)
        except (ValueError, TypeError, IndexError) as exc:
            self.stats["chunk_dropped"] += 1
            self.logger.warning(
                "disagg %s: streamed chunk for %s refused at install "
                "(%s); dropped", self.name, transfer_id, exc)
            return
        if "chunk_first" not in entry:
            entry["chunk_first"] = time.perf_counter()
            # the stream's low-water mark: blocks below it crossed as
            # handles, blocks at/above it as raw streamed bytes — the
            # final envelope's handle accounting keys on this
            entry["chunk_base"] = out["start_block"]
        entry["chunk_next"] = out["start_block"] + len(out["blocks"])
        self.stats["chunk_installs"] += 1
        self.stats["chunk_blocks"] += installed
        self.stats["raw_blocks"] += len(out["blocks"])
        self.stats["transfer_bytes"] += len(payload)
        # a streaming donor is demonstrably alive: restart the
        # transfer timeout per chunk so a long prompt's stream is not
        # killed mid-flight by a budget sized for one envelope
        timer = entry.pop("timer", None)
        if timer is not None:
            self.runtime.event.remove_timer_handler(timer)
            entry["timer"] = self.runtime.event.add_oneshot_handler(
                lambda: self._transfer_expired(transfer_id),
                self.transfer_timeout)

    def _landing_blocks(self, wire_blocks) -> list:
        if self.cache is not None and not self.cache.paged:
            # dense cache: owned host copies (per-leaf device_puts on
            # the event loop stalled decode rounds — PR 14 finding);
            # the admit-time concat ships one transfer per layer
            return [{"k": [_copy_host(layer["k"]) for layer in block],
                     "v": [_copy_host(layer["v"]) for layer in block]}
                    for block in wire_blocks]
        # paged landings (ISSUE 15) write the wire views straight
        # into pool blocks — ONE device scatter per layer, no host
        # copy in between: the transferred bytes land exactly once
        return [{"k": [layer["k"] for layer in block],
                 "v": [layer["v"] for layer in block]}
                for block in wire_blocks]

    def _submit_installed(self, entry: dict,
                          kv_blocks: tuple | None = None) -> None:
        accepted = self.decoder.submit(
            entry["request_id"], entry["prompt"], entry["max_new"],
            entry["callback"], deadline=entry["deadline"],
            tenant=entry["tenant"], prefill_label="remote",
            kv_blocks=kv_blocks)
        if not accepted:
            self.stats["install_shed"] += 1
            if kv_blocks is not None and kv_blocks[1]:
                # ownership never transferred: the shed request must
                # not leak its pre-installed pool blocks
                self.decoder.pool.release_blocks(
                    kv_blocks[1], tenant=str(entry["tenant"] or ""))
            if entry["on_refused"] is not None:
                entry["on_refused"](entry["request_id"])

    def handle_hit_rate(self) -> float:
        """Fraction of transferred chain blocks that crossed as
        handles instead of raw KV bytes (decode-held chains)."""
        total = self.stats["handle_blocks"] + self.stats["raw_blocks"]
        return self.stats["handle_blocks"] / total if total else 0.0

    def pending_count(self) -> int:
        return len(self._pending)

    def stop(self) -> None:
        for transfer_id in list(self._pending):
            entry = self._settle(transfer_id)
            if entry is not None:
                # teardown owes every in-flight request a local home
                self._drop_chunks(entry)
                self.stats["local_fallbacks"] += 1
                self._local(entry["request_id"], entry["prompt"],
                            entry["max_new"], entry["callback"],
                            entry["deadline"], entry["tenant"],
                            entry["on_refused"])
        if self._cache_handler is not None:
            self._services_cache.remove_handler(self._cache_handler)
            self._cache_handler = None
        if self.runtime.peer is not None:
            # this client's uuid reply topic must not be re-pinned on
            # later redials of the shared channel
            self.runtime.peer.unregister_reply_topic(self.reply_topic)
        self.runtime.remove_message_handler(self._on_reply,
                                            self.reply_topic)


class SessionMigrator:
    """Both halves of graceful-drain session KV migration (ISSUE 19).

    A retiring serving runtime's sessions — pinned prefix-cache chains
    plus their SessionTable records — ship to a drain destination so a
    migrated conversation's NEXT turn is a prefix hit there, not a full
    re-prefill.  One instance serves both roles over one binary topic
    ({runtime.topic_path}/migrate):

      source:  migrate(dest) offers each live session
               (wire.encode_kv_migrate: tokens the pinned chain covers
               + the table's history), and on the destination's ack
               ships the chain as ordinary chunk-streamed KV_TRANSFER
               envelopes — blocks the destination already holds
               (content-addressed) cross as handles, host-tier rows are
               promoted first (promote_for), and the done leg releases
               the local pin and table record;
      dest:    probes its cache for the offered chain, acks with its
               resident-block count, installs arriving chunks with the
               ordered-cursor guard, and on the final envelope re-pins
               the chain under the session handle, re-creates the table
               record, and sends done.

    Failure degrades, never corrupts: a timed-out transfer keeps the
    session at the source (crash re-materialization from the state
    plane still covers it), a shed destination table.create releases
    the freshly-taken pin and withholds done, and a layout/gap anomaly
    drops chunks — the destination then lands history-only and the
    first turn there re-prefills.  Single-threaded on the owning
    runtime's engine, like everything else in this plane."""

    def __init__(self, runtime, cache, table=None, name: str = "migrate",
                 chunk_blocks: int = 8, transfer_timeout: float = 5.0,
                 registry=None):
        if cache is None:
            raise ValueError("SessionMigrator needs a PrefixKVCache "
                             "(the pinned chains ARE the cargo)")
        self.runtime = runtime
        self.cache = cache
        self.table = table
        self.name = str(name)
        self.logger = get_logger(f"disagg.migrate.{name}")
        self.chunk_blocks = max(1, int(chunk_blocks))
        self.transfer_timeout = float(transfer_timeout)
        self._registry = registry or default_registry()
        self.topic = f"{runtime.topic_path}/migrate"
        runtime.add_message_handler(self._on_message, self.topic,
                                    binary=True)
        self._outbound: dict[str, dict] = {}     # source-role transfers
        self._inbound: dict[str, dict] = {}      # destination-role
        self._done_callback = None
        self.stats = MirroredStats(
            {"offers": 0, "received": 0, "acks": 0, "chunks": 0,
             "shipped_blocks": 0, "handle_blocks": 0,
             "installed_blocks": 0, "landed": 0, "migrated": 0,
             "refused": 0, "expired": 0, "dropped_chunks": 0,
             "corrupt": 0},
            metric="kv_migrate_events_total",
            help="session KV migration events by kind",
            registry=self._registry, labels={"migrator": self.name})

    # -- source role -------------------------------------------------------
    def migrate(self, dest_topic: str, on_done=None) -> int:
        """Offer every live session to the migrator at `dest_topic`
        (a peer's .topic).  Returns the number of offers sent;
        `on_done(self)` fires once when every offer has settled (done
        leg or timeout) — with zero sessions it fires immediately."""
        self._done_callback = on_done
        sessions = self.table.items() if self.table is not None else []
        sent = 0
        for tenant, sid, payload in sessions:
            history, kv_tokens = [], 0
            if isinstance(payload, dict):
                history = [int(t) for t in payload.get("history", ())]
                kv_tokens = max(0, int(payload.get("kv_tokens", 0)))
            # the pinned chain covers a history prefix (session_store
            # matched the history and recorded the hit length)
            tokens = history[:kv_tokens]
            transfer_id = f"mig-{uuid.uuid4().hex[:12]}"
            entry = {"tenant": str(tenant), "sid": str(sid),
                     "tokens": tokens, "history": history,
                     "dest": str(dest_topic)}
            entry["timer"] = self.runtime.event.add_oneshot_handler(
                lambda tid=transfer_id: self._expired(tid),
                self.transfer_timeout)
            self._outbound[transfer_id] = entry
            context = tracing.current_trace()
            self.runtime.publish(str(dest_topic), wire.encode_kv_migrate(
                transfer_id, str(tenant), str(sid), self.topic,
                np.asarray(tokens, np.int32),
                np.asarray(history, np.int32),
                trace=context.to_fields(self.runtime.event.clock.now())
                if context is not None else None))
            self.stats["offers"] += 1
            sent += 1
        if sent == 0:
            self._maybe_finished()
        return sent

    def _restart_timer(self, entry: dict, transfer_id: str,
                       inbound: bool = False) -> None:
        timer = entry.pop("timer", None)
        if timer is not None:
            self.runtime.event.remove_timer_handler(timer)
        entry["timer"] = self.runtime.event.add_oneshot_handler(
            lambda: self._expired(transfer_id, inbound=inbound),
            self.transfer_timeout)

    def _expired(self, transfer_id: str, inbound: bool = False) -> None:
        table = self._inbound if inbound else self._outbound
        entry = table.pop(transfer_id, None)
        if entry is None:
            return
        entry.pop("timer", None)
        self.stats["expired"] += 1
        self.logger.warning(
            "migrate %s: transfer %s (%s/%s) timed out; the session "
            "stays %s", self.name, transfer_id, entry["tenant"],
            entry["sid"], "unlanded" if inbound else "at the source")
        if inbound:
            # a half-streamed chain is cached (content-addressed, no
            # harm) but the session never landed — no pin, no record
            return
        self._maybe_finished()

    def _maybe_finished(self) -> None:
        if self._outbound or self._done_callback is None:
            return
        callback, self._done_callback = self._done_callback, None
        callback(self)

    def _settle(self, transfer_id: str, inbound: bool = False):
        table = self._inbound if inbound else self._outbound
        entry = table.pop(transfer_id, None)
        if entry is None:
            return None
        timer = entry.pop("timer", None)
        if timer is not None:
            self.runtime.event.remove_timer_handler(timer)
        return entry

    # -- wire dispatch -----------------------------------------------------
    def _on_message(self, _topic, payload) -> None:
        try:
            command, params = wire.decode_envelope(payload)
        except wire.WireError as exc:
            self.stats["corrupt"] += 1
            self.logger.warning("migrate %s: corrupt envelope dropped: "
                                "%s", self.name, exc)
            return
        try:
            if command == wire.KV_MIGRATE_COMMAND:
                self._on_offer(command, params)
            elif command == wire.KV_MIGRATE_ACK_COMMAND:
                self._on_ack(command, params)
            elif command == wire.KV_MIGRATE_DONE_COMMAND:
                self._on_done_leg(command, params)
            elif command == wire.KV_TRANSFER_COMMAND:
                self._on_transfer(command, params)
            else:
                self.stats["corrupt"] += 1
                self.logger.warning("migrate %s: unexpected command %r "
                                    "dropped", self.name, command)
        except wire.WireError as exc:
            self.stats["corrupt"] += 1
            self.logger.warning("migrate %s: malformed %s dropped: %s",
                                self.name, command, exc)

    # -- destination role --------------------------------------------------
    def _on_offer(self, command, params) -> None:
        out = wire.validate_kv_migrate_params(command, params)
        transfer_id = out["transfer_id"]
        tenant = out["tenant"]
        tokens = [int(t) for t in np.asarray(out["tokens"])]
        self.stats["received"] += 1
        have = 0
        if tokens:
            if self.cache.tiered:
                # an earlier migration/demotion may have left this
                # chain host-resident HERE — promote before probing so
                # the ack's have mark spares those blocks the wire.
                # promote_for uses admit semantics ((len-1)//block: the
                # last position's KV is recomputed at admit) but the
                # migrator moves WHOLE chains — extend by a sentinel so
                # the final block promotes too
                self.cache.promote_for(tenant, tokens + tokens[-1:])
            _, have = self.cache.match(tenant, tokens)
        block = self.cache.block_tokens
        entry = {"tenant": tenant, "sid": out["sid"], "tokens": tokens,
                 "history": [int(t) for t in np.asarray(out["history"])],
                 "reply_topic": out["reply_topic"],
                 "cursor": None, "installed": 0}
        self._inbound[transfer_id] = entry
        self._restart_timer(entry, transfer_id, inbound=True)
        context = tracing.current_trace()
        self.runtime.publish(
            out["reply_topic"],
            wire.encode_kv_migrate_reply(
                wire.KV_MIGRATE_ACK_COMMAND, transfer_id, have // block,
                trace=context.to_fields(self.runtime.event.clock.now())
                if context is not None else None))

    def _on_transfer(self, command, params) -> None:
        out = wire.validate_kv_transfer_params(command, params)
        transfer_id = out["transfer_id"]
        entry = self._inbound.get(transfer_id)
        if entry is None:
            return              # late chunk after timeout
        cache = self.cache
        installed = 0
        usable = not out["blocks"] or \
            tuple(str(f) for f in out["layout"]) == cache.wire_layout()
        if usable and out["blocks"] and entry["cursor"] is not None \
                and out["start_block"] != entry["cursor"]:
            # ordered-cursor guard: a lost sibling left a gap — later
            # chunks no longer extend the landed prefix
            usable = False
        if usable and out["blocks"]:
            try:
                installed = cache.install_chain(
                    entry["tenant"], out["tokens"], out["start_block"],
                    self._landing(out["blocks"]))
                entry["cursor"] = out["start_block"] + len(out["blocks"])
                entry["installed"] += installed
                self.stats["installed_blocks"] += installed
                ledger = getattr(cache, "_ledger", None)
                if ledger is not None and installed:
                    ledger.event("migrate_in", installed)
            except (ValueError, TypeError, IndexError) as exc:
                self.stats["dropped_chunks"] += 1
                self.logger.warning(
                    "migrate %s: transfer %s chunk refused at install "
                    "(%s); dropped", self.name, transfer_id, exc)
        elif out["blocks"]:
            self.stats["dropped_chunks"] += 1
        if not out["final"]:
            self._restart_timer(entry, transfer_id, inbound=True)
            return
        self._land(transfer_id, self._settle(transfer_id, inbound=True))

    def _land(self, transfer_id: str, entry: dict) -> None:
        """Final envelope arrived: pin the (partially or fully) landed
        chain under the session handle, re-create the table record, and
        send done.  A shed create withholds done — the source's timeout
        then keeps the session there instead of deleting the only
        surviving copy."""
        cache = self.cache
        tenant, sid = entry["tenant"], entry["sid"]
        leaf, kv_tokens = cache.session_store(tenant, sid,
                                              entry["history"])
        if self.table is not None and not self.table.create(
                tenant, sid, {"history": entry["history"],
                              "kv": leaf or "",
                              "kv_tokens": kv_tokens}):
            cache.session_release(tenant, sid)
            self.stats["refused"] += 1
            self.logger.warning(
                "migrate %s: transfer %s refused — destination table "
                "shed (%s/%s); withholding done", self.name,
                transfer_id, tenant, sid)
            return
        self.stats["landed"] += 1
        context = tracing.current_trace()
        self.runtime.publish(
            entry["reply_topic"],
            wire.encode_kv_migrate_reply(
                wire.KV_MIGRATE_DONE_COMMAND, transfer_id,
                entry["installed"],
                trace=context.to_fields(self.runtime.event.clock.now())
                if context is not None else None))

    def _landing(self, wire_blocks) -> list:
        if not self.cache.paged:
            # dense cache: owned host copies (see PrefillClient — the
            # admit-time concat device-puts once per layer)
            return [{"k": [_copy_host(layer["k"]) for layer in block],
                     "v": [_copy_host(layer["v"]) for layer in block]}
                    for block in wire_blocks]
        return [{"k": [layer["k"] for layer in block],
                 "v": [layer["v"] for layer in block]}
                for block in wire_blocks]

    # -- source role, reply legs -------------------------------------------
    def _on_ack(self, command, params) -> None:
        transfer_id, have_blocks = \
            wire.validate_kv_migrate_reply(command, params)
        entry = self._outbound.get(transfer_id)
        if entry is None:
            return
        self.stats["acks"] += 1
        cache = self.cache
        block = cache.block_tokens
        tenant, tokens = entry["tenant"], entry["tokens"]
        if cache.tiered and tokens:
            # demoted session rows must be pool-resident before
            # block_rows can ship them — sync whole-chain promotion
            # (the admit-semantics sentinel again: ship the final
            # block as well, not just the probe-relevant prefix)
            cache.promote_for(tenant, tokens + tokens[-1:])
        keys, hit = cache.match(tenant, tokens)
        start = min(max(0, int(have_blocks)), hit // block)
        end = hit // block
        self.stats["handle_blocks"] += start
        self.stats["shipped_blocks"] += end - start
        ledger = getattr(cache, "_ledger", None)
        if ledger is not None and end > start:
            ledger.event("migrate_out", end - start)
        context = tracing.current_trace()
        trace = context.to_fields(self.runtime.event.clock.now()) \
            if context is not None else None
        # chunk-streamed ship: every envelope carries the full token
        # list (install_chain re-keys from it), blocks in chunk_blocks
        # strides; the final flag rides the last envelope — always
        # sent, even with zero blocks to move, because it is what
        # triggers the destination's land
        cursor = start
        while True:
            upto = min(end, cursor + self.chunk_blocks)
            final = upto >= end
            self.runtime.publish(entry["dest"], wire.encode_kv_transfer(
                transfer_id, tenant, tokens, cursor, block,
                cache.wire_layout(),
                _chain_wire_blocks(cache, keys[cursor:upto]),
                trace=trace, final=final))
            self.stats["chunks"] += 1
            cursor = upto
            if final:
                break
        self._restart_timer(entry, transfer_id)

    def _on_done_leg(self, command, params) -> None:
        transfer_id, _installed = \
            wire.validate_kv_migrate_reply(command, params)
        entry = self._settle(transfer_id)
        if entry is None:
            return
        # the destination owns the session now: drop the local pin and
        # the table record (its demotion hook must NOT fire — remove,
        # not demote)
        self.cache.session_release(entry["tenant"], entry["sid"])
        if self.table is not None:
            self.table.remove(entry["tenant"], entry["sid"],
                              reason="migrated")
        self.stats["migrated"] += 1
        self._maybe_finished()

    def pending_count(self) -> int:
        return len(self._outbound) + len(self._inbound)

    def stop(self) -> None:
        for transfer_id in list(self._outbound):
            self._settle(transfer_id)       # sessions stay local
        for transfer_id in list(self._inbound):
            self._settle(transfer_id, inbound=True)
        self.runtime.remove_message_handler(self._on_message, self.topic)


def two_pool_autoscalers(runtime, prefill_manager, decode_manager,
                         prefill_policy=None, decode_policy=None,
                         interval: float = 2.0,
                         topic_filter: str | None = None):
    """Instantiate the PR 9 autoscaler once per pool, each armed with
    ITS pool's signals (ISSUE 14): the prefill pool scales on prefill
    queue depth (the TTFT backlog — only prefill runtimes publish the
    gauge), the decode pool on fleet-merged ITL p95 (only multi-token
    generation observes ITL; a max_new=1 prefill decoder never does).
    Both pools subscribe the same namespace snapshots, so signal
    isolation comes from arming ONLY families the other pool cannot
    emit — the default worst-of-process signals (mailbox, batch wait,
    hop p95) are disarmed for both, or a prompt burst backlogging the
    prefill runtimes would grow the DECODE pool through their batch
    gauges (review finding).  Returns (prefill_autoscaler,
    decode_autoscaler) — two independent scale loops over two
    independent LifeCycleManagers."""
    from .autoscaler import Autoscaler, ScalePolicy
    prefill_policy = prefill_policy or ScalePolicy(
        prefill_queue_up=8.0, prefill_queue_down=1.0,
        mailbox_depth_up=float("inf"), hop_p95_up=float("inf"),
        batch_wait_up=float("inf"), queue_depth_up=float("inf"))
    decode_policy = decode_policy or ScalePolicy(
        itl_p95_up=0.05, itl_p95_down=0.005,
        mailbox_depth_up=float("inf"), hop_p95_up=float("inf"),
        batch_wait_up=float("inf"), queue_depth_up=float("inf"))
    prefill = Autoscaler(runtime, "prefill-pool",
                         manager=prefill_manager,
                         policy=prefill_policy, interval=interval,
                         topic_filter=topic_filter)
    decode = Autoscaler(runtime, "decode-pool", manager=decode_manager,
                        policy=decode_policy, interval=interval,
                        topic_filter=topic_filter)
    return prefill, decode


class DisaggHarness:
    """A complete two-pool serving plane in one process: registrar +
    peer-enabled prefill and decode runtimes over a MemoryBroker and
    one (real-clock) EventEngine.  The harness behind the
    lat_llama_disagg_* bench rung, scripts/disagg_smoke.py, and the
    chaos tests; `disagg=False` builds the colocated A/B — the SAME
    decode decoder and cache, no prefill pool, no client."""

    def __init__(self, params, config, *, disagg: bool = True,
                 block_tokens: int = 16, max_slots: int = 8,
                 prefill_slots: int = 4, steps_per_sync: int = 4,
                 prefill_buckets=(64,), prefill_chunk: int | None = None,
                 cache_mb: int = 512, decoder_opts: dict | None = None,
                 fault_plan=None, transfer_timeout: float = 5.0,
                 retries: int = 1, batch_window: float = 0.0,
                 chunk_stream: bool | None = None, registry=None):
        from .event import EventEngine
        from .registrar import Registrar
        from .serving import ContinuousDecoder, PrefixKVCache
        from .share import ServicesCache
        from .transport.memory import MemoryBroker, MemoryMessage
        from .process import ProcessRuntime

        self.engine = EventEngine()
        self.broker = MemoryBroker()
        self.disagg = bool(disagg)
        self._registry = registry or default_registry()

        def make_rt(name):
            def factory(on_message, lwt_topic, lwt_payload, lwt_retain):
                return MemoryMessage(
                    on_message=on_message, broker=self.broker,
                    lwt_topic=lwt_topic, lwt_payload=lwt_payload,
                    lwt_retain=lwt_retain, client_id=name)
            return ProcessRuntime(name=name, engine=self.engine,
                                  transport_factory=factory).initialize()

        self.registrar_rt = make_rt("disagg_reg")
        self.registrar = Registrar(self.registrar_rt)
        opts = dict(decoder_opts or {})

        self.decode_rt = make_rt("disagg_decode")
        self.decode_rt.enable_peer()
        self.cache = PrefixKVCache(
            block_tokens=int(block_tokens),
            max_bytes=int(cache_mb) << 20,
            name="disagg.decode", registry=self._registry)
        self.decoder = ContinuousDecoder(
            params, config, max_slots=int(max_slots),
            prefill_buckets=tuple(prefill_buckets),
            steps_per_sync=int(steps_per_sync),
            prefill_chunk=prefill_chunk, name="disagg.decode",
            prefix_cache=self.cache, registry=self._registry, **opts)
        # drive the pumps FLAT-OUT (once per engine step), not on a
        # periodic timer: a 2 ms timer against ~10 ms CPU rounds makes
        # the engine's timer catch-up loop replay the pump dozens of
        # times per step and STARVE the message queues (transfers
        # crawled while decode spun — found live), while a slow timer
        # idles the decoder and hides the very prefill interference
        # this harness measures.  Flat-out = saturated decode AND one
        # queue drain per step, the closest one engine gets to two
        # busy hosts.
        self.engine.add_flatout_handler(self.decoder.pump)

        self.prefill_rt = None
        self.prefill = None
        self.client = None
        if self.disagg:
            self.prefill_rt = make_rt("disagg_prefill")
            self.prefill_rt.enable_peer(fault_plan=fault_plan)
            self.prefill = PrefillRuntime(
                self.prefill_rt, "disagg_prefill",
                params=params, config=config,
                block_tokens=int(block_tokens), cache_mb=cache_mb,
                max_slots=int(prefill_slots),
                prefill_buckets=tuple(prefill_buckets),
                prefill_chunk=prefill_chunk, decoder_opts=opts,
                pump_period=0, batch_window=batch_window,
                chunk_stream=chunk_stream, registry=self._registry)
            cache = ServicesCache(self.decode_rt)
            self.client = PrefillClient(
                self.decode_rt, self.decoder, services_cache=cache,
                name="disagg", transfer_timeout=transfer_timeout,
                retries=retries, registry=self._registry)
            self._services_cache = cache

    # -- driving ------------------------------------------------------------
    def wait_discovered(self, timeout: float = 10.0) -> bool:
        """Block (stepping the engine) until the client can see the
        prefill pool; True in colocated mode."""
        if not self.disagg:
            return True
        return self.engine.run_until(lambda: bool(self.client.loads),
                                     timeout=timeout)

    def submit(self, request_id, prompt, max_new, callback,
               tenant: str = "", deadline=None):
        if self.client is not None:
            return self.client.submit(request_id, prompt, max_new,
                                      callback, deadline=deadline,
                                      tenant=tenant)
        return self.decoder.submit(request_id, prompt, max_new,
                                   callback, deadline=deadline,
                                   tenant=tenant)

    def run_until(self, predicate, timeout: float = 30.0) -> bool:
        return self.engine.run_until(predicate, timeout=timeout)

    def measure(self, window: float = 6.0, streams: int = 6,
                stream_prompt: int = 12, stream_new: int = 24,
                burst: int = 4, burst_prompt: int = 288,
                burst_new: int = 4, burst_every: float = 1.5,
                seed: int = 11) -> dict:
        """The two-pool workload behind the lat_llama_disagg_* rung
        and scripts/disagg_smoke.py: `streams` closed-loop decode
        streams (short prompts, long generations — pure token flow,
        tenant "stream") run the whole time; the second half ADDS a
        concurrent cold-prefill burst (`burst` long random prompts
        every `burst_every` s, tenant "burst").  Reports the decode
        streams' ITL p95 per phase from the tenant-filtered mergeable
        sketches — in colocated mode the burst's chunk extends ride
        the decode rounds and dilate it; disaggregated, the burst
        prefills on the prefill pool and only the suffix + one
        scatter touch the decode decoder.  Also reports transfer
        cost/volume, handle-hit rate, fallback counts, and a
        zero-lost accounting (submitted == completed after drain)."""
        rng = np.random.default_rng(seed)
        vocab = self.decoder.config.vocab
        state = {"stop": False, "stream_done": 0, "burst_done": 0,
                 "stream_posted": 0, "burst_posted": 0, "seq": 0}
        # bursts share a seeded "system prompt" prefix (half the
        # prompt) with a unique tail: after the first burst's harvest
        # the decode side holds the prefix chain, so later transfers
        # ship those blocks as HANDLES — the rung's handle-hit surface
        shared_prefix = rng.integers(
            1, vocab, size=burst_prompt // 2).tolist()

        def post_stream(i):
            state["seq"] += 1
            state["stream_posted"] += 1
            prompt = rng.integers(1, vocab,
                                  size=stream_prompt).tolist()

            def on_done(_rid, _tokens):
                state["stream_done"] += 1
                if not state["stop"]:
                    post_stream(i)

            self.submit(f"st{i}.{state['seq']}", prompt, stream_new,
                        on_done, tenant="stream")

        def on_burst_done(_rid, _tokens):
            state["burst_done"] += 1

        def post_burst(count=None):
            for _ in range(count or burst):
                state["seq"] += 1
                state["burst_posted"] += 1
                prompt = shared_prefix + rng.integers(
                    1, vocab,
                    size=burst_prompt - len(shared_prefix)).tolist()
                self.submit(f"bu{state['seq']}", prompt, burst_new,
                            on_burst_done, tenant="burst")

        # warmup: every compile variant (stream admit widths, burst
        # chunk extends, prefix-copy widths, transfer machinery) runs
        # once before anything is measured — including the odd burst
        # widths (a full burst AND a lone prompt)
        for i in range(streams):
            post_stream(i)
        post_burst()
        post_burst(1)
        # gate on the BURST completions specifically: the streams
        # complete quickly and keep resubmitting, so a combined count
        # would declare warm while the burst prompts (and their
        # compile variants / first transfers) are still in flight —
        # measured, found live as a 34 s "transfer p50"
        self.run_until(
            lambda: state["burst_done"] >= burst + 1 and
            state["stream_done"] >= streams, timeout=600.0)
        # second burst wave: the shared prefix is cached now, so this
        # compiles the prefix-hit copy/extend variants (and, disagg,
        # the handle-shipping path) BEFORE the measured window
        post_burst()
        self.run_until(
            lambda: state["burst_done"] >= 2 * burst + 1,
            timeout=600.0)
        self.decoder.clear_slo_sketches()
        self.decoder.ttft_samples.clear()
        self.decoder.itl_samples.clear()
        self.decoder.gap_samples.clear()

        def stall_p95():
            samples = sorted(self.decoder.gap_samples)
            self.decoder.gap_samples.clear()
            if not samples:
                return None
            return round(
                samples[int(0.95 * (len(samples) - 1))] * 1000.0, 3)

        deadline = time.perf_counter() + window / 2.0
        self.run_until(lambda: time.perf_counter() >= deadline,
                       timeout=window + 120.0)
        baseline = self.decoder.slo_sketch_stats(tenant="stream")
        baseline_stall = stall_p95()
        base_done = state["stream_done"]
        self.decoder.clear_slo_sketches()

        timer = self.engine.add_timer_handler(post_burst, burst_every)
        deadline = time.perf_counter() + window / 2.0
        self.run_until(lambda: time.perf_counter() >= deadline,
                       timeout=window + 120.0)
        self.engine.remove_timer_handler(timer)
        state["stop"] = True
        drained = self.run_until(
            lambda: self.decoder.idle and
            (self.client is None or self.client.pending_count() == 0),
            timeout=180.0)
        burst_phase = self.decoder.slo_sketch_stats(tenant="stream")
        posted = state["stream_posted"] + state["burst_posted"]
        done = state["stream_done"] + state["burst_done"]
        out = {
            "itl_p95_baseline_ms": baseline["itl_p95_ms"],
            "itl_p50_baseline_ms": baseline["itl_p50_ms"],
            "itl_p95_burst_ms": burst_phase["itl_p95_ms"],
            "itl_p50_burst_ms": burst_phase["itl_p50_ms"],
            # worst inter-sync stall per request (the number prefill
            # interference inflates most directly — ITL means dilute
            # a stalled round across the whole generation)
            "stall_p95_baseline_ms": baseline_stall,
            "stall_p95_burst_ms": stall_p95(),
            "stream_completions": state["stream_done"],
            "stream_completions_baseline": base_done,
            "burst_completions": state["burst_done"],
            "posted": posted, "completed": done,
            "lost": posted - done, "drained": bool(drained),
        }
        if self.client is not None:
            stats = self.client.stats
            samples = sorted(self.client.transfer_samples)
            out.update({
                "transfers": stats["transfers"],
                "transfer_bytes": stats["transfer_bytes"],
                "transfer_p50_ms": round(
                    samples[len(samples) // 2] * 1000.0, 3)
                if samples else None,
                "transfer_p95_ms": round(
                    samples[int(0.95 * (len(samples) - 1))] * 1000.0,
                    3) if samples else None,
                "handle_hit_rate": round(
                    self.client.handle_hit_rate(), 4),
                "local_fallbacks": stats["local_fallbacks"],
                "install_shed": stats["install_shed"],
                # chunk streaming (ISSUE 17): how many transfers
                # overlapped the donor's prefill compute, and how much
                # transfer wall time that overlap hid
                "chunk_streamed": stats["chunk_streamed"],
                "chunk_installs": stats["chunk_installs"],
                "chunk_dropped": stats["chunk_dropped"],
                "transfer_overlap_s": round(
                    stats["transfer_overlap_s"], 4),
            })
        return out

    def kill_prefill(self) -> None:
        """Chaos: the prefill pool dies mid-stream (process crash —
        LWT removes its records, channels collapse).  In-flight
        transfers ride the client's fallback ladder."""
        if self.prefill_rt is not None:
            self.prefill_rt.terminate(graceful=False)
            self.prefill_rt = None
            self.prefill = None

    def stop(self) -> None:
        if self.client is not None:
            self.client.stop()
        # drain decoder work owed to callbacks before teardown
        if self.prefill is not None:
            self.prefill.stop()
        self.engine.remove_flatout_handler(self.decoder.pump)
        if self.prefill_rt is not None:
            self.prefill_rt.terminate()
        self.decode_rt.terminate()
        self.registrar_rt.terminate()
