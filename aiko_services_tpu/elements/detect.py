# Detection + agent elements: the BASELINE config 4 detect stage and the
# config 5 LLM agent stage.

from __future__ import annotations

from ..pipeline import DEFERRED, Frame, FrameOutput, PipelineElement

__all__ = ["PE_Detect", "PE_LlamaAgent"]


def _session_key(raw: str) -> str:
    """SessionTable keys may not contain '.', '/', or spaces; stream /
    frame ids may (stream ids embed topic-ish paths).  Deterministic
    sanitization keeps the same stream mapping to the same session."""
    return raw.replace(".", "-").replace("/", "-").replace(" ", "-")


class PE_Detect(PipelineElement):
    """Batched object detection through the ComputeRuntime (the detect
    stage of video → detect → tracker).  Emits {"boxes": [[x1,y1,x2,y2]..],
    "scores", "classes"} with zero-score detections stripped host-side.

    Parameters: preset (detector_r18/detector_test), image_size, mode,
    score_threshold, max_batch, max_wait, compute, wire (raw|dct8),
    dct_keep."""

    # any-size RGB frame (resized host-side to image_size); uint8 is
    # the wire-native form, floats keep the historical 0-255 contract.
    # Detections are host-side python lists — explicit opt-out.
    contracts = {
        "in:image": "u8[*,*,3] | dct8-u8[*,*,3] | f32[*,*,3]",
        "out:boxes": "any", "out:scores": "any", "out:classes": "any",
    }

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._program = f"detect.{self.definition.name}"
        self._setup_done = False

    def _setup(self) -> None:
        if self._setup_done:
            return

        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..compute import resolve_pipelined
        from ..models.detector import (
            DETECTOR_PRESETS, detect, detector_axes, detector_init)

        preset, _ = self.get_parameter("preset", "detector_r18")
        image_size, _ = self.get_parameter("image_size", 256)
        threshold, _ = self.get_parameter("score_threshold", 0.3)
        max_batch, _ = self.get_parameter("max_batch", 16)
        max_wait, _ = self.get_parameter("max_wait", 0.05)
        self.mode, _ = self.get_parameter("mode", "batched")
        self.image_size = int(image_size)

        compute_name, _ = self.get_parameter("compute", "compute")
        self.compute = self.runtime.service_by_name(compute_name)
        if self.compute is None:
            raise RuntimeError(f"detect element {self.name}: no "
                               f"ComputeRuntime named {compute_name!r}")
        config = DETECTOR_PRESETS[str(preset)]
        # dtype is opt-in bf16: measured on the bench chip, bf16 convs
        # run 2.4x SLOWER than f32 for this backbone (67.8 vs 27.8
        # fps/chip at batch 32/256px) — the conv path, unlike matmuls,
        # does not win from bf16 here.  detect()'s score/box
        # post-processing is f32 regardless.
        dtype_name, _ = self.get_parameter("dtype", "float32")
        if str(dtype_name) == "bfloat16":
            import dataclasses
            config = dataclasses.replace(
                config, dtype=jnp.bfloat16,
                backbone=dataclasses.replace(config.backbone,
                                             dtype=jnp.bfloat16))
        params = detector_init(jax.random.PRNGKey(0), config)
        self.params = self.compute.place_params(params,
                                                detector_axes(params))
        threshold = float(threshold)

        # wire format: "raw" ships uint8 (normalize on device — already
        # 4x under f32); "dct8" ships quantized int8 DCT coefficients
        # (another 4x under raw at keep=16, JPEG-grade fidelity) and the
        # device program fuses dequant+iDCT+normalize+model.  The
        # host->device hop is the scarce resource for camera pipelines.
        wire, _ = self.get_parameter("wire", "raw")
        wire = str(wire)
        dct_keep, _ = self.get_parameter("dct_keep", 16)
        dct_keep = int(dct_keep)
        size_ = self.image_size
        if wire == "dct8":
            from ..ops.image_wire import dct8_decode

            forward = jax.jit(lambda params, codes: detect(
                params, config=config,
                images=dct8_decode(codes, size_, size_),
                score_threshold=threshold))
        else:
            forward = jax.jit(lambda params, raw: detect(
                params, config=config,
                images=raw.astype(jnp.float32) / 255.0,
                score_threshold=threshold))

        def run_bucket(_bucket, images):
            return forward(self.params, images)

        def to_uint8(p):
            # float frames keep the historical 0-255 contract (the old
            # collate divided floats by 255 too)
            p = np.asarray(p)
            if p.dtype == np.uint8:
                return p
            return np.clip(p, 0, 255).astype(np.uint8)

        # pad partial batches to max_batch: ONE compile per bucket
        # (same recompilation-storm guard as PE_WhisperASR); split()
        # only reads the real rows back
        from ..utils import parse_bool
        pad_batch, _ = self.get_parameter("pad_batch",
                                          self.mode == "batched")
        pad_batch = parse_bool(pad_batch, self.mode == "batched")
        size = self.image_size
        full = int(max_batch)

        def collate(_bucket, payloads):
            rows = full if pad_batch else len(payloads)
            if wire == "dct8":
                from ..ops.image_wire import dct8_encode
                batch = np.zeros((rows, size // 8, size // 8, 3,
                                  dct_keep), np.int8)
                for i, p in enumerate(payloads):
                    batch[i] = dct8_encode(to_uint8(p), keep=dct_keep)
                return jnp.asarray(batch)
            batch = np.zeros((rows, size, size, 3), np.uint8)
            for i, p in enumerate(payloads):
                batch[i] = to_uint8(p)
            return jnp.asarray(batch)

        def split(results, count):
            boxes, scores, classes = (np.asarray(r) for r in results)
            out = []
            for i in range(count):
                keep = scores[i] > 0.0
                out.append({"boxes": boxes[i][keep].tolist(),
                            "scores": scores[i][keep].tolist(),
                            "classes": classes[i][keep].tolist()})
            return out

        pipelined, _ = self.get_parameter("pipelined", False)
        max_in_flight, _ = self.get_parameter("max_in_flight", 4)
        self.compute.register_batched(
            self._program, run_bucket, [self.image_size], collate, split,
            max_batch=int(max_batch), max_wait=float(max_wait),
            pipelined=resolve_pipelined(pipelined, self.mode),
            max_in_flight=int(max_in_flight))
        self._setup_done = True

    def start_stream(self, stream) -> None:
        self._setup()

    def process_frame(self, frame: Frame, image=None, **_) -> FrameOutput:
        import numpy as np

        self._setup()
        image = np.asarray(image)
        if image.shape[:2] != (self.image_size, self.image_size):
            from PIL import Image
            image = np.asarray(Image.fromarray(image.astype("uint8"))
                               .resize((self.image_size,
                                        self.image_size)))

        if self.mode == "sync":
            box = {}
            self.compute.submit(self._program, frame.stream_id, image,
                                self.image_size,
                                lambda _sid, r: box.setdefault("r", r))
            self.compute.programs[self._program].scheduler.drain(
                force=True)
            result = box["r"]
            if isinstance(result, Exception):
                return FrameOutput(False, diagnostic=repr(result))
            return FrameOutput(True, result)

        def callback(_sid, result):
            self.pipeline.post("resume_frame", frame,
                               self.definition.name, result)

        self.compute.submit(self._program, frame.stream_id, image,
                            self.image_size, callback)
        return FrameOutput(True, DEFERRED)


class PE_LlamaAgent(PipelineElement):
    """LLM agent stage (BASELINE config 5: vision+ASR+Llama agent).

    Takes `text` (e.g. an ASR transcript + telemetry), prompts the
    decoder-only model, emits {"response", "response_tokens"}.  The model
    is TP-sharded over the ComputeRuntime's mesh via its logical axes.

    Tokenization is a pluggable hook (parameter-free byte fallback keeps
    the element self-contained; a real BPE tokenizer drops in via the
    `tokenizer`/`detokenizer` attributes)."""

    contracts = {"in:text": "str", "out:response": "str",
                 "out:response_tokens": "i32[*]"}

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._setup_done = False
        self._stats_timer = None
        self.prefix_cache = None
        self._session_table = None
        self.tokenizer = lambda text: [b % 250 for b in
                                       text.encode("utf-8")][:120]
        self.detokenizer = lambda tokens: " ".join(str(t) for t in tokens)

    def _publish_serving_stats(self) -> None:
        """Decoder occupancy/throughput into the pipeline's EC share —
        the observability the batch path gets from _publish_stats.
        Dedup'd: EC updates fan out to every leaseholder, so an idle
        decoder must not stream identical values every second."""
        producer = getattr(self.pipeline, "ec_producer", None)
        if producer is None:
            return
        name = self.definition.name
        stats = self.decoder.stats
        for key, value in (
                (f"serving.{name}.active", self.decoder.active_count),
                (f"serving.{name}.completed", stats["completed"]),
                (f"serving.{name}.steps", stats["steps"]),
                (f"serving.{name}.occupancy",
                 round(self.decoder.mean_occupancy(), 3))):
            if producer.get(key) != value:
                producer.update(key, value)

    def _setup(self) -> None:
        if self._setup_done:
            return
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..models.llama import (
            LLAMA_PRESETS, llama_axes, llama_greedy_decode, llama_init)

        preset, _ = self.get_parameter("preset", "tiny")
        max_tokens, _ = self.get_parameter("max_tokens", 16)
        self.prompt_length, _ = self.get_parameter("prompt_length", 128)
        max_batch, _ = self.get_parameter("max_batch", 8)
        max_wait, _ = self.get_parameter("max_wait", 0.05)
        self.mode, _ = self.get_parameter("mode", "batched")
        self._program = f"agent.{self.definition.name}"

        compute_name, _ = self.get_parameter("compute", "compute")
        self.compute = self.runtime.service_by_name(compute_name)
        if self.compute is None:
            raise RuntimeError(f"agent element {self.name}: no "
                               f"ComputeRuntime named {compute_name!r}")
        config = LLAMA_PRESETS[str(preset)]
        tokenizer_path, _ = self.get_parameter("tokenizer", "")
        if tokenizer_path:
            from ..models.tokenizer import load_tokenizer
            # stream-start model load is the sanctioned lazy-init seam
            bpe = load_tokenizer(str(tokenizer_path))  # graft: disable=lint-blocking-call
            limit = int(self.prompt_length)
            vocab = config.vocab
            # drop ids the model's embedding can't represent — jnp.take
            # would clamp them silently (same guard greedy_decode applies
            # to whisper specials)
            self.tokenizer = lambda text: [
                t for t in bpe.encode(text) if t < vocab][:limit]
            self.detokenizer = bpe.decode
        params = llama_init(jax.random.PRNGKey(0), config)
        self.params = self.compute.place_params(params,
                                                llama_axes(config))
        tokens = int(max_tokens)
        self.max_tokens = tokens

        if self.mode == "continuous":
            # iteration-level scheduling: requests join/leave the running
            # batch between decode steps (serving.ContinuousDecoder) —
            # ragged generation lengths no longer idle the MXU
            from ..serving import ContinuousDecoder, PrefixKVCache
            from ..utils import parse_bool
            # serving role (ISSUE 14): tag the OWNING pipeline's
            # discovery record so role-aware discovery/routing
            # (serving_disagg, ops/admission.DeadlineRouter) can tell
            # prefill, decode, and colocated pools apart
            role, _ = self.get_parameter("role", "")
            if role and self.pipeline is not None:
                from ..serving_disagg import tag_role
                tag_role(self.pipeline, str(role))
            steps_per_sync, _ = self.get_parameter("steps_per_sync", 4)
            eos_token, _ = self.get_parameter("eos_token", -1)
            # prefix/KV reuse (ISSUE 13): parameter `prefix_block` > 0
            # binds a hash-addressed prefix cache to the decoder, so
            # shared system prompts and multi-turn histories skip
            # re-prefill.  Chunked prefill is forced on (default: one
            # bucket-sized chunk) because conversation histories
            # outgrow the prefill bucket, and chunking lifts the
            # prompt cap to max_seq.
            prefix_block, _ = self.get_parameter("prefix_block", 0)
            prefill_chunk, _ = self.get_parameter("prefill_chunk", 0)
            self.prefix_cache = None
            if int(prefix_block) > 0:
                cache_mb, _ = self.get_parameter("prefix_cache_mb", 64)
                tenant_mb, _ = self.get_parameter("prefix_tenant_mb", 0)
                self.prefix_cache = PrefixKVCache(
                    block_tokens=int(prefix_block),
                    max_bytes=int(float(cache_mb) * (1 << 20)),
                    tenant_max_bytes=int(float(tenant_mb) * (1 << 20))
                    or None,
                    name=self.definition.name)
                prefill_chunk = int(prefill_chunk) or \
                    int(self.prompt_length)
                # tiered KV (ISSUE 17): parameter `host_kv_mb` > 0
                # backs the prefix cache with a host-RAM block store —
                # session demotion and LRU pressure demote chain
                # blocks to host instead of forgetting them, and the
                # admission/session-touch prefetch kicks re-land them
                # asynchronously before the next turn's admit round
                host_kv_mb, _ = self.get_parameter("host_kv_mb", 0)
                if int(host_kv_mb) > 0:
                    from ..serving_tiered import HostBlockStore
                    host_tenant_mb, _ = self.get_parameter(
                        "host_kv_tenant_mb", 0)
                    self.prefix_cache.attach_host_store(HostBlockStore(
                        max_bytes=int(float(host_kv_mb) * (1 << 20)),
                        tenant_max_bytes=int(
                            float(host_tenant_mb) * (1 << 20)) or None,
                        name=self.definition.name))
            # paged KV (ISSUE 15): parameter `paged` rebuilds the slot
            # cache as a block pool + per-slot tables — prefix hits
            # alias instead of copying, and the disagg path can land
            # shipped KV by direct slot-table install even WITHOUT a
            # prefix cache bound (see below)
            paged, _ = self.get_parameter("paged", False)
            paged = parse_bool(paged, False)
            self.decoder = ContinuousDecoder(
                self.params, config, max_slots=int(max_batch),
                prefill_buckets=(int(self.prompt_length),),
                steps_per_sync=int(steps_per_sync),
                prefill_chunk=int(prefill_chunk) or None,
                eos_token=int(eos_token) if int(eos_token) >= 0 else None,
                name=self.definition.name,
                prefix_cache=self.prefix_cache,
                paged_kv=paged, kv_block=int(prefix_block) or 32)
            # session-resident conversation KV (ISSUE 13 / PR 10
            # residue c): parameter `sessions` persists per-(tenant,
            # session) history in a SessionTable; each turn re-submits
            # its whole history and the prefix cache longest-matches
            # it, so a returning session resumes decode instead of
            # re-prefilling.  Lease expiry / byte-budget demotion
            # release the pinned KV handles through the table's hooks.
            sessions, _ = self.get_parameter("sessions", False)
            self._session_table = None
            self._session_view = None
            if parse_bool(sessions, False) and \
                    self.prefix_cache is not None:
                from ..state.sessions import SessionTable, SessionView
                session_lease, _ = self.get_parameter(
                    "session_lease", 300.0)
                session_shards, _ = self.get_parameter(
                    "session_shards", 2)
                session_idle, _ = self.get_parameter(
                    "session_idle", 0.0)
                # tiered cache: expiry/demotion DEMOTE the pinned KV
                # to the host store (demote-not-forget, ISSUE 17);
                # without a host store demote_sessions degrades to
                # release_sessions exactly
                self._session_table = SessionTable(
                    self.pipeline, num_shards=int(session_shards),
                    lease_time=float(session_lease),
                    on_expired=self.prefix_cache.demote_sessions,
                    on_demoted=self.prefix_cache.demote_sessions,
                    demote_idle=float(session_idle) or None)
                # crash re-materialization source (ISSUE 19):
                # parameter `session_mirror` names ANOTHER runtime's
                # SessionTable topic root; its shard deltas replicate
                # into a SessionView here, so when that runtime dies
                # and callers fail over to this pipeline, the
                # conversation history is already local — the turn's
                # full-history re-submit re-prefills (chunked) and
                # the continuation is BIT-IDENTICAL to a never-crashed
                # decode, no KV bytes required
                mirror, _ = self.get_parameter("session_mirror", "")
                if str(mirror or ""):
                    self._session_view = SessionView(
                        self.runtime, str(mirror),
                        int(session_shards))
            # disaggregated serving (ISSUE 14): parameter `disagg`
            # routes prompts through a PrefillClient — a role=prefill
            # runtime computes the prompt KV and ships it over the
            # peer plane; this decoder only prefills the ragged
            # suffix.  The shipped chain needs somewhere to land: a
            # bound prefix cache, or (ISSUE 15) a paged decoder whose
            # pool takes the blocks by direct slot-table install —
            # so a cacheless decode pool engages too.  Falls back to
            # local prefill whenever the pool is absent — never a
            # dropped request.
            self._prefill_client = None
            disagg, _ = self.get_parameter("disagg", False)
            if parse_bool(disagg, False) and \
                    (self.prefix_cache is not None or paged):
                from ..serving_disagg import PrefillClient
                transfer_timeout, _ = self.get_parameter(
                    "disagg_timeout", 5.0)
                disagg_retries, _ = self.get_parameter(
                    "disagg_retries", 1)
                self._prefill_client = PrefillClient(
                    self.runtime, self.decoder,
                    services_cache=getattr(self.pipeline,
                                           "_services_cache", None),
                    name=self.definition.name,
                    transfer_timeout=float(transfer_timeout),
                    retries=int(disagg_retries))
            self._setup_done = True
            return

        decode = jax.jit(lambda params, prompt: llama_greedy_decode(
            params, config, prompt, max_tokens=tokens))

        def run_bucket(_bucket, prompts):
            return decode(self.params, prompts)

        def collate(_bucket, payloads):
            return jnp.asarray(np.stack(payloads), jnp.int32)

        def split(results, count):
            generated = np.asarray(results)
            return [generated[i].tolist() for i in range(count)]

        self.compute.register_batched(
            self._program, run_bucket, [int(self.prompt_length)],
            collate, split, max_batch=int(max_batch),
            max_wait=float(max_wait))
        self._setup_done = True

    def start_stream(self, stream) -> None:
        self._setup()
        if self.mode == "continuous":
            # pump timer lives while any stream is open (same teardown
            # discipline as the other timer-owning elements)
            self._open_streams = getattr(self, "_open_streams", 0) + 1
            if self._open_streams == 1:
                self.decoder.attach(self.runtime.event)
                self.decoder.on_idle = None
                if self._stats_timer is None:
                    self._stats_timer = self.runtime.event.\
                        add_timer_handler(self._publish_serving_stats,
                                          1.0)

    def stop_stream(self, stream) -> None:
        if self.mode == "continuous":
            self._open_streams = max(0,
                                     getattr(self, "_open_streams", 0) - 1)
            if self._open_streams == 0:
                # in-flight requests must still complete (their frames
                # are parked DEFERRED) — detach only once drained; the
                # stats timer lives until then so drain completions
                # still publish
                if self.decoder.idle:
                    self._teardown_continuous()
                else:
                    self.decoder.on_idle = lambda: (
                        self._teardown_continuous()
                        if getattr(self, "_open_streams", 0) == 0
                        else None)

    def _teardown_continuous(self) -> None:
        self._publish_serving_stats()       # final truth, not stale
        if self._stats_timer is not None:
            self.runtime.event.remove_timer_handler(self._stats_timer)
            self._stats_timer = None
        if getattr(self, "_prefill_client", None) is not None:
            self._prefill_client.stop()
            self._prefill_client = None
        if getattr(self, "_session_view", None) is not None:
            self._session_view.terminate()
            self._session_view = None
        if self._session_table is not None:
            self._session_table.stop()
        if self.prefix_cache is not None and \
                self.prefix_cache.promoter is not None:
            self.prefix_cache.promoter.stop()
        self.decoder.detach(self.runtime.event)

    def _pad_prompt(self, text):
        import numpy as np

        tokens = self.tokenizer(str(text)) or [1]
        length = int(self.prompt_length)
        padded = ([0] * max(0, length - len(tokens)) + tokens)[-length:]
        return np.asarray(padded, np.int32)

    def _to_outputs(self, generated):
        return {"response_tokens": generated,
                "response": self.detokenizer(generated)}

    def process_frame(self, frame: Frame, text="", **_) -> FrameOutput:
        self._setup()

        if self.mode == "continuous":
            turn = self.tokenizer(str(text)) or [1]
            # conversation state (ISSUE 13): with sessions on, the turn
            # prompt is the session's WHOLE history plus the new text —
            # re-submitted every turn, which is exactly what the prefix
            # cache longest-matches, so only the new tokens prefill
            tenant_param, _ = self.get_parameter("tenant", "",
                                                 frame.stream)
            # ONE normalized tenant key for decoder, cache, and table:
            # harvested blocks, session pins, and table keys must
            # share a root or session_store would match nothing — and
            # SessionTable keys may not contain '.', '/', or spaces,
            # so the key is sanitized up front
            tenant = _session_key(str(tenant_param or "default"))
            table = self._session_table
            session_id = ""
            history: list = []
            cap = self.decoder.max_seq - self.max_tokens - 2
            if table is not None:
                session_param, _ = self.get_parameter("session", "",
                                                      frame.stream)
                session_id = _session_key(
                    str(session_param or frame.stream_id))
                payload = table.get(tenant, session_id)
                if isinstance(payload, dict):
                    history = [int(t) for t in
                               payload.get("history", ())]
                elif getattr(self, "_session_view", None) is not None:
                    # failover turn (ISSUE 19): the local table has
                    # never seen this session but the mirrored state
                    # plane has — adopt its history; on_done below
                    # re-creates the session locally, so ONE turn
                    # re-materializes it completely
                    mirrored = self._session_view.get(tenant,
                                                      session_id)
                    if isinstance(mirrored, dict):
                        history = [int(t) for t in
                                   mirrored.get("history", ())]
            tokens = (history + turn)[-cap:] if history else turn[-cap:]
            if history and self.prefix_cache is not None and \
                    self.prefix_cache.tiered:
                # session touch = the earliest possible promotion kick
                # (ISSUE 17): a revived conversation's demoted chain
                # starts re-landing from host RAM NOW, while the turn
                # is still threading through submit/admission
                self.prefix_cache.prefetch(tenant, tokens)

            def on_done(_rid, generated):
                if table is not None:
                    # the finished turn IS the next turn's prefix:
                    # pin its chain under the session handle and
                    # persist the history in the state plane (lease
                    # expiry / demotion release the pin via the
                    # table's hooks).  A shed create (tenant at its
                    # session-count budget) must release the pin it
                    # just took — no table entry means no expiry hook
                    # would ever drop it.
                    new_history = (tokens + [int(t) for t in
                                             generated])[-cap:]
                    leaf, kv_tokens = self.prefix_cache.session_store(
                        tenant, session_id, new_history)
                    if not table.create(tenant, session_id,
                                        {"history": new_history,
                                         "kv": leaf or "",
                                         "kv_tokens": kv_tokens}):
                        self.prefix_cache.session_release(tenant,
                                                          session_id)
                self.pipeline.post("resume_frame", frame,
                                   self.definition.name,
                                   self._to_outputs(generated))

            # the frame's end-to-end deadline rides the ambient
            # TraceContext in ENGINE-clock seconds; the decoder's
            # admission runs on time.monotonic — carry only the
            # REMAINING budget across the domain boundary (ISSUE 12:
            # the journey then reports the margin at completion)
            import time as _time
            from ..observe.tracing import current_trace
            context = current_trace()
            deadline = None
            if context is not None and context.deadline is not None:
                remaining = context.remaining(
                    self.runtime.event.clock.now())
                if remaining is not None:
                    deadline = _time.monotonic() + max(0.0, remaining)
            request_id = f"{frame.stream_id}.{frame.frame_id}"
            client = getattr(self, "_prefill_client", None)
            if client is not None:
                # disaggregated path (ISSUE 14): the transfer is
                # async, so a decoder refusal AFTER the KV lands must
                # fail the parked frame through resume_frame
                def on_refused(_rid):
                    self.pipeline.post(
                        "resume_frame", frame, self.definition.name,
                        RuntimeError(
                            "decoder admission shed after prefill "
                            "transfer: estimated admit wait outruns "
                            "the remaining deadline budget"))
                accepted = client.submit(
                    request_id, tokens, self.max_tokens, on_done,
                    deadline=deadline, tenant=tenant,
                    on_refused=on_refused)
            else:
                accepted = self.decoder.submit(
                    request_id, tokens, self.max_tokens, on_done,
                    deadline=deadline,
                    tenant=tenant if self.prefix_cache is not None
                    else None)
            if not accepted:
                return FrameOutput(False, diagnostic=(
                    "decoder admission shed: estimated admit wait "
                    "outruns the remaining deadline budget"))
            return FrameOutput(True, DEFERRED)

        prompt = self._pad_prompt(text)
        length = int(self.prompt_length)

        if self.mode == "sync":
            box = {}
            self.compute.submit(self._program, frame.stream_id, prompt,
                                length,
                                lambda _sid, r: box.setdefault("r", r))
            self.compute.programs[self._program].scheduler.drain(
                force=True)
            result = box["r"]
            if isinstance(result, Exception):
                return FrameOutput(False, diagnostic=repr(result))
            return FrameOutput(True, self._to_outputs(result))

        def callback(_sid, result):
            outputs = result if isinstance(result, Exception) else \
                self._to_outputs(result)
            self.pipeline.post("resume_frame", frame,
                               self.definition.name, outputs)

        self.compute.submit(self._program, frame.stream_id, prompt,
                            length, callback)
        return FrameOutput(True, DEFERRED)
