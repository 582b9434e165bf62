# Continuous batching for autoregressive decode: iteration-level
# scheduling of LLM generation on TPU.
#
# The BatchingScheduler (ops/batching.py) coalesces FIXED-size work —
# right for ASR chunks, wrong for generation, where requests finish at
# different steps and a fixed batch would idle the MXU on ragged tails.
# Here requests join and leave the running batch BETWEEN decode steps
# (the vLLM-style iteration-level discipline), built TPU-first:
#
#   * one compiled step function decodes one token for ALL slots —
#     [max_slots] is static, so XLA compiles exactly once; empty/done
#     slots compute garbage that is masked on the host (lane occupancy
#     is the scheduler's job, not the compiler's);
#   * per-slot KV caches live in one [S, H, T, D] buffer per layer with
#     per-slot lengths — no batch-global cursor, no reallocation;
#   * prefill is bucketed by prompt length (static shapes per bucket)
#     and scattered into a free slot's cache rows;
#   * K decode steps run per device round via lax.scan
#     (steps_per_sync), so the host syncs [K, S] tokens instead of
#     round-tripping per token — the per-sync host cost amortizes;
#   * prefill runs OFF the decode critical path (ISSUE 7): each pump
#     round dispatches the decode scan FIRST, then queues admit/extend
#     device calls BEHIND it — they execute while the host syncs the
#     scan and resolves tokens, so a decode round's sync never waits on
#     prefill (the Sarathi-Serve stall-free discipline).  A freshly
#     admitted slot's first token resolves from the admit program's own
#     output at the NEXT round's sync — the compiled decode step no
#     longer carries the deferred-admit resolution;
#   * the KV cache is storable as int8 with per-(slot, head, position)
#     scales (kv_cache_dtype="int8"): admits/extends write quantized
#     rows, the decode scan folds the scales into scores/weights
#     (layers.quantize_kv_cache) — the HBM-bound step's dominant read
#     is halved;
#   * self-speculative multi-token decoding (speculate_k=k): a
#     prompt-lookup n-gram drafter over a device-side context buffer
#     proposes k tokens per slot, one widened forward verifies the
#     (1+k)-token block, and greedy acceptance advances each slot by
#     its accepted run — provably the same tokens as the
#     non-speculative path, but up to 1+k tokens per weight-stream.
#
# The reference has no generation serving at all (its LLM hop is a
# blocking HTTP call: reference examples/speech/speech_elements.py:
# 155-172).  No counterpart file exists — this is TPU-native new build.

from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import time
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .models import layers as L
from .models.llama import (SCOPE_ATTN_CORE, SCOPE_ATTN_PROJ, SCOPE_HEAD,
                           SCOPE_MLP, LlamaConfig, llama_ffn)
from .observe.profiler import ROUND_FIELDS, PhaseProfiler, slow_round
from .utils import get_logger

_WALL_S = ROUND_FIELDS.index("wall_s")    # of a PhaseProfiler round record

# no paged step attends at fewer positions than this: below it the views
# are a tenth of the step, and a decoder whose max_seq is no larger
# keeps exactly one width (every test geometry; tests of the ladder
# patch it down to theirs)
_ATTEND_FLOOR = 512


def _attend_ladder(max_seq: int, kv_block: int) -> tuple:
    """The widths a paged decoder's step may build its views and attend
    at, ascending: the cap, half of it and a quarter of it, each
    rounded up to whole blocks, none under _ATTEND_FLOOR.  Halving
    wastes under 2x of the views and bounds the programs at three a
    step count."""
    widths = {max_seq}
    for divisor in (2, 4):
        width = -(-max_seq // (divisor * kv_block)) * kv_block
        if _ATTEND_FLOOR <= width < max_seq:
            widths.add(width)
    return tuple(sorted(widths))

__all__ = ["ContinuousDecoder", "DecodeRequest", "PrefixKVCache",
           "prefix_chain_keys", "check_block_geometry"]

# how a PAGED decoder's step, speculative step and extend attend:
# "two_pass" gathers slot-major views of the pool through the block
# table and runs the scores / softmax / weights einsums over them (the
# bit-parity oracle, and the CPU's path); "paged_kernel" runs the fused
# pallas kernel (ops.paged_attention), which reads pool blocks straight
# through the table and builds no views.  Unset (None), all three
# gather but the PLAIN step of a decoder that the kernel suits, which
# attends each slot's live blocks through it (ContinuousDecoder's
# constructor says where).  A dense decoder attends its own
# cache and takes no kernel.  Read at decoder CONSTRUCTION (stashed as
# self.paged_kernel and self.step_kernel), so flipping the module
# global never switches a live decoder's compiled programs mid-stream;
# any other value is refused there.
ATTENTION_IMPL = os.environ.get("AIKO_DECODE_ATTENTION")


@dataclasses.dataclass
class DecodeRequest:
    request_id: str
    prompt: list                      # token ids
    max_new_tokens: int
    callback: Callable                # callback(request_id, token_list)
    generated: list = dataclasses.field(default_factory=list)
    slot: int = -1
    # SLO timestamps (scheduler clock): TTFT = first_time - submit_time;
    # inter-token latency derives from (last_time - first_time) and the
    # per-sync max_gap (tokens arrive in sync bursts — the gap BETWEEN
    # syncs is what an admit stall inflates, so it is tracked per
    # request as the worst observed stall)
    submit_time: float = 0.0
    first_time: float = 0.0
    last_time: float = 0.0
    max_gap: float = 0.0
    # chunked-prefill progress: tokens of `prompt` already written to
    # the slot's KV cache; prefilling=True while chunks remain
    prefill_pos: int = 0
    prefilling: bool = False
    # request journey (ISSUE 12): per-request lifecycle record — the
    # admission verdict, queue/prefill timeline, bounded per-token tick
    # ring, deadline margin — correlated to the frame's TraceContext
    # (observe/journey.py).  None only when journeys are disabled.
    journey: object = None
    # end-to-end completion deadline (scheduler clock) as passed to
    # submit(); the journey reports the margin at completion against it
    deadline: float | None = None
    # prefix/KV reuse (ISSUE 13): tokens satisfied from the prefix
    # cache at admit (0 = cold), the pinned chain keys (released at
    # retire), whether the cache was already probed (miss metrics must
    # count once per request, not once per deferred round), and the
    # tenant the request bills its cache traffic to
    prefix_hit: int = 0
    prefix_nodes: list = dataclasses.field(default_factory=list)
    prefix_probed: bool = False
    tenant: str = ""
    # prefill population label override (ISSUE 14): "" derives
    # cached/cold from prefix_hit; the disaggregated client stamps
    # "remote" so the TTFT sketches and journeys split out requests
    # whose prompt KV was computed by a prefill runtime
    prefill_label: str = ""
    # in-flight prefix dedup window (ISSUE 14 satellite, PR 13 residue
    # d): dedup_wait holds the leading-block key this request is
    # waiting on (a same-batch duplicate defers until the leader's
    # prompt blocks land); dedup_hot marks a leader some follower is
    # waiting on (its prompt harvests EARLY, at first token, instead
    # of at retire); inflight_key is the leader's registration key
    dedup_wait: str = ""
    dedup_hot: bool = False
    inflight_key: str = ""
    # direct slot-table install (ISSUE 15 satellite): pool block ids a
    # disaggregated client pre-installed for this request on a paged
    # CACHELESS decoder — admit aliases them into the slot's table
    # (ownership transfers to the slot) and prefills only the suffix
    kv_block_ids: list = dataclasses.field(default_factory=list)
    # chunk-streamed prefill progress (ISSUE 17): invoked (request,
    # finished) after each chunk extend advances prefill_pos — the
    # disaggregated PrefillRuntime harvests + ships the newly
    # complete blocks from here, so transfer overlaps the remaining
    # prefill compute instead of trailing it
    progress_callback: object = None


def prefix_chain_keys(tenant: str, tokens, block_tokens: int) -> list:
    """Hash-chain block keys for a token sequence: block i is keyed
    blake2b(parent_key, block_i_tokens), with block 0's parent the
    TENANT root — so every key commits to the entire token prefix
    behind it (the path identity of SGLang's RadixAttention, in hash
    form over fixed blocks like vLLM's prefix caching) and two tenants
    never share a block (isolation by construction, per-tenant byte
    accounting for free).  Only complete blocks are keyed; the ragged
    tail is always prefilled."""
    tenant = str(tenant or "default")
    parent = b"t\x00" + tenant.encode("utf-8")
    keys = []
    for i in range(len(tokens) // block_tokens):
        digest = hashlib.blake2b(parent, digest_size=16)
        digest.update(np.asarray(
            tokens[i * block_tokens:(i + 1) * block_tokens],
            np.int64).tobytes())
        parent = digest.digest()
        keys.append(parent.hex())
    return keys


def check_block_geometry(layout, block_tokens: int, entry) -> None:
    """Refuse a shipped block whose ARRAYS do not match a bound
    storage layout — the wire schema proves dtype/rank, but a
    schema-legal payload with the wrong layer count or head/head-dim
    extents would poison the slot cache and wedge the pump at the next
    hit (PR 14 review finding).  Shared by the prefix cache's
    install_chain and the paged direct slot-table install (ISSUE 15).
    Raises ValueError; the disaggregated client rides its
    corrupt-transfer rung."""
    layers, heads, head_dim = (int(layout[0]), int(layout[1]),
                               int(layout[2]))
    int8 = str(layout[4]) not in ("False", "0", "")
    for side in ("k", "v"):
        rows = entry[side]
        if len(rows) != layers:
            raise ValueError(
                f"block ships {len(rows)} layers, cache layout "
                f"has {layers}")
        want = (heads, int(block_tokens), head_dim)
        for leaf in rows:
            if isinstance(leaf, dict) != int8:
                raise ValueError(
                    f"block {side} storage form does not match "
                    f"the cache's int8={int8} layout")
            values = leaf["q"] if isinstance(leaf, dict) else leaf
            if tuple(values.shape) != want:
                raise ValueError(
                    f"block {side} rows shape "
                    f"{tuple(values.shape)} != layout {want}")
            if isinstance(leaf, dict) and \
                    tuple(leaf["s"].shape) != want[:2]:
                raise ValueError(
                    f"block {side} scale shape "
                    f"{tuple(leaf['s'].shape)} != {want[:2]}")


def _stack_block_leaves(leaves):
    """Stack per-block host leaves into one [M, H, B, D] layer stack
    (int8 dicts leaf-wise) — the one-transfer-per-layer form the pool's
    write_blocks scatter consumes."""
    if isinstance(leaves[0], dict):
        return {"q": np.stack([leaf["q"] for leaf in leaves]),
                "s": np.stack([leaf["s"] for leaf in leaves])}
    return np.stack(leaves)


class _PrefixBlock:
    """One cached block: per-layer K/V rows in the DECODER's storage
    layout ([H, B, D] arrays, or {"q", "s"} int8 dicts — a hit on an
    int8 cache is a bytes win too), plus the tree bookkeeping eviction
    needs (parent/children for leaf-first order, refs for pinning).
    In PAGED mode (ISSUE 15) the rows live in the decoder's block pool
    instead: `pool_id` names the pool block (the cache holds one pool
    ref on it) and k_rows/v_rows are None — a hit aliases the pool
    block into the slot's table, no rows move at all."""

    __slots__ = ("key", "parent", "tenant", "k_rows", "v_rows",
                 "refs", "children", "nbytes", "pool_id")

    def __init__(self, key, parent, tenant, k_rows, v_rows, nbytes,
                 pool_id=None):
        self.key = key
        self.parent = parent
        self.tenant = tenant
        self.k_rows = k_rows
        self.v_rows = v_rows
        self.refs = 0
        self.children: set = set()
        self.nbytes = int(nbytes)
        self.pool_id = pool_id


class PrefixKVCache:
    """Hash-addressed prefix/KV reuse cache for ContinuousDecoder
    (ISSUE 13, ROADMAP item 3).

    Prompts are chunked into fixed `block_tokens` blocks, each keyed by
    hash(parent_key, block_tokens) — see prefix_chain_keys.  Admit does
    a longest-prefix match: a hit copies the cached K/V rows into the
    slot cache and prefill runs only on the uncached suffix, so a
    shared system prompt or a conversation's whole history costs one
    block copy instead of a re-prefill.  Blocks are harvested when a
    request RETIRES (prompt + all generated tokens but the last, whose
    K/V is never written), so a multi-turn session's next turn
    longest-matches everything it has ever said.

    HBM budgeting: a global byte cap plus an optional per-tenant cap;
    over budget, eviction walks LRU order and takes LEAF blocks only
    (refs == 0 and no children — evicting an interior block would
    orphan its entire subtree), so a block pinned by a live slot or a
    session handle is never dropped.  Session handles
    (session_store/session_release) pin a (tenant, sid) chain between
    turns; the PR 10 SessionTable's lease expiry / demotion hooks
    release them.

    Mirrors serving_prefix_{hit,miss}_tokens_total{tenant} counters and
    the prefix_cache_bytes gauge into the registry so the PR 11/12
    observability planes see reuse as a first-class signal.

    Single-threaded like the decoder that owns it (pump runs on the
    event engine); shareable across decoders of the SAME geometry
    (bind() enforces layout agreement)."""

    def __init__(self, block_tokens: int = 32,
                 max_bytes: int | None = 512 << 20,
                 tenant_max_bytes: int | None = None,
                 name: str = "prefix", registry=None):
        self.block_tokens = int(block_tokens)
        if self.block_tokens < 1:
            raise ValueError(
                f"block_tokens must be >= 1, got {block_tokens}")
        self.max_bytes = int(max_bytes) if max_bytes else None
        self.tenant_max_bytes = int(tenant_max_bytes) \
            if tenant_max_bytes else None
        self.name = str(name)
        # one OrderedDict is both storage and LRU order (oldest-
        # touched first; eviction walks from the front, touch is
        # move_to_end) — bounded by eviction itself (budget caps)
        from collections import OrderedDict
        self._nodes: OrderedDict = OrderedDict()
        self._tenant_bytes: dict = {}
        self._sessions: dict = {}       # (tenant, sid) -> [keys]
        self.bytes_used = 0
        self._layout = None
        # paged mode (ISSUE 15): when a paged decoder binds this cache
        # it attaches its BlockPool — nodes then hold pool block ids
        # instead of row arrays, insert/evict move refcounts instead of
        # bytes, and install_chain writes shipped rows straight into
        # pool blocks
        self._pool = None
        self._dense_bound = False
        # tiered KV (ISSUE 17): an attached HostBlockStore turns
        # eviction of pool-resident blocks into DEMOTION (rows copy to
        # host, the chain key survives) and brings the AsyncPromoter's
        # prefetch/promote seam online — see attach_host_store
        self._host = None
        self._promoter = None
        # KV memory ledger (ISSUE 20): when attached, dense inserts/
        # evictions charge the device tier directly (paged bytes are
        # the pool's to report), double-releases become recorded
        # violations, and the auditor reads this cache for the
        # pinned-vs-evictable split
        self._ledger = None
        from .observe.metrics import MirroredStats, default_registry
        self._registry = registry or default_registry()
        self.stats = MirroredStats(
            {"hits": 0, "misses": 0, "hit_tokens": 0, "miss_tokens": 0,
             "inserts": 0, "evictions": 0, "insert_refused": 0,
             "session_handles": 0, "session_released": 0,
             "demoted": 0, "promoted": 0},
            metric="prefix_cache_events_total",
            help="prefix KV cache events by kind",
            registry=self._registry,
            skip=("hit_tokens", "miss_tokens"))
        self._gauge_bytes = self._registry.gauge(
            "prefix_cache_bytes",
            "bytes pinned by cached prefix KV blocks",
            labels={"cache": self.name})
        self._gauge_blocks = self._registry.gauge(
            "prefix_cache_blocks", "cached prefix KV blocks",
            labels={"cache": self.name})
        self._token_counters: dict = {}

    # -- binding -----------------------------------------------------------
    def bind(self, layout: tuple, paged: bool = False) -> None:
        """Record (and enforce) the storage layout this cache holds:
        decoders sharing a cache must agree on (layers, kv heads, head
        dim, dtype, int8-ness, block size) or a hit would scatter rows
        of the wrong shape into a live slot.  `paged` records the
        binder's storage mode so dense and paged decoders can never
        mix on one cache regardless of construction order (a dense
        node's rows and a paged node's pool id are mutually
        unreadable)."""
        if self._layout is None:
            self._layout = tuple(layout)
        elif self._layout != tuple(layout):
            raise ValueError(
                f"prefix cache {self.name!r} already bound to layout "
                f"{self._layout}, decoder wants {tuple(layout)}")
        self._dense_bound = self._dense_bound or not paged

    @property
    def layout(self) -> tuple | None:
        """The bound storage layout — the geometry handshake the
        disaggregated KV transfer carries (ISSUE 14): a prefill
        runtime's transfer declares its donor layout and the decode
        side refuses a mismatch before any row lands."""
        return self._layout

    # -- paged storage (ISSUE 15) ------------------------------------------
    @property
    def paged(self) -> bool:
        return self._pool is not None

    @property
    def pool(self):
        """The attached BlockPool, or None — what a second paged
        decoder sharing this cache adopts at construction."""
        return self._pool

    def attach_pool(self, pool) -> None:
        """Bind this cache to a paged decoder's BlockPool: cached
        blocks become refcounted pool residents.  One pool per cache —
        decoders sharing a paged cache must share the pool (they
        already must share a geometry via bind())."""
        if self._pool is not None and self._pool is not pool:
            raise ValueError(
                f"prefix cache {self.name!r} is already attached to "
                f"pool {self._pool.name!r}")
        if self._dense_bound:
            # order-independent twin of the dense-decoder-refuses-
            # paged-cache check: a dense decoder bound FIRST would
            # later insert() rowful nodes a paged hit cannot alias
            # (pool_id None), crashing the pump instead of failing
            # loudly here at construction
            raise ValueError(
                f"prefix cache {self.name!r} is bound by a dense "
                f"decoder; dense and paged decoders cannot share a "
                f"cache")
        if self._pool is None and self._nodes:
            raise ValueError(
                f"prefix cache {self.name!r} holds dense blocks; "
                f"cannot switch to paged storage mid-flight")
        self._pool = pool
        if self._ledger is not None:
            pool.attach_ledger(self._ledger)

    def attach_ledger(self, ledger) -> None:
        """Wire the KV memory ledger through every tier this cache
        fronts: the pool reports device transitions, the host store
        reports demote/evict/promote, and the cache itself reports
        dense bytes + double-release violations.  One call covers the
        whole stack whichever attach order the caller used."""
        self._ledger = ledger
        if ledger is None:
            return
        ledger.attach_cache(self)
        if self._pool is not None:
            self._pool.attach_ledger(ledger)
        if self._host is not None:
            self._host.attach_ledger(ledger)

    def attach_host_store(self, store, promoter=None) -> None:
        """Bring the host KV tier online (ISSUE 17): pool-resident
        blocks DEMOTE into `store` instead of vanishing when LRU
        pressure or the SessionTable's demotion wheel evicts them, and
        the returned promoter's prefetch/promote_for seam re-lands
        them ahead of the prompts that need them.  Paged caches only —
        a dense node's rows never shared a pool geometry to begin
        with (offload them is a different, uninteresting copy)."""
        if self._dense_bound:
            raise ValueError(
                f"prefix cache {self.name!r} is bound by a dense "
                f"decoder; the host tier offloads pool blocks")
        if self._host is not None and self._host is not store:
            raise ValueError(
                f"prefix cache {self.name!r} already has host store "
                f"{self._host.name!r}")
        self._host = store
        if self._ledger is not None:
            store.attach_ledger(self._ledger)
        if self._promoter is None:
            if promoter is None:
                from .serving_tiered import AsyncPromoter
                promoter = AsyncPromoter(self, store,
                                         registry=self._registry)
            self._promoter = promoter

    @property
    def host_store(self):
        return self._host

    @property
    def promoter(self):
        return self._promoter

    @property
    def tiered(self) -> bool:
        return self._host is not None

    @property
    def promotions_ready(self) -> bool:
        """Hot-path probe: staged async promotions are waiting for
        poll_promotions() (checked every admit round)."""
        return self._promoter is not None and self._promoter.ready

    def prefetch(self, tenant: str, tokens) -> int:
        """Non-blocking promotion kick for this prompt's
        host-resident chain tail (admission probes, session touches,
        the disagg client's submit).  No-op without a host tier."""
        if self._promoter is None:
            return 0
        return self._promoter.prefetch(tenant, tokens)

    def poll_promotions(self) -> int:
        """Land staged async promotions (event loop only)."""
        if self._promoter is None:
            return 0
        return self._promoter.poll()

    def promote_for(self, tenant: str, tokens) -> int:
        """Admit-time sync fallback: ensure this prompt's promotable
        chain tail is device-resident before the probe runs."""
        if self._promoter is None:
            return 0
        return self._promoter.promote_for(tenant, tokens)

    def insert_block(self, tenant: str, parent: str, key: str,
                     pool_id: int) -> bool:
        """Paged insert: the harvest path's zero-copy registration —
        retain one pool ref on `pool_id` and record the key.  The
        slot's own block BECOMES the cache entry; no rows move.
        Same budget/refusal semantics as insert()."""
        tenant = str(tenant or "default")
        if key in self._nodes:
            self._nodes.move_to_end(key)
            return True
        self._pool.retain([pool_id])
        node = _PrefixBlock(key, parent, tenant, None, None,
                            self._pool.block_nbytes,
                            pool_id=int(pool_id))
        self._nodes[key] = node
        parent_node = self._nodes.get(parent)
        if parent_node is not None:
            parent_node.children.add(key)
        self.bytes_used += node.nbytes
        self._tenant_bytes[tenant] = \
            self._tenant_bytes.get(tenant, 0) + node.nbytes
        self.stats["inserts"] += 1
        self._evict_to_budget(tenant)
        if key not in self._nodes:      # budget evicted the newcomer
            self.stats["insert_refused"] += 1
            self._publish_gauges()
            return False
        self._publish_gauges()
        return True

    def block_rows(self, node) -> tuple:
        """(per-layer K leaves, per-layer V leaves) of a cached block
        in the storage layout — dense nodes carry their own rows,
        paged nodes read the pool (device-side slice views; the wire
        shipper host-copies them)."""
        if node.pool_id is not None:
            return self._pool.block_rows(node.pool_id)
        return node.k_rows, node.v_rows

    def wire_layout(self) -> tuple:
        """The layout as wire-safe string fields (what
        transport.wire.encode_kv_transfer ships)."""
        return tuple(str(f) for f in (self._layout or ()))

    def layout_compatible(self, fields) -> bool:
        """True when a transfer's declared layout fields match this
        cache's bound layout (string-compared: the fields crossed a
        text-semantics wire)."""
        return self._layout is not None and \
            tuple(str(f) for f in fields) == self.wire_layout()

    # -- disaggregated KV admit (ISSUE 14) ----------------------------------
    def install_chain(self, tenant: str, tokens, start_block: int,
                      blocks) -> int:
        """Install shipped chain blocks [start_block, start_block +
        len(blocks)) of `tokens` into this cache — the decode-side KV
        admit path of the disaggregated split.  Keys are recomputed
        locally from the tokens (content-addressed: the hash chain IS
        the handle, nothing but indices crosses for blocks the decode
        side already holds).  Rows must be in this cache's storage
        layout; host ndarrays are fine — a hit's copy-in concat
        device-puts the admitted chain as one transfer per layer
        (serving_disagg installs owned host copies, deliberately NOT
        per-leaf device_puts on the event loop).  Returns
        the number of blocks newly resident (already-cached keys count
        — the transfer confirmed them); stops early when the byte
        budget refuses an insert, so children never dangle."""
        tokens = [int(t) for t in tokens]
        count = min(len(tokens) // self.block_tokens,
                    start_block + len(blocks))
        if count <= start_block:
            return 0
        keys = self.keys_for(tenant,
                             tokens[:count * self.block_tokens])
        for entry in blocks[:count - start_block]:
            self._check_block_geometry(entry)
        parent = keys[start_block - 1] if start_block else ""
        installed = 0
        if self.paged:
            # paged landing (ISSUE 15): the wire rows write STRAIGHT
            # into freshly allocated pool blocks — one scatter per
            # layer for the whole chain — and the cache records the
            # ids.  The later prefix-admit is then a pure table edit:
            # the transferred bytes land exactly once.  The alloc refs
            # are ours; insert_block retains its own, so releasing at
            # the end leaves cache-held blocks at refs 1 and refused
            # ones free.
            entries = blocks[:count - start_block]
            ids = self._pool.alloc_blocks(len(entries),
                                          tenant=tenant)
            layers = int(self._layout[0])
            k_layers = [_stack_block_leaves(
                [entry["k"][i] for entry in entries])
                for i in range(layers)]
            v_layers = [_stack_block_leaves(
                [entry["v"][i] for entry in entries])
                for i in range(layers)]
            self._pool.write_blocks(ids, k_layers, v_layers)
            for j in range(start_block, count):
                if not self.insert_block(tenant, parent, keys[j],
                                         ids[j - start_block]):
                    break
                installed += 1
                parent = keys[j]
            self._pool.release_blocks(ids, tenant=tenant)
            if installed and self._ledger is not None:
                self._ledger.event("install", installed)
            return installed
        for j in range(start_block, count):
            entry = blocks[j - start_block]
            if not self.insert(tenant, parent, keys[j],
                               entry["k"], entry["v"]):
                break
            installed += 1
            parent = keys[j]
        return installed

    def _check_block_geometry(self, entry) -> None:
        if self._layout is None:
            raise ValueError("install into an unbound prefix cache")
        check_block_geometry(self._layout, self.block_tokens, entry)

    # -- lookup ------------------------------------------------------------
    def keys_for(self, tenant: str, tokens) -> list:
        return prefix_chain_keys(tenant, tokens, self.block_tokens)

    def has(self, key: str) -> bool:
        return key in self._nodes

    def nodes(self, keys) -> list:
        return [self._nodes[key] for key in keys]

    def match(self, tenant: str, tokens,
              limit: int | None = None) -> tuple:
        """(chain keys, hit tokens) of the longest cached prefix, over
        at most `limit` tokens (callers cap at len-1 so at least one
        suffix token remains to produce the first output).  Pure probe:
        no refcounts, no LRU movement, no metrics — what the admission
        estimator uses (ISSUE 13 satellite)."""
        cap = len(tokens) if limit is None else min(limit, len(tokens))
        count = max(0, cap) // self.block_tokens
        if count == 0:
            return [], 0
        keys = self.keys_for(tenant, tokens[:count * self.block_tokens])
        hit = 0
        for key in keys:
            if key not in self._nodes:
                break
            hit += 1
        return keys[:hit], hit * self.block_tokens

    def acquire(self, tenant: str, tokens,
                limit: int | None = None) -> tuple:
        """match() + pin: refs++ on every chain node (released by the
        owner at retire), LRU touch, and the per-tenant hit/miss token
        counters the bench and the SLO planes read."""
        keys, hit = self.match(tenant, tokens, limit)
        for key in keys:
            self._nodes[key].refs += 1
            self._nodes.move_to_end(key)
        self.stats["hits" if hit else "misses"] += 1
        self.stats["hit_tokens"] += hit
        self.stats["miss_tokens"] += len(tokens) - hit
        self._count_tokens(tenant, hit, len(tokens) - hit)
        return keys, hit

    def release(self, keys) -> None:
        for key in keys:
            node = self._nodes.get(key)
            if node is None:
                continue        # evicted/purged since pin: legitimate
            if node.refs > 0:
                node.refs -= 1
            elif self._ledger is not None:
                # an unpin of an unpinned resident block is a paired-
                # release bug somewhere upstream — record it with the
                # chain key so the postmortem names the chain
                self._ledger.violation(
                    "double-release", tenant=node.tenant,
                    chain_key=key,
                    detail=f"cache {self.name}: refs already 0")

    def evictable_bytes(self, tenant=None) -> int:
        """Bytes held by unpinned (refs == 0) cached blocks — the
        ledger's pinned-vs-evictable split reads this lazily (interior
        blocks count too: they become evictable leaves as their
        subtrees drain)."""
        tenant = None if tenant is None else str(tenant or "default")
        return sum(node.nbytes for node in self._nodes.values()
                   if node.refs == 0 and
                   (tenant is None or node.tenant == tenant))

    def hit_rate(self) -> float:
        total = self.stats["hit_tokens"] + self.stats["miss_tokens"]
        return self.stats["hit_tokens"] / total if total else 0.0

    def _count_tokens(self, tenant: str, hit: int, miss: int) -> None:
        tenant = str(tenant or "default")
        counters = self._token_counters.get(tenant)
        if counters is None:
            counters = tuple(self._registry.counter(
                f"serving_prefix_{kind}_tokens_total",
                f"prompt tokens {kind} by the prefix KV cache",
                labels={"cache": self.name, "tenant": tenant})
                for kind in ("hit", "miss"))
            self._token_counters[tenant] = counters
        if hit:
            counters[0].inc(hit)
        if miss:
            counters[1].inc(miss)

    # -- insertion / eviction ----------------------------------------------
    def insert(self, tenant: str, parent: str, key: str,
               k_rows, v_rows) -> bool:
        """Register one block (per-layer K/V leaves).  Content-
        addressed: an existing key is just touched.  Returns False when
        the byte budgets refused it (everything evictable was already
        evicted and the budget still doesn't fit) — the caller must
        stop its chain there, or children would dangle."""
        tenant = str(tenant or "default")
        if key in self._nodes:
            self._nodes.move_to_end(key)
            return True
        nbytes = L.kv_rows_nbytes(k_rows) + L.kv_rows_nbytes(v_rows)
        node = _PrefixBlock(key, parent, tenant, k_rows, v_rows, nbytes)
        self._nodes[key] = node
        parent_node = self._nodes.get(parent)
        if parent_node is not None:
            parent_node.children.add(key)
        self.bytes_used += nbytes
        self._tenant_bytes[tenant] = \
            self._tenant_bytes.get(tenant, 0) + nbytes
        self.stats["inserts"] += 1
        if self._ledger is not None:
            # dense mode: the cache IS the device-tier truth source
            # (paged bytes are charged by the pool at alloc)
            self._ledger.device_delta(tenant, nbytes, "cache_insert")
        self._evict_to_budget(tenant)
        if key not in self._nodes:      # budget evicted the newcomer
            self.stats["insert_refused"] += 1
            self._publish_gauges()
            return False
        self._publish_gauges()
        return True

    def tenant_bytes(self, tenant: str) -> int:
        return self._tenant_bytes.get(str(tenant or "default"), 0)

    def _over_budget(self, tenant: str) -> str | None:
        if self.tenant_max_bytes is not None and \
                self.tenant_bytes(tenant) > self.tenant_max_bytes:
            return tenant
        if self.max_bytes is not None and \
                self.bytes_used > self.max_bytes:
            return ""                   # global breach: any tenant
        return None

    def _evict_to_budget(self, tenant: str) -> None:
        """Evict LRU-first LEAVES (unpinned, childless) until budgets
        hold.  A pass that frees nothing ends the loop: pinned bytes
        may legitimately exceed the budget (a block pinned by a live
        slot is never evicted), and interior blocks become leaves as
        their subtrees drain on later passes."""
        while True:
            scope = self._over_budget(tenant)
            if scope is None:
                return
            victim = None
            for node in self._nodes.values():
                if node.refs or node.children:
                    continue
                if scope and node.tenant != scope:
                    continue
                victim = node
                break
            if victim is None:
                # all-pinned pressure (ISSUE 17 satellite): every
                # evictable leaf is session-pinned.  With a host tier
                # attached, route the pressure into DEMOTION — unpin
                # and demote the oldest session's chain, then retry —
                # instead of refusing the insert outright.
                if self._host is not None and \
                        self._demote_oldest_session(scope):
                    continue
                return
            self._evict(victim)

    def _demote_oldest_session(self, scope: str | None) -> int:
        """Demote the oldest session handle (scope-matched on a
        tenant breach) to the host tier; returns blocks freed from
        the device (0 ends the caller's pressure loop — remaining
        pins belong to live requests, not idle sessions)."""
        for tenant, sid in self._sessions:
            if scope and tenant != scope:
                continue
            return self.demote_sessions([(tenant, sid)])
        return 0

    def demote_sessions(self, pairs) -> int:
        """Batch demotion matching SessionTable's on_expired /
        on_demoted callback shape ([(tenant, sid), ...]) — the
        expiry/demotion wheel's KV trigger (ISSUE 17).  Releases each
        session's pin, then walks its chain LEAF→ROOT demoting blocks
        to the host tier; a block still pinned by a live request or
        shared with another chain ends the walk (it stays device-
        resident — demotion never breaks a reader).  Without a host
        store this degrades to release_sessions (unpin only).
        Returns device blocks demoted."""
        demoted = 0
        for tenant, sid in pairs:
            keys = self._sessions.pop(
                (str(tenant or "default"), str(sid)), None)
            if keys is None:
                continue
            self.release(keys)
            self.stats["session_released"] += 1
            if self._ledger is not None:
                self._ledger.event("session_demote")
            if self._host is None:
                continue
            for key in reversed(keys):
                node = self._nodes.get(key)
                if node is None:
                    continue    # already demoted/evicted; walk on up
                if node.refs or node.children:
                    break       # pinned or shared below: stays hot
                self._evict(node)
                demoted += 1
        return demoted

    def _evict(self, node: _PrefixBlock) -> None:
        del self._nodes[node.key]
        if node.pool_id is not None:
            # demote-not-forget (ISSUE 17): with a host tier attached
            # the rows copy down BEFORE the pool ref goes — the chain
            # key survives in HostBlockStore and the promoter can
            # re-land it; only a host-budget refusal makes this a
            # true eviction
            if self._host is not None:
                k_rows, v_rows = self._pool.block_rows(node.pool_id)
                if self._host.put_from_device(
                        node.tenant, node.parent, node.key,
                        k_rows, v_rows, node.nbytes):
                    self.stats["demoted"] += 1
            # paged: the cache's ref goes; the pool block frees when
            # no slot table still aliases it
            self._pool.release_blocks([node.pool_id],
                                      tenant=node.tenant)
        elif self._ledger is not None:
            self._ledger.device_delta(node.tenant, -node.nbytes,
                                      "cache_evict")
        parent = self._nodes.get(node.parent)
        if parent is not None:
            parent.children.discard(node.key)
        self.bytes_used -= node.nbytes
        remaining = self._tenant_bytes.get(node.tenant, 0) - node.nbytes
        if remaining > 0:
            self._tenant_bytes[node.tenant] = remaining
        else:
            self._tenant_bytes.pop(node.tenant, None)
        self.stats["evictions"] += 1
        self._publish_gauges()

    def _publish_gauges(self) -> None:
        self._gauge_bytes.set(self.bytes_used)
        self._gauge_blocks.set(len(self._nodes))

    def __len__(self) -> int:
        return len(self._nodes)

    # -- session-resident conversation KV (ISSUE 13 / PR 10 residue c) -----
    def session_store(self, tenant: str, sid: str, tokens) -> tuple:
        """Pin the longest cached chain for `tokens` under a
        (tenant, sid) handle — the finished turn's history, registered
        so the session's blocks survive eviction between turns.
        Replaces (and releases) the session's previous handle.
        Returns (leaf key | None, pinned tokens)."""
        self.session_release(tenant, sid)
        keys, hit = self.match(tenant, tokens)
        if not keys:
            return None, 0
        for key in keys:
            self._nodes[key].refs += 1
            self._nodes.move_to_end(key)
        self._sessions[(str(tenant or "default"), str(sid))] = keys
        self.stats["session_handles"] += 1
        if self._ledger is not None:
            self._ledger.event("session_pin")
        return keys[-1], hit

    def session_release(self, tenant: str, sid: str) -> bool:
        """Drop a session's pin (SessionTable lease expiry / demotion
        path): the chain stays cached but becomes evictable."""
        keys = self._sessions.pop(
            (str(tenant or "default"), str(sid)), None)
        if keys is None:
            return False
        self.release(keys)
        self.stats["session_released"] += 1
        return True

    def session_tokens(self, tenant: str, sid: str) -> int:
        keys = self._sessions.get(
            (str(tenant or "default"), str(sid)), ())
        return len(keys) * self.block_tokens

    def release_sessions(self, keys) -> None:
        """Batch form matching SessionTable's on_expired/on_demoted
        callback shape: [(tenant, sid), ...]."""
        for tenant, sid in keys:
            self.session_release(tenant, sid)

    def sessions(self) -> list:
        """Live session handles, oldest-pinned first:
        [(tenant, sid), ...] — the drain migrator's enumeration
        surface (ISSUE 19)."""
        return list(self._sessions)

    def purge(self, demote: bool = True) -> int:
        """Evict everything evictable: release every session pin,
        then strip the tree leaf-first until only request-pinned
        nodes remain.  The drain endgame (ISSUE 19): after migration
        shipped the chains, the source purges with demote=False — a
        host-tier copy of state another runtime now owns would be
        dead weight — and the drain leak audit asserts the pool
        reaches zero live blocks.  Returns nodes evicted."""
        for tenant, sid in list(self._sessions):
            self.session_release(tenant, sid)
        host = self._host
        if not demote:
            self._host = None
        evicted = 0
        try:
            progress = True
            while progress:
                progress = False
                for node in list(self._nodes.values()):
                    if node.refs or node.children:
                        continue
                    self._evict(node)
                    evicted += 1
                    progress = True
        finally:
            self._host = host
        return evicted


def _kv_planes(cache, dtype):
    """(dot-operand values, fold scale or None) for a main-cache leaf.
    int8 caches (layers.quantize_kv_cache) keep the int8 buffer as the
    dot operand — the convert fuses, nothing re-materializes — and
    hand back the per-(slot, head, position) scale for folding into
    scores (K) and weights (V): the same fold discipline as
    layers.mha's quantized cross-KV, at serving's per-position
    grain."""
    if isinstance(cache, dict):
        return cache["q"].astype(dtype), cache["s"]
    return cache, None


def _cache_time(cache) -> int:
    """Time-axis extent of a main-cache leaf (array or int8 dict)."""
    return (cache["q"] if isinstance(cache, dict) else cache).shape[2]


def _grouped_block_attention(layer, config: LlamaConfig, x, cos, sin,
                             k_cache, v_cache, k_side, v_side,
                             entry_lengths, lengths, write_index,
                             side_valid):
    """Shared core of the block-KV decode attentions: project QKV for
    the [S, W] block at per-slot positions `lengths`, write this
    block's K/V into the side buffers at `write_index`, and attend
    over read-only main cache (positions < entry_lengths — causally
    visible to every query) + side entries selected by the caller's
    `side_valid` mask ([S,1,1,W,P]-broadcastable).  The ONE place the
    greedy numerics live: the plain scan (W=1) and the speculative
    verify (W=1+k) must stay the same computation or the
    greedy-equivalence invariant breaks.  int8 main caches
    (layers.quantize_kv_cache) keep the int8 buffer as the dot operand
    and fold their per-(slot, head, position) scales into the main
    scores (K) and weights (V); the side buffers stay in the compute
    dtype (they are one round wide — quantizing them would save
    nothing and cost an int8 round-trip every step).

    GQA runs as a grouped einsum against the SHARED KV: materializing
    repeated caches (jnp.repeat) multiplies the cache's bytes by the
    group and divides the slots that fit on a chip by it.  Scores and
    outputs are dots in the cache's own dtype with f32 ACCUMULATION
    (preferred_element_type): an explicit f32 upcast of the cache
    would double the bytes of the step's dominant read."""
    num_heads, num_kv = config.num_heads, config.num_kv_heads
    with jax.named_scope(SCOPE_ATTN_PROJ):
        q, k, v = _project_qkv(layer, config, x)
        q = L.apply_rope(q, cos, sin, lengths)
        k = L.apply_rope(k, cos, sin, lengths)
        k_side = jax.lax.dynamic_update_slice_in_dim(k_side, k,
                                                     write_index, axis=2)
        v_side = jax.lax.dynamic_update_slice_in_dim(v_side, v,
                                                     write_index, axis=2)

    slots_n, num_q, head_dim = q.shape[0], q.shape[2], q.shape[3]
    group = num_heads // num_kv
    with jax.named_scope(SCOPE_ATTN_CORE):
        q_grouped = q.reshape(slots_n, num_kv, group, num_q, head_dim)
        scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
        k_main, k_fold = _kv_planes(k_cache, x.dtype)
        v_main, v_fold = _kv_planes(v_cache, x.dtype)
        main_t = k_main.shape[2]
        main_valid = (jnp.arange(main_t)[None] <
                      entry_lengths[:, None])[:, None, None, None]
        scores_main = jnp.einsum(
            "skgqd,sktd->skgqt", q_grouped, k_main,
            preferred_element_type=jnp.float32) * scale
        if k_fold is not None:
            scores_main = scores_main * k_fold[:, :, None, None, :]
        scores_side = jnp.einsum(
            "skgqd,sktd->skgqt", q_grouped, k_side,
            preferred_element_type=jnp.float32) * scale
        scores = jnp.concatenate(
            [jnp.where(main_valid, scores_main, -1e30),
             jnp.where(side_valid, scores_side, -1e30)], axis=-1)
        weights = jax.nn.softmax(scores, axis=-1)
        w_main = weights[..., :main_t]
        if v_fold is not None:
            w_main = w_main * v_fold[:, :, None, None, :]
        out = jnp.einsum("skgqt,sktd->skgqd",
                         w_main.astype(v_main.dtype), v_main,
                         preferred_element_type=jnp.float32) + \
            jnp.einsum("skgqt,sktd->skgqd",
                       weights[..., main_t:].astype(v_side.dtype), v_side,
                       preferred_element_type=jnp.float32)
        out = out.reshape(slots_n, num_heads, num_q,
                          head_dim).astype(x.dtype)
    with jax.named_scope(SCOPE_ATTN_PROJ):
        return (L.linear(layer["attn"]["o"], L._merge_heads(out)),
                k_side, v_side)


def _slot_attention_block(layer, config: LlamaConfig, x, cos, sin,
                          k_cache, v_cache, k_side, v_side,
                          entry_lengths, lengths, step_index):
    """Block-KV decode attention: the main cache is read-only (tokens
    [0, entry_lengths) per slot); this round's tokens live in the side
    buffers at scan indices [0, step_index].  The new token's K/V is
    written to side[:, :, step_index] — a slot-uniform index, so XLA
    keeps the update in place instead of rewriting the whole cache."""
    side_positions = jnp.arange(k_side.shape[2])
    side_valid = ((side_positions[None] <= step_index) &
                  (side_positions[None] <
                   (lengths - entry_lengths + 1)[:, None])
                  )[:, None, None, None]
    return _grouped_block_attention(layer, config, x, cos, sin,
                                    k_cache, v_cache, k_side, v_side,
                                    entry_lengths, lengths, step_index,
                                    side_valid)


def _slot_attention_spec(layer, config: LlamaConfig, x, cos, sin,
                         k_cache, v_cache, k_side, v_side, pos_side,
                         entry_lengths, lengths, base):
    """Widened block-KV attention for the speculative verify step: `x`
    carries w = 1 + speculate_k tokens per slot at absolute positions
    lengths + [0, w).  The round's tokens live in the side buffers
    tagged with their ABSOLUTE cache positions (`pos_side` — rejected
    drafts are invalidated to an out-of-bounds position and never
    attended), so causality inside and across verify blocks is one
    comparison: pos_side <= q_pos."""
    width = x.shape[1]
    q_pos = lengths[:, None] + jnp.arange(width)[None]       # [S, w]
    side_valid = (pos_side[:, None, :] <=
                  q_pos[:, :, None])[:, None, None]      # [S,1,1,w,P]
    return _grouped_block_attention(layer, config, x, cos, sin,
                                    k_cache, v_cache, k_side, v_side,
                                    entry_lengths, lengths, base,
                                    side_valid)


def _project_qkv(layer, config: LlamaConfig, x):
    """q/k/v of a [S, W] block, split into heads: the one place the
    decode, verify and paged programs project them."""
    attn = layer["attn"]
    q = L._split_heads(L.linear(attn["q"], x), config.num_heads)
    k = L._split_heads(L.linear(attn["k"], x), config.num_kv_heads)
    v = L._split_heads(L.linear(attn["v"], x), config.num_kv_heads)
    return q, k, v


def _token_block_argmax(params, config: LlamaConfig, token_block,
                        attend, ffn=None):
    """Shared transformer pass over a [S, W] token block: `attend(i,
    layer, normed)` supplies each layer's attention output (and owns
    the cache-write strategy); `ffn(layer, x)`, where a model brings
    its own feed-forward (its norm, residual and scopes with it),
    stands in for the SwiGLU of llama_ffn.  Returns the per-position argmax
    [S, W] — bf16 operand reads (an f32 UPCAST of the [dim, vocab]
    head would double the step's largest weight read), f32
    accumulation KEPT f32 into the argmax: rounding the logits to
    bf16 first can flip near-ties against the f32 oracle.  W is 1 for
    the plain decode step and 1 + speculate_k for the verify step."""
    x = L.embedding(params["embed"], token_block).astype(config.dtype)
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(SCOPE_ATTN_PROJ):
            normed = L.rms_norm(layer["ln_attn"], x)
        x = x + attend(i, layer, normed)
        if ffn is not None:
            x = ffn(layer, x)
            continue
        with jax.named_scope(SCOPE_MLP):
            normed = L.rms_norm(layer["ln_mlp"], x)
            # dense SwiGLU or MoE per the config — MoE llama serves
            # through the same continuous-batching step
            x = x + llama_ffn(layer, config, normed)
    with jax.named_scope(SCOPE_HEAD):
        x = L.rms_norm(params["ln_out"], x)
        logits = L.linear_logits(params["lm_head"], x)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _build_step(config: LlamaConfig):
    """One decode iteration for every slot; jitted once, caches donated
    so the slot buffers update in place on device.  Params are an
    ARGUMENT, not a closure capture — captured trees get baked into the
    compiled program as constants (gigabytes for real checkpoints,
    duplicated per recompile).  Since ISSUE 7 the step does NOT return
    the entry tokens: deferred admits resolve from the admit program's
    own output at the next round's sync, so the decode scan carries
    nothing on behalf of prefill."""
    cos, sin = L.rope_frequencies(config.head_dim, config.max_seq_len,
                                  config.rope_theta)

    def step_k_block(params, tokens, lengths, active, budgets,
                     k_caches, v_caches, num_steps, eos):
        """lax.scan of `num_steps` iterations; returns tokens emitted
        [K, S] plus the per-step active mask [K, S] (True where the
        emitted token is real output).  A slot retires INSIDE the scan
        the moment it emits `eos` or exhausts its `budgets` entry —
        retired slots stop growing their context and their later
        emissions are discarded by the host, so a request finishing at
        step 1 of a 32-step round neither pollutes its cache nor
        miscounts as useful work.

        The main caches stay READ-ONLY through the scan (closed over,
        never carried): this round's K/V land in [S, H, num_steps, D]
        side buffers at the scan index — uniform across slots, so XLA
        updates them in place, where a functional write at per-slot
        cursors makes every step rewrite the whole cache — and one
        per-slot merge runs after the scan.  int8 main caches
        (kv_cache_dtype="int8") are read via the scale fold and the
        merge quantizes the side rows ONCE per round — the scan itself
        never touches int8 encode."""
        entry_lengths = lengths
        entry_active = active
        slots_n = tokens.shape[0]
        side_shape = (slots_n, config.num_kv_heads, num_steps,
                      config.head_dim)
        k_sides = [jnp.zeros(side_shape, config.dtype)
                   for _ in range(config.num_layers)]
        v_sides = [jnp.zeros(side_shape, config.dtype)
                   for _ in range(config.num_layers)]

        def body(carry, step_index):
            tokens, lengths, active, budgets, k_sides, v_sides = carry
            new_k, new_v = [], []

            def attend(i, layer, normed):
                attn_out, k_s, v_s = _slot_attention_block(
                    layer, config, normed, cos, sin, k_caches[i],
                    v_caches[i], k_sides[i], v_sides[i],
                    entry_lengths, lengths, step_index)
                new_k.append(k_s)
                new_v.append(v_s)
                return attn_out

            next_tokens = _token_block_argmax(
                params, config, tokens[:, None], attend)[:, 0]
            next_tokens = jnp.where(active, next_tokens, tokens)
            lengths = jnp.where(active, lengths + 1, lengths)
            budgets = jnp.where(active, budgets - 1, budgets)
            still = active & (budgets > 0) & (next_tokens != eos)
            return ((next_tokens, lengths, still, budgets, new_k,
                     new_v), (next_tokens, active))

        (tokens, lengths, active, budgets, k_sides, v_sides), \
            (emitted, emitted_active) = jax.lax.scan(
                body, (tokens, lengths, active, budgets, k_sides,
                       v_sides), jnp.arange(num_steps))

        # one merge per round: each slot's side tokens scatter into the
        # main cache at its round-entry offset.  Rows past a slot's
        # actual take are garbage landing at positions beyond its
        # length — dead cells, overwritten before they are ever
        # attended (same invariant as the admit scatter's padding).
        # Slots INACTIVE at round entry must not merge at all: a
        # mid-prefill slot's stale length points INTO the prompt its
        # extend chunks are writing, and the decode scan runs between
        # those chunks.
        merge_at = jnp.minimum(entry_lengths,
                               _cache_time(k_caches[0]) - num_steps)
        keep = entry_active[:, None, None, None]
        keep_s = entry_active[:, None, None]

        def merge(cache, side):
            if isinstance(cache, dict):
                quant = L.quantize_kv_cache(side)
                new_q = jax.vmap(
                    lambda row, srow, off: jax.lax.dynamic_update_slice(
                        row, srow, (0, off, 0)))(cache["q"], quant["q"],
                                                 merge_at)
                new_s = jax.vmap(
                    lambda row, srow, off: jax.lax.dynamic_update_slice(
                        row, srow, (0, off)))(cache["s"], quant["s"],
                                              merge_at)
                return {"q": jnp.where(keep, new_q, cache["q"]),
                        "s": jnp.where(keep_s, new_s, cache["s"])}
            updated = jax.vmap(
                lambda row, srow, off: jax.lax.dynamic_update_slice(
                    row, srow, (0, off, 0)))(cache, side, merge_at)
            return jnp.where(keep, updated, cache)

        new_k_caches = [merge(k_caches[i], k_sides[i])
                        for i in range(config.num_layers)]
        new_v_caches = [merge(v_caches[i], v_sides[i])
                        for i in range(config.num_layers)]
        return (emitted, emitted_active, tokens, lengths,
                new_k_caches, new_v_caches)

    return jax.jit(step_k_block,
                   static_argnames=("num_steps", "eos"),
                   donate_argnames=("k_caches", "v_caches"))


@functools.lru_cache(maxsize=16)
def _step_for(config: LlamaConfig):
    """Process-wide cache of compiled step builders: decoders sharing
    a config share ONE jit object, so the XLA executables inside it
    (keyed by shapes / static args) are reused across instances —
    rebuilding a decoder, or building several in one process (tests,
    multi-tenant serving), pays no recompile."""
    return _build_step(config)


# invalid side-buffer / context position: far past any legal cache
# index, so pos-based causal masks fail and scatter merges drop it
# (mode="drop") instead of corrupting a live row
_POS_INVALID = 1 << 30


def _spec_scan_body(config: LlamaConfig, cos, sin, k_spec: int,
                    ngram: int, params, eos, k_caches, v_caches,
                    entry_lengths, attention=None):
    """The speculative drafting/verify/acceptance scan body, shared
    VERBATIM by the dense (_build_spec_step) and paged
    (serving_paged._build_paged_spec_step) builders — like the
    attention bodies, ONE copy is what keeps the paged/dense
    bit-parity invariant safe from a fix landing on only one side.
    The builders differ only in how k_caches/v_caches are obtained
    (dense slot caches vs per-round pool gathers) and how the
    consumed side entries merge back at scan exit.

    `attention` is the verify attention seam (default
    _slot_attention_spec over slot-major caches); the paged pallas
    kernel path passes _kernel_attention_spec with k_caches/v_caches
    holding the raw pool leaves — the draft/accept machinery around
    it stays this one copy either way."""
    width = k_spec + 1
    if attention is None:
        attention = _slot_attention_spec
    slots_n = entry_lengths.shape[0]
    col = jnp.arange(width)[None]                        # [1, w]
    row = jnp.arange(slots_n)[:, None]                   # [S, 1]

    def draft(context, tokens, lengths):
        """Prompt-lookup drafts [S, k_spec]: match the last `ngram`
        tokens (the pending token + ngram-1 history tokens) at every
        history position, take the LATEST hit, and propose the tokens
        that followed it.  A miss proposes zeros — certain rejection,
        which costs nothing extra: the verify block runs at width
        1 + k_spec regardless, and acceptance never affects WHICH
        tokens are emitted, only how many per iteration."""
        ctx_len = context.shape[1]
        pos = jnp.arange(ctx_len)[None]                  # [1, C]
        hit = (pos >= ngram - 1) & (pos < lengths[:, None]) & \
            (context == tokens[:, None])
        for i in range(1, ngram):
            prev = jnp.take_along_axis(
                context, jnp.maximum(lengths[:, None] - i, 0), axis=1)
            # roll never wraps into the valid region: hit requires
            # pos >= ngram-1 >= i
            hit = hit & (jnp.roll(context, i, axis=1) == prev)
        # prefer the latest hit whose continuation is FULLY written
        # history (k real tokens follow it); fall back to the latest
        # with at least one — a frontier hit would draft unwritten
        # garbage and waste the verify width on certain rejections
        full = hit & (pos <= lengths[:, None] - 1 - k_spec)
        some = hit & (pos < lengths[:, None] - 1)
        best_full = jnp.max(jnp.where(full, pos, -1), axis=1)
        best_some = jnp.max(jnp.where(some, pos, -1), axis=1)
        best = jnp.where(best_full >= 0, best_full, best_some)  # [S]
        take = jnp.clip(best[:, None] + 1 + jnp.arange(k_spec)[None],
                        0, ctx_len - 1)
        drafts = jnp.take_along_axis(context, take, axis=1)
        return jnp.where(best[:, None] >= 0, drafts, 0)

    def body(carry, step_index):
        (tokens, lengths, active, budgets, context, k_sides,
         v_sides, pos_side) = carry
        drafts = draft(context, tokens, lengths)
        seq = jnp.concatenate([tokens[:, None], drafts], axis=1)
        base = step_index * width
        q_pos = lengths[:, None] + col                   # [S, w]
        # provisional: the whole block is live while it attends to
        # itself; rejected entries are invalidated after acceptance
        pos_side = jax.lax.dynamic_update_slice(pos_side, q_pos,
                                                (0, base))
        new_k, new_v = [], []

        def attend(i, layer, normed):
            attn_out, k_s, v_s = attention(
                layer, config, normed, cos, sin, k_caches[i],
                v_caches[i], k_sides[i], v_sides[i], pos_side,
                entry_lengths, lengths, base)
            new_k.append(k_s)
            new_v.append(v_s)
            return attn_out

        block_argmax = _token_block_argmax(params, config, seq,
                                           attend)      # [S, w]
        k_sides, v_sides = new_k, new_v
        # greedy acceptance: argmax after consuming seq[:j] must
        # reproduce draft j; the first miss takes the model's own
        # token (always emitted — that is the non-speculative step)
        match = (drafts == block_argmax[:, :-1])
        accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32),
                                       axis=1), axis=1)  # [S]
        can = (col <= accepted[:, None]) & \
            (col < budgets[:, None]) & active[:, None]
        stop = (block_argmax == eos) & can
        keep = jnp.cumprod(1 - stop.astype(jnp.int32), axis=1)
        keep_excl = jnp.concatenate(
            [jnp.ones((slots_n, 1), jnp.int32), keep[:, :-1]],
            axis=1)
        emit = can & (keep_excl > 0)
        emitted_n = jnp.sum(emit, axis=1).astype(jnp.int32)
        last = jnp.take_along_axis(
            block_argmax, jnp.maximum(emitted_n - 1, 0)[:, None],
            axis=1)[:, 0]
        tokens = jnp.where(emitted_n > 0, last, tokens)
        # context gets the whole block for active slots: entries
        # past the consumed run are garbage BEYOND the new length,
        # overwritten by the next iteration before the drafter
        # (masked to pos < length) could ever read them
        ctx_pos = jnp.where(active[:, None], q_pos, _POS_INVALID)
        context = context.at[row, ctx_pos].set(seq, mode="drop")
        lengths = lengths + emitted_n
        budgets = budgets - emitted_n
        active = active & (budgets > 0) & \
            ~jnp.any(stop & emit, axis=1)
        final_pos = jnp.where(col < emitted_n[:, None], q_pos,
                              _POS_INVALID)
        pos_side = jax.lax.dynamic_update_slice(pos_side, final_pos,
                                                (0, base))
        return ((tokens, lengths, active, budgets, context,
                 k_sides, v_sides, pos_side),
                (block_argmax, emit))

    return body


def _build_spec_step(config: LlamaConfig, k_spec: int, ngram: int):
    """Self-speculative decode scan (speculate_k): each iteration
    drafts `k_spec` tokens per slot by prompt lookup — an n-gram match
    against the slot's OWN device-side context buffer, no second
    model (Leviathan et al. 2023 acceptance over a self-drafter) —
    then scores the (1 + k_spec)-token block in ONE widened forward
    and advances each slot by its accepted run.  Greedy acceptance:
    draft j survives iff the model's argmax after consuming tokens
    < j equals it, and the first miss is replaced by the model's own
    argmax — so the emitted stream is PROVABLY the non-speculative
    greedy stream; speculation only changes how many tokens one
    weight-stream yields (the decode step is HBM-bound: the widened
    matmuls re-read the same weights once).

    Block-KV discipline with absolute positions: the main cache stays
    read-only through the scan; the round's tokens land in side
    buffers tagged `pos_side` (rejected drafts invalidated to
    _POS_INVALID) and scatter-merge into the main cache once per
    round, out-of-bounds entries dropping on the floor."""
    cos, sin = L.rope_frequencies(config.head_dim, config.max_seq_len,
                                  config.rope_theta)
    width = k_spec + 1

    def spec_step(params, tokens, lengths, active, budgets, context,
                  k_caches, v_caches, num_steps, eos):
        entry_lengths = lengths
        slots_n = tokens.shape[0]
        side_len = num_steps * width
        side_shape = (slots_n, config.num_kv_heads, side_len,
                      config.head_dim)
        k_sides = [jnp.zeros(side_shape, config.dtype)
                   for _ in range(config.num_layers)]
        v_sides = [jnp.zeros(side_shape, config.dtype)
                   for _ in range(config.num_layers)]
        pos_side = jnp.full((slots_n, side_len), _POS_INVALID,
                            jnp.int32)
        body = _spec_scan_body(config, cos, sin, k_spec, ngram,
                               params, eos, k_caches, v_caches,
                               entry_lengths)

        (tokens, lengths, active, budgets, context, k_sides, v_sides,
         pos_side), (emitted, emit_mask) = jax.lax.scan(
            body, (tokens, lengths, active, budgets, context, k_sides,
                   v_sides, pos_side), jnp.arange(num_steps))

        # scatter-merge: each consumed side entry lands at its absolute
        # position; _POS_INVALID entries (rejected drafts, inactive
        # slots, mid-prefill slots) drop instead of clamping into a
        # live row
        def merge(cache, side):
            if isinstance(cache, dict):
                quant = L.quantize_kv_cache(side)
                new_q = jax.vmap(
                    lambda c, s, p: c.at[:, p, :].set(s, mode="drop"))(
                    cache["q"], quant["q"], pos_side)
                new_s = jax.vmap(
                    lambda c, s, p: c.at[:, p].set(s, mode="drop"))(
                    cache["s"], quant["s"], pos_side)
                return {"q": new_q, "s": new_s}
            return jax.vmap(
                lambda c, s, p: c.at[:, p, :].set(s, mode="drop"))(
                cache, side, pos_side)

        new_k_caches = [merge(k_caches[i], k_sides[i])
                        for i in range(config.num_layers)]
        new_v_caches = [merge(v_caches[i], v_sides[i])
                        for i in range(config.num_layers)]
        return (emitted, emit_mask, tokens, lengths, context,
                new_k_caches, new_v_caches)

    return jax.jit(spec_step, static_argnames=("num_steps", "eos"),
                   donate_argnames=("context", "k_caches", "v_caches"))


@functools.lru_cache(maxsize=16)
def _spec_step_for(config: LlamaConfig, k_spec: int, ngram: int):
    """Same process-wide sharing as _step_for, for the speculative
    variant."""
    return _build_spec_step(config, k_spec, ngram)


class ContinuousDecoder:
    """Iteration-level scheduler over a fixed slot pool.

    submit() enqueues a request; drive it from the event engine
    (attach()) or call pump() manually.  Each pump round, decode-first:
    dispatch steps_per_sync decode iterations, dispatch prefill work
    (bucketed admits + chunk extends) BEHIND the scan so it runs in
    the host's sync gap, sync the emitted tokens plus earlier rounds'
    admit outputs, retire EOS/max-length slots through their
    callbacks.  Opt-in levers: kv_cache_dtype="int8" (half the cache
    read of the HBM-bound step), speculate_k=k (multi-token decoding
    via self-drafted prompt lookup, greedy-equivalent), weight_quant."""

    def __init__(self, params, config: LlamaConfig, max_slots: int = 8,
                 max_seq: int | None = None, eos_token: int | None = None,
                 prefill_buckets=(32, 128), steps_per_sync: int = 4,
                 t_block: int = 256, prefill_chunk: int | None = None,
                 prefill_budget: int | None = None,
                 weight_quant: bool = False,
                 kv_cache_dtype: str | None = None,
                 speculate_k: int = 0, speculate_ngram: int = 2,
                 name: str = "decoder", registry=None,
                 prefix_cache: PrefixKVCache | None = None,
                 paged_kv: bool = False, kv_block: int = 32):
        self.config = config
        # what the model declares for the paged path: its cache's
        # leaves, its layer functions, the serving paths its pool is
        # carried through (serving_paged.PagedModel)
        self._model = config.paged_model()
        if ATTENTION_IMPL not in (None, "two_pass", "paged_kernel"):
            raise ValueError(
                f"AIKO_DECODE_ATTENTION / serving.ATTENTION_IMPL must be "
                f"'two_pass' or 'paged_kernel' (or unset), got "
                f"{ATTENTION_IMPL!r}")
        # int8 KV cache (ISSUE 7): the slot caches store int8 values
        # with per-(slot, head, position) f32 scales
        # (layers.quantize_kv_cache).  Admits/extends write quantized
        # rows off the decode critical path; the decode scan reads the
        # int8 buffer as the dot operand and FOLDS the scales into
        # scores/weights — the HBM-bound step's dominant read halves.
        # Greedy outputs are NOT bit-identical to the full-precision
        # cache (int8 rounding of stored K/V), so the mode is opt-in
        # like weight_quant.
        dtype_norm = (kv_cache_dtype or "native").lower()
        if dtype_norm not in ("native", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be None/'native'/'int8', got "
                f"{kv_cache_dtype!r}")
        self.kv_int8 = dtype_norm == "int8"
        # self-speculative decoding (ISSUE 7): each scan iteration
        # drafts speculate_k tokens by prompt lookup over a device-side
        # context buffer and verifies the widened block in one forward;
        # greedy acceptance makes the emitted stream identical to the
        # non-speculative path.  The side buffers grow to
        # steps_per_sync * (1 + speculate_k) entries — size
        # steps_per_sync for the same per-round token output, not on
        # top of it.
        self.speculate_k = int(speculate_k or 0)
        if self.speculate_k < 0:
            raise ValueError(f"speculate_k must be >= 0, got "
                             f"{speculate_k}")
        self.speculate_ngram = int(speculate_ngram)
        if self.speculate_k and self.speculate_ngram < 1:
            raise ValueError("speculate_ngram must be >= 1")
        # weight-only int8 (W8A16): every linear's weight tree-rewritten
        # to {w8, s} once here — linear()/linear_logits consume it
        # transparently across prefill, chunked extends, and the
        # decode scan.  Half the weights' bytes; not measured in any
        # cell on this installation.  Greedy outputs are NOT
        # bit-identical to bf16 (int8 rounding), and MoE routers are
        # excluded (top-k flips).
        if weight_quant:
            params = L.quantize_linear_tree(params)
        self.weight_quant = bool(weight_quant)
        self.params = params
        # a path that this model's pool is not carried through refuses
        # HERE, by name: none runs another model's code on its cache
        for asked, path, what in (
                (not paged_kv, "dense_cache",
                 "the dense slot cache (paged_kv=False)"),
                (self.kv_int8, "int8_kv", "an int8 KV cache"),
                (self.speculate_k, "speculation",
                 "speculative decoding (speculate_k)"),
                (prefix_cache is not None, "prefix_cache",
                 "a prefix cache"),
                (weight_quant, "weight_quant",
                 "weight-only int8 (weight_quant)"),
                (not self._weights_on_one_device(), "tensor_parallel",
                 "tensor-parallel (sharded) weights")):
            if asked:
                self._require(path, what)
        self.max_slots = max_slots
        self.max_seq = max_seq or config.max_seq_len
        self.eos_token = eos_token
        self.steps_per_sync = steps_per_sync
        # chunked prefill: prompts longer than the largest bucket are
        # admitted to a slot immediately but their prefill runs
        # `prefill_chunk` tokens per pump round (a compiled cache-extend
        # program), so one long prompt stalls every active decode slot
        # by at most ~one chunk instead of its full length — the
        # classic inter-token-latency spike under prompt-heavy load.
        # Also lifts the prompt-length cap from the largest bucket to
        # max_seq.  None = single-shot bucketed prefill only.
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        if self.prefill_chunk is not None and not \
                (1 <= self.prefill_chunk <= self.max_seq - 1):
            # fail at construction, not mid-serving with a wedged slot
            raise ValueError(
                f"prefill_chunk must be in [1, {self.max_seq - 1}], "
                f"got {self.prefill_chunk}")
        # per-round prefill token budget: bucketed admits stop (FIFO,
        # no reordering) and chunk advances are rationed once a round
        # has dispatched this much prefill work.  None = unbounded.
        self.prefill_budget = int(prefill_budget) if prefill_budget \
            else None
        # granularity of the DENSE cache's time axis: each round reads
        # cache[:, :, :t_cap] with t_cap the smallest multiple of
        # t_block covering the longest active context (a resize is a
        # device copy and one compiled program per distinct t_cap —
        # max_seq/t_block variants).  In paged mode it sizes the pool's
        # first allocation and its growth and NOTHING ELSE: no paged
        # program's width follows it (tables, extends and admits keep
        # max_seq; the step takes its width from _attend_ladder).
        self.t_block = max(1, int(t_block))
        # buckets beyond the cache's time axis would blow up the admit
        # scatter — clamp, dedupe, keep sorted
        self.prefill_buckets = tuple(sorted(
            {min(int(b), self.max_seq - 1) for b in prefill_buckets}))
        self.logger = get_logger(f"serving.{name}")
        self.on_idle = None          # hook: fires when the last slot
                                     # retires and nothing is pending
        self.on_token = None         # hook: on_token(request_id, slot,
                                     # token, now) for every token as it
                                     # is delivered, before retirement

        # paged KV (ISSUE 15): the slot caches become ONE refcounted
        # block pool plus per-slot int32 block tables — a prefix hit
        # aliases cached blocks into the table (zero copy), harvest is
        # a refcount bump, the disaggregated install lands once.  The
        # compiled step gathers a slot-major view from the pool and
        # runs the SAME attention bodies at the same shapes, so greedy
        # output is bit-identical to the dense cache (the parity
        # matrix in tests/test_paged_kv.py asserts it across int8 /
        # chunked / spec / mid-stream / disagg).  Dense stays the
        # default and the parity oracle.
        self.paged = bool(paged_kv)
        self.kv_block = int(prefix_cache.block_tokens) \
            if prefix_cache is not None else int(kv_block)
        if self.paged and self.kv_block < 1:
            raise ValueError(
                f"kv_block must be >= 1, got {kv_block}")
        if self.paged and self.kv_block % self._model.block_multiple:
            raise ValueError(
                f"{type(config).__name__}: kv_block must be a multiple of "
                f"{self._model.block_multiple} (the model reads a leaf by "
                f"whole tiles of rows), got {self.kv_block}")

        # the dense cache's TIME axis is allocated at the workload, not
        # at max_seq: it grows/shrinks in t_block steps to cover the
        # longest active context (_fit_caches).  HBM capacity AND
        # per-step bandwidth then scale with actual occupancy — a
        # max_seq allocation makes every decode step stream max_seq
        # worth of cache (an in-program slice doesn't help: it
        # materializes, measured 3× attention bytes).  A paged decoder
        # holds no such axis: _cache_t is then the constant width of
        # its tables, extends and admits, max_seq, and what follows
        # the workload is the width its step attends at, round by
        # round (_attend_width).
        start_t = min(self.t_block, self.max_seq)
        self._cache_t = self.max_seq if self.paged else start_t

        # prefix/KV reuse cache (ISSUE 13): hash-addressed block
        # sharing across requests and sessions.  The cache stores rows
        # in THIS decoder's storage layout (int8 dicts when kv_int8 —
        # a hit is a bytes win too); bind() enforces layout agreement
        # when several decoders share one cache.  Harvest at retire,
        # longest-match at admit, copy-in via _prefix_copy_fn_for.
        self.prefix_cache = prefix_cache
        self._ledger = None             # KV memory ledger (ISSUE 20)
        item = jnp.dtype(config.dtype).itemsize
        # the layout tuple is the geometry handshake for binding AND
        # for the disaggregated wire — a cacheless paged decoder still
        # needs it for the direct slot-table install (ISSUE 15).  It
        # speaks of K and V leaves of one shape: the first leaf's
        from .serving_paged import first_leaf
        heads, lanes = first_leaf(config)
        self._kv_layout = (config.num_layers, heads, lanes,
                           str(config.dtype), self.kv_int8,
                           self.kv_block, item)
        if prefix_cache is not None:
            prefix_cache.bind(self._kv_layout, paged=self.paged)
            if not self.paged and prefix_cache.paged:
                raise ValueError(
                    "prefix cache holds paged (pool-resident) blocks; "
                    "a dense decoder cannot bind it")

        if self.paged:
            from .serving_paged import BlockPool
            block = self.kv_block
            # table width covers the worst-case extent a round's merge
            # can write (max_seq + the merge's headroom: cells
            # that nobody attends, so no step's views reach them)
            headroom = 0 if self.speculate_k else steps_per_sync
            self._table_blocks = -(-(self.max_seq + headroom) // block)
            initial = max_slots * (-(-start_t // block))
            if prefix_cache is not None and prefix_cache.paged:
                # a decoder sharing an already-attached cache ADOPTS
                # its pool (attach_pool's one-pool-per-cache contract;
                # bind() above proved the geometry agrees) and reserves
                # its own slot coverage on top of what's resident.
                self.pool = prefix_cache.pool
                self.pool.reserve(self.pool.num_blocks - 1 + initial)
            else:
                self.pool = BlockPool(
                    self.config, block, self.kv_int8,
                    initial_blocks=initial,
                    grow_blocks=max(
                        1, max_slots * self.t_block // block),
                    name=name, registry=registry)
                if prefix_cache is not None:
                    prefix_cache.attach_pool(self.pool)
                    if prefix_cache.max_bytes:
                        # anticipate the cache's pool residency up
                        # front: a pool capacity change retraces every
                        # compiled program that touches it, so steady
                        # state should be reachable without mid-serving
                        # growth.  Bounded by one full-max_seq slot
                        # population — the same worst case the dense
                        # cache could reach.
                        anticipated = min(
                            prefix_cache.max_bytes
                            // self.pool.block_nbytes,
                            max_slots * (-(-self.max_seq // block)))
                        self.pool.reserve(initial + anticipated)
            self._tables_np = np.zeros(
                (max_slots, self._table_blocks), np.int32)
            # reused per-round gather buffer for admit/extend table
            # rows: the pump hot path must not allocate a fresh host
            # array every batch (lint-hot-alloc); consumers copy it
            # to device with jnp.array before the next round reuses it
            self._tables_scratch = np.zeros_like(self._tables_np)
            self._tables_dirty = True
            self._tables_dev = None
            # the paged pallas-kernel toggle is latched here — builder
            # cache keys include it, so oracle and kernel decoders
            # coexist in one process (parity tests build one of each).
            # paged_kernel: the kernel was ASKED for (the plain and the
            # speculative step and the extend take it).  step_kernel:
            # the step this decoder runs takes it, asked for, or told
            # nothing where the kernel suits the decoder: on a TPU
            # (elsewhere it would run in the interpreter), at a pool
            # whose live blocks it walks by hand (its table body reads
            # every entry of every slot: no gain over views), with
            # weights that sit on one device.  A model may have a kernel
            # for its OWN step (`PagedModel.step_kernel`: a recurrence
            # over slot state, ISSUE 34; its own walk of the pool,
            # ISSUE 39): the same rule, the same flag
            on_tpu = jax.default_backend() == "tpu"
            walks = self._model.walks(config, self.kv_int8, not on_tpu)
            self._model_kernel = self._model.step_kernel is not None \
                and self._model.step_kernel(config, not on_tpu)
            self.paged_kernel = ATTENTION_IMPL == "paged_kernel"
            self.step_kernel = self.paged_kernel or (
                ATTENTION_IMPL is None and not self.speculate_k
                and on_tpu and (walks == "kernel" or self._model_kernel)
                and self._weights_on_one_device())
            # a step whose kernel walks each slot's own live blocks has
            # no width: the table goes in whole, ONE program a step
            # count.  The gather step builds its views at a width of
            # the ladder, and the kernel's table body (a head of 64, an
            # int8 pool) follows the table as far as it is cut
            # (or the model reads the pool by hand, kernel or not)
            self._walks_live = walks == "model" or (
                self.step_kernel and walks == "kernel")
            self._attend_widths = (self.max_seq,) if self._walks_live \
                else _attend_ladder(self.max_seq, block)
            # what the model keeps of a slot and not of a token
            # (ISSUE 33): device arrays beside the pool, which step,
            # admit and extend take and hand back rewritten
            self.slot_state = None
            if getattr(config, "slot_state", ()):
                from .serving_paged import SlotState
                if not prefill_chunk or self.max_seq % prefill_chunk:
                    # a chunk that slid back over positions already
                    # prefilled would run them through the state twice
                    raise ValueError(
                        "a model with slot state prefills long prompts "
                        "chunk after chunk from where the last one "
                        "ended: prefill_chunk must be set and divide "
                        f"max_seq, got {prefill_chunk} and {self.max_seq}")
                self.slot_state = SlotState(config, max_slots)
            # (num_steps, width, state's placement) -> executable
            self._step_programs: dict = {}
            # per-slot owned/aliased pool block ids, in table order
            self._slot_blocks: list[list] = \
                [[] for _ in range(max_slots)]
            self._k = None
            self._v = None
        else:
            self.pool = None
            self.slot_state = None
            self.paged_kernel = self.step_kernel = False
            self._walks_live = False
            self._k = self._zero_caches()
            self._v = self._zero_caches()
        self._tokens = jnp.zeros((max_slots,), jnp.int32)
        self._lengths = jnp.zeros((max_slots,), jnp.int32)
        # device-side token history per slot, written by admits /
        # extends / the verify scan — what the speculative drafter
        # matches against.  A [1, 1] stub when speculation is off so
        # the admit/extend programs keep ONE signature either way
        # (threaded through and returned unchanged).
        self._context = jnp.zeros(
            (max_slots, self.max_seq) if self.speculate_k else (1, 1),
            jnp.int32)
        self._resize_fns: dict = {}

        self._prefix_pad = None         # lazy zero pad block (copy-in)
        # measured host dispatch seconds per prefill token (EWMA): the
        # prompt-cost term of estimated_admit_wait, which prefix hits
        # credit away (ISSUE 13 satellite)
        self._prefill_token_ewma: float | None = None

        if self.paged:
            from .serving_paged import (_paged_spec_step_for,
                                        _paged_step_for)
            self._step = _paged_spec_step_for(
                config, self.speculate_k, self.speculate_ngram,
                self.step_kernel) \
                if self.speculate_k \
                else _paged_step_for(config, self.step_kernel)
            if walks == "model":
                how = "the model's own reads of the pool" + (
                    " (its step's pallas kernels)"
                    if self.step_kernel and self._model_kernel else "")
            elif self._walks_live:
                how = "the paged kernel, each slot's live blocks"
            else:
                how = "%s at widths %s" % (
                    "the paged kernel's table body" if self.step_kernel
                    else "gathered views", self._attend_widths)
            self.logger.info("decode step attends through %s", how)
            if self.slot_state is not None:
                self.logger.info(
                    "decode step's recurrence over slot state runs as %s",
                    "the pallas kernel, the state of the slots that "
                    "decode once in and once out"
                    if self.step_kernel and self._model_kernel
                    else "XLA's program over every slot's state")
                if self._model.scan_kernel is not None:
                    self.logger.info(
                        "a prompt's pieces (admit, extend) run the "
                        "recurrence's chunked form as %s",
                        "the pallas kernel, one call a layer"
                        if self._model.scan_kernel(config, not on_tpu)
                        else "XLA's program, the model's own chunked form")
            from .serving_paged import run_write_form
            self.logger.info(
                "decode step writes a round's rows to the pool as %s",
                "rows at sparse positions" if self.speculate_k
                else run_write_form(config, self.steps_per_sync,
                                    self.kv_block))
        else:
            self._step = _spec_step_for(config, self.speculate_k,
                                        self.speculate_ngram) \
                if self.speculate_k else _step_for(config)
        # in-flight prefix dedup window (ISSUE 14 satellite): leading
        # block key -> the request currently prefilling that chain.
        # Bounded by the slot pool: entries unregister at early
        # harvest or retire, and only admitted requests register.
        self._inflight_chains: dict[str, DecodeRequest] = {}
        self._prefill_fns: dict = {}
        self._slots: list[DecodeRequest | None] = [None] * max_slots
        self._pending: list[DecodeRequest] = []
        # admit/extend output stash: (firsts device array, [(row,
        # request), ...]) per dispatch — resolved at the NEXT round's
        # sync, by which point the prefill program has run behind the
        # decode scan (single in-order device stream), so the fetch
        # never stalls the round
        self._admit_waves: list = []
        self._timer = None
        # preallocated per-round host buffers: pump/_round_plan are the
        # per-step hot path (graft-check lint-hot-alloc polices them)
        self._active_np = np.zeros((max_slots,), bool)
        self._budgets_np = np.zeros((max_slots,), np.int32)
        # each slot's length on the device at round entry, for a kernel
        # round's record (_round_plan fills it)
        self._entry_np = np.zeros((max_slots,), np.int64)
        # bytes of K and V one cache position holds over all layers
        # and slots (what the prefix copy-in and harvest counters
        # charge a position).  int8 cache: D int8 values + one f32
        # scale per (slot, head, position) — ~(D+4)/(2D) of the bf16
        # bytes
        from .serving_paged import token_nbytes
        self._kv_bytes_per_t = int(max_slots *
                                   token_nbytes(config, self.kv_int8))
        # cumulative decode-loop counters, mirrored onto the process
        # metrics registry (serving_decoder_total{kind=...}) so the
        # bench and the dashboard metrics pane read the SAME numbers
        # the decoder increments (ISSUE 5).  tokens_decode /
        # tokens_prefill split what the old single token flow hid:
        # decode-scan emissions vs prompt tokens prefilled — the
        # overhead ISSUE 7 moves off the decode round is exactly their
        # ratio.
        # decode-round phase profiler (ISSUE 11, 24): every pump round's
        # wall time attributed to named phases (plan / scan dispatch /
        # admit+extend dispatch / host sync / wave resolve / deliver),
        # as sums, as one record a round in a bounded ring, and as
        # spans of a profiler session.  Always on: one perf_counter
        # read per boundary.
        from .observe.journey import JourneyLog
        from .observe.metrics import MirroredStats, default_registry
        self.profiler = PhaseProfiler(name)
        self._registry = registry or default_registry()
        # request journeys + mergeable SLO sketches (ISSUE 12): every
        # request gets a RequestJourney correlated to the ambient
        # TraceContext, and TTFT/ITL observations land in per-tenant
        # DDSketch families (serving_{ttft,itl}_seconds{decoder,tenant})
        # whose retained-snapshot form MERGES across processes — the
        # fleet-true percentile surface the health plane alerts on,
        # with the worst requests' trace ids as exemplars.
        self.journeys = JourneyLog(name=name, proc=name,
                                   registry=self._registry)
        self._slo_sketches: dict = {}
        self.stats = MirroredStats(
            {"steps": 0, "rounds": 0, "completed": 0,
             "prefills": 0, "occupancy_sum": 0.0,
             "prefill_s": 0.0, "decode_s": 0.0,
             "useful_steps": 0, "wasted_steps": 0,
             "tokens_decode": 0, "tokens_prefill": 0,
             "spec_proposed": 0, "spec_accepted": 0,
             "accepted_per_step": 0.0,
             "prefill_chunks": 0,
             "chunk_admits": 0, "prefix_admits": 0,
             "round_prefill_tokens_max": 0,
             "admission_shed": 0,
             "dedup_deferred": 0, "dedup_shared": 0,
             # paged A/B surfaces (ISSUE 15): bytes a prefix hit
             # copied into the slot (paged: 0 — aliasing), bytes
             # harvest copied out at retire (paged: 0 — refcount
             # bump), and copy-on-extend events (paged only: a write
             # into a SHARED block copies it first)
             "prefix_copy_bytes": 0, "harvest_copy_bytes": 0,
             "cow_copies": 0, "cow_copy_bytes": 0,
             "install_misaligned": 0,
             # graceful drain (ISSUE 19): submissions refused while
             # draining, requests handed back for re-routing, and
             # deadline checkpoints that harvested a live slot's
             # chain instead of letting it finish
             "drain_refused": 0, "drain_evacuated": 0,
             "drain_checkpoints": 0,
             # requests whose slot state (a recurrent layer's) was
             # started from zeros (ISSUE 33)
             "slot_states_zeroed": 0}
            # what the model's step counts of itself (an expert
            # layer's routing), brought back in the round's one fetch
            | {name: 0 for name in self._model.counters},
            metric="serving_decoder_total",
            help="continuous-decoder events by kind",
            # levels and time-sums stay dict-only: a high-water mark or
            # a seconds accumulator inside an events-by-kind counter
            # family would make rate()/sum() over the family meaningless
            registry=self._registry,
            skip=("occupancy_sum", "prefill_s", "decode_s",
                  "accepted_per_step", "round_prefill_tokens_max",
                  "prefix_copy_bytes", "harvest_copy_bytes",
                  "cow_copy_bytes"))
        # SLO samples (seconds): TTFT per request, mean inter-token
        # latency per retired request, and each request's worst
        # inter-sync stall — the number chunked prefill bounds
        self.ttft_samples: deque = deque(maxlen=8192)
        self.itl_samples: deque = deque(maxlen=8192)
        self.gap_samples: deque = deque(maxlen=8192)
        self._round_prefill_tokens = 0
        # beside it for the round's record (ISSUE 36): the admit and
        # extend programs the round dispatched, the positions already
        # in the cache that their rows' attention reads, and the prompt
        # tokens dispatched since the last step was: they stand on the
        # device ahead of the next one
        self._round_prefill_pieces = 0
        self._round_prefix_tokens = 0
        self._prefill_ahead = 0
        # EWMA of recent working-round wall time (alpha 0.3), fed by
        # pump(): the deadline-aware admission estimate's time base
        self._round_ewma: float | None = None
        # graceful drain (ISSUE 19): armed by drain() — submit()
        # refuses new work, pump() checkpoints in-flight slots when
        # the deadline passes, and the completion callback fires once
        # when the decoder reaches idle with every live chain
        # harvested.  The gauge is the autoscaler's shrink-safety
        # signal: live slots + queued requests, published per decoder
        # so a fleet shrink can refuse a victim that still holds work.
        self._draining = False
        self._drained = False
        self._drain_deadline: float | None = None
        self._drain_evacuate = None
        self._drain_complete = None
        self._gauge_active = self._registry.gauge(
            "serving_active_slots",
            "live decode slots + queued requests (the drain/shrink "
            "in-flight safety signal)", labels={"decoder": name})

    # -- public API --------------------------------------------------------
    def estimated_admit_wait(self, prompt=None,
                             tenant: str = "") -> float | None:
        """Coarse time-to-first-token wait estimate for the NEXT
        submitted request: at least one working round when a slot is
        free, scaled by the backlog's share of the slot pool when all
        slots are taken.  Deliberately a cheap lower-bound heuristic —
        it exists to shed requests that are grossly doomed under
        overload (the deadline-aware admission gate, ISSUE 9), not to
        predict TTFT; None until a round has been measured, because
        admission must not drop work on a number it doesn't have.

        With `prompt`, the estimate adds that prompt's prefill cost at
        the measured per-token dispatch rate, CREDITING expected
        prefix-cache hits (a pure block-key probe, no side effects) —
        a cached-heavy tenant's real admit cost is near the round
        floor, and shedding or autoscaling on the cold re-prefill
        number would over-shed/over-scale it (ISSUE 13)."""
        if self._round_ewma is None:
            return None
        free = sum(1 for request in self._slots if request is None)
        waiting = len(self._pending)
        if waiting < free:
            wait = self._round_ewma
        else:
            wait = self._round_ewma * \
                (1.0 + (waiting - free + 1) / max(1, self.max_slots))
        if prompt is not None and self._prefill_token_ewma:
            uncached = len(prompt)
            if self.prefix_cache is not None and len(prompt) > 1:
                _, hit = self.prefix_cache.match(
                    tenant, prompt, limit=len(prompt) - 1)
                uncached -= hit
                if hit < len(prompt) - 1 and self.prefix_cache.tiered:
                    # admission-probe promotion kick (ISSUE 17): the
                    # probe knows this prompt is coming before its
                    # admit round — start re-landing its host-tier
                    # chain tail now (non-blocking)
                    self.prefix_cache.prefetch(tenant, prompt)
            wait += uncached * self._prefill_token_ewma
        return wait

    def _note_prefill_rate(self, tokens: int, elapsed: float) -> None:
        """Fold one prefill dispatch's (tokens, wall) into the
        per-token EWMA the admission estimate charges prompts at.
        Asymmetric on purpose: a LOWER rate is taken outright while a
        higher one is damped and clamped — dispatch walls that include
        a jit compile (first sight of a (chunk, width, cache_t) shape)
        are orders of magnitude above the real cost, and an EWMA that
        believed them would shed deadline-carrying prompts on a number
        that is compiler overhead, not serving cost.  One clean round
        snaps the estimate back to the measured floor."""
        if tokens <= 0 or elapsed <= 0.0:
            return
        rate = elapsed / tokens
        current = self._prefill_token_ewma
        if current is None or rate < current:
            self._prefill_token_ewma = rate
        else:
            self._prefill_token_ewma = \
                0.7 * current + 0.3 * min(rate, 10.0 * current)

    def _slo_sketch(self, kind: str, tenant: str,
                    prefill: str | None = None):
        """Per-(kind, tenant[, prefill]) mergeable SLO sketch, lazily
        registered: serving_{kind}_seconds{decoder, tenant[, prefill]}
        (ISSUE 12).  Tenant is a BOUNDED label (tenant names come from
        serving policy, not request identity — lint-metric-label's
        discipline); `prefill` splits the TTFT population into
        cached/cold (ISSUE 13) so the SLO report and the conversation
        bench can quote both."""
        key = (kind, tenant, prefill)
        sketch = self._slo_sketches.get(key)
        if sketch is None:
            labels = {"decoder": self.journeys.name,
                      "tenant": tenant or "default"}
            if prefill is not None:
                labels["prefill"] = prefill
            sketch = self._registry.sketch(
                f"serving_{kind}_seconds",
                f"per-request {kind} seconds (mergeable quantile "
                f"sketch with worst-request trace-id exemplars)",
                labels=labels)
            self._slo_sketches[key] = sketch
        return sketch

    def submit(self, request_id: str, prompt, max_new_tokens: int,
               callback, deadline: float | None = None,
               tenant: str | None = None,
               prefill_label: str | None = None,
               kv_blocks: tuple | None = None,
               progress_callback=None) -> bool:
        """Enqueue one request; returns False when deadline-aware
        admission rejected it instead (the callback is NOT invoked —
        the caller owns the refusal).  `deadline` (absolute,
        time.monotonic seconds) is the request's END-TO-END completion
        target — the frame deadline the serving walk carries, crossed
        into this clock domain (PE_LlamaAgent does the conversion).
        `tenant`, when given, overrides the admission note's tenant —
        the caller that also keys session KV handles (PE_LlamaAgent)
        passes the SAME normalized key here, so harvested blocks and
        session pins land under one tenant root (ISSUE 13).
        Admission uses the estimated admit wait (a time-to-FIRST-token
        bound) as its necessary condition: a request that cannot even
        reach its first token inside the budget is refused NOW, so the
        caller fails over or degrades instead of queueing doomed work
        (ISSUE 9); the journey's deadline margin is judged at
        completion against the same end-to-end target (ISSUE 12).

        Every submission opens a RequestJourney (ISSUE 12) correlated
        to the AMBIENT TraceContext — the serving walk runs under the
        caller's context, so the journey's spans join the same trace as
        the wire hop — and claims the pipeline admission note (verdict
        + measured fair-queue wait) posted for that trace id."""
        from .observe.journey import RequestJourney, take_admission_note
        from .observe.tracing import current_trace
        now = time.monotonic()
        context = current_trace()
        note = take_admission_note(context.trace_id) \
            if context is not None else None
        journey = RequestJourney(
            request_id, now,
            trace_id=context.trace_id if context is not None else "",
            parent_span_id=context.span_id
            if context is not None else "",
            tenant=tenant if tenant is not None
            else (note or {}).get("tenant", ""),
            tier=(note or {}).get("tier", 1),
            deadline=deadline,
            admission_verdict=(note or {}).get("verdict", ""),
            admission_wait_s=(note or {}).get("queue_wait_s"),
            prompt_tokens=len(prompt))
        if self._draining:
            # drain armed (ISSUE 19): no new admissions — the caller
            # re-routes to a healthy runtime (pipeline failover) or
            # the drain destination.  Counted AND journeyed so the
            # soak can assert the refusal path and a trace shows why
            # this request bounced.
            self.stats["drain_refused"] += 1
            self.journeys.finish(journey, time.monotonic(),
                                 outcome="drained")
            return False
        # keep the TAIL on overflow (recent context matters most).
        # Without chunked prefill the largest bucket is a hard cap (an
        # oversized prompt would blow up _admit's scatter); with it,
        # long prompts stream in chunks and the cap is max_seq itself.
        # Normalized BEFORE admission so the wait estimate's prefill
        # term (and its prefix-cache probe) sees the prompt that will
        # actually admit.
        if self.prefill_chunk:
            limit = self.max_seq - 1
        else:
            limit = min(self.max_seq - 1, self.prefill_buckets[-1])
        # empty prompts would seed generation from a pad position —
        # normalize to a single pad token at position 0
        prompt = [int(t) for t in prompt] or [0]
        truncated = len(prompt) > limit
        prompt = prompt[-limit:]
        if self.prefix_cache is not None and len(prompt) > 1 and \
                self.prefix_cache.tiered:
            # submit-time promotion kick (ISSUE 17): the admit round
            # is at least one pump tick away — a prefetch kicked here
            # overlaps the whole queue wait, so the admit probe finds
            # the chain staged (or already resident) instead of
            # paying the H2D inline
            self.prefix_cache.prefetch(journey.tenant, prompt)
        if deadline is not None:
            wait = self.estimated_admit_wait(prompt=prompt,
                                             tenant=journey.tenant)
            if wait is not None and now + wait >= float(deadline):
                self.stats["admission_shed"] += 1
                self.journeys.finish(journey, time.monotonic(),
                                     outcome="shed")
                return False
        if prefill_label:
            # population override (ISSUE 14): a remote-prefilled
            # request is "cached" mechanically (the shipped chain
            # hits) but belongs to its own TTFT/journey population
            journey.prefill_label = str(prefill_label)
        request = DecodeRequest(
            request_id, prompt, int(max_new_tokens), callback,
            submit_time=now, journey=journey, deadline=deadline,
            tenant=journey.tenant,
            prefill_label=str(prefill_label or ""),
            progress_callback=progress_callback)
        if kv_blocks:
            # direct slot-table install (ISSUE 15 satellite): the
            # caller pre-installed pool blocks covering the prompt's
            # leading tokens (install_shipped_blocks); admit aliases
            # them into the slot's table and prefills only the suffix.
            # At least one suffix token must remain to produce the
            # first output, so a whole-prompt cover drops its final
            # block back to the pool here.  Ownership transfers on
            # acceptance only — a shed above returned False with the
            # ids untouched, so the caller's release stays balanced.
            if not self.paged:
                raise ValueError(
                    "kv_blocks install needs a paged decoder")
            covered, ids = int(kv_blocks[0]), list(kv_blocks[1])
            if truncated:
                # the ids cover the ORIGINAL prompt's head — exactly
                # the tokens the tail-truncation above removed — so
                # aliasing them would attend to KV for a different
                # prompt and silently emit wrong tokens.  In-repo
                # callers cap with serving_disagg._prompt_cap BEFORE
                # installing (this never fires on that path); a direct
                # API caller pays a cold prefill instead.
                self.logger.warning(
                    "kv_blocks install for %s dropped: prompt "
                    "exceeds the admit cap %d (%d-token cover); "
                    "cold prefill", request_id, limit, covered)
                self.stats["install_misaligned"] += 1
                self.pool.release_blocks(ids, tenant=journey.tenant)
            else:
                block = self.kv_block
                usable = min(covered, len(ids) * block,
                             ((len(prompt) - 1) // block) * block)
                keep = max(0, usable // block)
                if len(ids) > keep:
                    self.pool.release_blocks(ids[keep:],
                                             tenant=journey.tenant)
                request.kv_block_ids = ids[:keep]
                request.prefix_hit = keep * block
                request.prefix_probed = True
        self._pending.append(request)
        self._note_active()
        return True

    def attach_ledger(self, ledger) -> None:
        """Wire the KV memory ledger (ISSUE 20) through this
        decoder's storage stack: the prefix cache fans it out to its
        pool and host tiers; a cacheless paged decoder attaches the
        pool directly.  Dense slot caches are preallocated arrays —
        nothing per-tenant to account without a prefix cache."""
        self._ledger = ledger
        if self.prefix_cache is not None:
            self.prefix_cache.attach_ledger(ledger)
        elif self.paged and ledger is not None:
            self.pool.attach_ledger(ledger)

    @property
    def ledger(self):
        return self._ledger

    def attach(self, engine, period: float = 0.002) -> int:
        # idempotent: re-attaching while already pumping (e.g. a stream
        # reopens during a deferred teardown) must not orphan the
        # first timer
        if self._timer is None:
            self._timer = engine.add_timer_handler(self.pump, period)
        return self._timer

    @property
    def attached(self) -> bool:
        return self._timer is not None

    def detach(self, engine) -> None:
        if self._timer is not None:
            engine.remove_timer_handler(self._timer)
            self._timer = None

    @property
    def active_count(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    @property
    def idle(self) -> bool:
        return self.active_count == 0 and not self._pending

    def _note_active(self) -> None:
        self._gauge_active.set(self.active_count + len(self._pending))

    # -- graceful drain (ISSUE 19) -----------------------------------------
    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        return self._drained

    def _require(self, path: str, what: str) -> None:
        """Refuse, by name, a serving path that the model's pool is
        not carried through (PagedModel.supports)."""
        if path not in self._model.supports:
            from .serving_paged import layer_leaves
            kinds = sorted(set(layer_leaves(self.config)))
            raise ValueError(
                f"{type(self.config).__name__}: {what} is not carried "
                f"for this model's cache (a layer's leaves: {kinds}); "
                f"its paged decoder serves native rows, unshared, on one "
                f"device")

    def drain(self, deadline: float | None = None,
              on_evacuate=None, on_complete=None) -> list:
        """Arm a graceful wind-down: stop admitting, let in-flight
        slots finish (or checkpoint them at the first round boundary
        past `deadline`, relative seconds), harvest every live chain
        into the prefix cache, and fire `on_complete(self)` once when
        the decoder is idle.  Queued (never-admitted) requests are
        evacuated NOW and returned as plain descriptors — request_id,
        prompt, generated-so-far, max_new_tokens, callback, deadline,
        tenant — for the caller to re-submit elsewhere; checkpointed
        in-flight slots evacuate the same way through `on_evacuate`.
        Without an evacuation route a checkpointed request's callback
        is invoked with whatever generated so far — degraded, never
        silently dropped.  Idempotent: re-arming tightens the deadline
        but never un-drains (resume() does that)."""
        self._require("drain", "drain and migration (drain())")
        now = time.monotonic()
        self._draining = True
        self._drained = False
        self._drain_deadline = None if deadline is None \
            else now + float(deadline)
        if on_evacuate is not None:
            self._drain_evacuate = on_evacuate
        if on_complete is not None:
            self._drain_complete = on_complete
        pending, self._pending = self._pending, []
        evacuated = [self._evacuate(request, now) for request in pending]
        if self.idle:
            self._drain_finish()
        self._note_active()
        return evacuated

    def resume(self) -> None:
        """Re-open admission after a drain (planned-restart rollback,
        tests): clears the drain latch; the decoder serves again."""
        self._draining = False
        self._drained = False
        self._drain_deadline = None
        self._drain_evacuate = None
        self._drain_complete = None

    def _evacuate(self, request: DecodeRequest, now: float) -> dict:
        """Close one request's journey as evacuated and hand back a
        re-submittable descriptor (prompt + generated so far: the
        continuation's prompt on the next runtime)."""
        if request.inflight_key and \
                self._inflight_chains.get(request.inflight_key) \
                is request:
            # a queued dedup leader leaves with its registration —
            # otherwise a post-resume duplicate waits forever on a
            # chain nobody is prefilling
            self._inflight_chains.pop(request.inflight_key, None)
            request.inflight_key = ""
        self.stats["drain_evacuated"] += 1
        if request.journey is not None:
            self.journeys.finish(request.journey, now,
                                 outcome="evacuated")
            request.journey = None
        return {"request_id": request.request_id,
                "prompt": list(request.prompt),
                "generated": list(request.generated or []),
                "max_new_tokens": int(request.max_new_tokens),
                "callback": request.callback,
                "deadline": request.deadline,
                "tenant": request.tenant}

    def _drain_checkpoint(self) -> None:
        """Deadline checkpoint, at a round boundary: every live slot
        harvests the complete blocks of its written context into the
        prefix cache (mid-prefill slots harvest [0, prefill_pos); the
        decode slots drop the LAST generated token — its KV row is
        only written when it is fed back next round), then evacuates
        with its partial generation.  The re-submitted continuation
        prefix-hits the harvested chain instead of re-prefilling."""
        now = time.monotonic()
        for slot in range(self.max_slots):
            request = self._slots[slot]
            if request is None:
                continue
            if self.prefix_cache is not None:
                try:
                    if request.prefilling:
                        self.harvest_progress(request)
                    else:
                        context = list(request.prompt) + \
                            list(request.generated or [])
                        self._harvest_rows(slot, request.tenant,
                                           context[:-1])
                except Exception:
                    self.logger.exception(
                        "drain checkpoint harvest failed for %s",
                        request.request_id)
                if request.prefix_nodes:
                    self.prefix_cache.release(request.prefix_nodes)
                    request.prefix_nodes = []
            if self.paged:
                self._release_slot_blocks(slot)
            self._slots[slot] = None
            self.stats["drain_checkpoints"] += 1
            descriptor = self._evacuate(request, now)
            if self._drain_evacuate is not None:
                try:
                    self._drain_evacuate(descriptor)
                except Exception:
                    self.logger.exception(
                        "drain evacuation failed for %s",
                        request.request_id)
            else:
                try:
                    request.callback(request.request_id,
                                     descriptor["generated"])
                except Exception:
                    self.logger.exception("callback failed for %s",
                                          request.request_id)
        self._note_active()

    def _drain_finish(self) -> None:
        self._drained = True
        self._drain_deadline = None
        callback, self._drain_complete = self._drain_complete, None
        if callback is not None:
            try:
                callback(self)
            except Exception:
                self.logger.exception("drain completion callback "
                                      "failed")

    # -- scheduling --------------------------------------------------------
    def _bucket_for(self, length: int) -> int:
        for bucket in self.prefill_buckets:
            if length <= bucket:
                return bucket
        return self.prefill_buckets[-1]

    def _admit_fn(self, bucket: int, width: int):
        """Compiled once per (bucket, admit-width): ONE program runs the
        stacked prefill for up to `width` prompts AND scatters their
        K/V prefixes, first tokens, and lengths into the slot buffers
        on device.  The host syncs a single [width] token array per
        group — not one round-trip per request (the per-request admit
        was a throughput cliff under bursty arrivals on thin links).
        Shared process-wide via _admit_fn_for, like the decode step."""
        key = (bucket, width)
        if key not in self._prefill_fns:
            if self.paged:
                from .serving_paged import _paged_admit_fn_for
                self._prefill_fns[key] = _paged_admit_fn_for(
                    self.config, bucket, width, self.kv_int8,
                    bool(self.speculate_k))
            else:
                self._prefill_fns[key] = _admit_fn_for(
                    self.config, bucket, width, self.kv_int8,
                    bool(self.speculate_k))
        return self._prefill_fns[key]

    def _extend_fn(self, chunk: int, width: int):
        """Compiled once per (chunk, admit-width): advances up to
        `width` mid-prefill slots by one `chunk`-token piece of their
        prompt — see _extend_fn_for.  Shared process-wide.  The chunk
        is prefill_chunk for chunked admits; prefix-hit suffixes
        without a global prefill_chunk use a pow2-sized chunk of their
        own (bounded compile variants)."""
        key = ("extend", chunk, width)
        if key not in self._prefill_fns:
            # compile-cache boundary: builder runs once per (chunk,
            # width); allocs inside it are trace-time, not per-round
            if self.paged:
                from .serving_paged import (_paged_extend_fn_for,
                                            run_write_form)
                self.logger.info(
                    "extend %d x %d writes a chunk to the pool as %s",
                    chunk, width,
                    run_write_form(self.config, chunk, self.kv_block))
                self._prefill_fns[key] = _paged_extend_fn_for(  # graft: disable=lint-hot-alloc
                    self.config, chunk, width, self.kv_int8,
                    bool(self.speculate_k), self.paged_kernel)
            else:
                self._prefill_fns[key] = _extend_fn_for(  # graft: disable=lint-hot-alloc
                    self.config, chunk, width, self.kv_int8,
                    bool(self.speculate_k))
        return self._prefill_fns[key]

    def _advance_prefills(self) -> None:
        """Run one prompt chunk for mid-prefill slots (batched, pow2
        widths).  Slots closest to completion go first so in-flight
        prompts finish (and start emitting) sooner; prefill_budget
        rations how many rows advance per round.  Prefix-hit admits
        (ISSUE 13) stream their uncached SUFFIX through the same
        machinery: with prefill_chunk set they ride the normal chunk
        size, without it each suffix runs as one pow2-sized chunk."""
        rows = [s for s in range(self.max_slots)
                if self._slots[s] is not None
                and self._slots[s].prefilling]
        if not rows:
            return
        rows.sort(key=lambda s: len(self._slots[s].prompt) -
                  self._slots[s].prefill_pos)      # fewest remaining first
        # the extend writes up to offset+chunk; never let a decode-side
        # shrink cut below it (grow-only: max with current size)
        need = 0
        spent = self._round_prefill_tokens
        planned = 0
        plans_by_chunk: dict[int, list] = {}
        for slot in rows:
            request = self._slots[slot]
            total = len(request.prompt)
            remaining = total - request.prefill_pos
            chunk = self.prefill_chunk or min(
                self._next_pow2(max(1, remaining)), self.max_seq - 1)
            if self.prefill_budget is not None and planned and \
                    spent + chunk > self.prefill_budget:
                break          # ration; first row always progresses
            spent += chunk
            planned += 1
            if remaining > chunk:
                offset, finish = request.prefill_pos, False
            else:
                # final chunk slides BACK to end exactly at the prompt
                # tail: the overlap recomputes identical K/V
                # (idempotent) and offset+chunk stays <= total, so the
                # cache never needs to grow past the prompt itself —
                # EXCEPT below a prefix-cache hit, whose rows must not
                # be recomputed (the savings are the point): anchor at
                # the written boundary and pad forward instead (the
                # garbage tail past the prompt is dead cells, same as
                # the shorter-than-chunk admit)
                offset = max(0, total - chunk)
                if self.slot_state is not None:
                    # slot state has run through everything before
                    # prefill_pos already: the chunk starts there and
                    # pads forward (max_seq is whole chunks)
                    offset = request.prefill_pos
                elif offset < request.prefix_hit:
                    # ...but never let the write extent leave the
                    # cache: near the seq cap the forward pad would
                    # exceed max_seq, where _fit_caches clamps and the
                    # extend's dynamic_update_slice would CLAMP the
                    # start index — silently shifting rows onto wrong
                    # positions.  Sliding back into the cached region
                    # there is the correct fallback: the overlap
                    # recompute is idempotent (same program, same
                    # offset, same prefix bytes as the donor's own
                    # final chunk).
                    offset = min(request.prefill_pos,
                                 self.max_seq - chunk)
                finish = True
            plans_by_chunk.setdefault(chunk, []).append(
                (slot, request, offset, finish))
            # the write extent is always offset+chunk (a prompt shorter
            # than one chunk pads — the garbage tail is overwritten by
            # decode tokens before it is ever attended)
            need = max(need, offset + chunk)
        if not plans_by_chunk:
            return
        self._fit_caches(max(need, self._cache_t))
        start = time.perf_counter()
        before = self.stats["tokens_prefill"]
        for chunk, plans in plans_by_chunk.items():
            while plans:
                width = min(self.max_slots, self._next_pow2(len(plans)))
                batch, plans = plans[:width], plans[width:]
                self._extend_group(chunk, width, batch)
        elapsed = time.perf_counter() - start
        self.stats["prefill_s"] += elapsed
        self._note_prefill_rate(self.stats["tokens_prefill"] - before,
                                elapsed)

    def _extend_group(self, chunk: int, width: int, batch: list) -> None:
        n = len(batch)
        slots = [slot for slot, *_ in batch]
        used = set(slots)
        spare = [s for s in range(self.max_slots) if s not in used]
        pad_slots = spare[:width - n]
        # per-round staging vectors: rewritten in full every batch and
        # handed straight to jnp.asarray — alloc cost is noise next to
        # the device transfer they feed (unlike the table gather below,
        # which reuses self._tables_scratch)
        chunk_tokens = np.zeros((width, chunk), np.int32)  # graft: disable=lint-hot-alloc
        offsets = np.zeros((width,), np.int32)  # graft: disable=lint-hot-alloc
        final_idx = np.zeros((width,), np.int32)  # graft: disable=lint-hot-alloc
        valid = np.zeros((width,), bool)  # graft: disable=lint-hot-alloc
        finish_arr = np.zeros((width,), bool)  # graft: disable=lint-hot-alloc
        for j, (slot, request, offset, finish) in enumerate(batch):
            piece = request.prompt[offset:offset + chunk]
            chunk_tokens[j, :len(piece)] = piece
            offsets[j] = offset
            final_idx[j] = len(request.prompt) - 1 - offset if finish \
                else 0
            valid[j] = True
            finish_arr[j] = finish
        if self.paged:
            # copy-on-extend (ISSUE 15): the chunk writes positions
            # [offset, offset+chunk) — any SHARED block there (the
            # near-seq-cap slide-back into a cached region) copies to
            # a fresh block first, so aliased readers keep their rows.
            # The recompute that follows is idempotent, so parity
            # holds either way; the copy preserves the ALIASED chain.
            pairs = []
            for slot, request, offset, finish in batch:
                self._ensure_coverage(slot, offset + chunk)
                pairs.extend(self._copy_on_write(slot, offset,
                                                 offset + chunk))
            if pairs:
                copied = self.pool.copy_blocks(
                    [src for src, _ in pairs],
                    [dst for _, dst in pairs])
                self.stats["cow_copies"] += len(pairs)
                self.stats["cow_copy_bytes"] += copied
            nbt = -(-self._cache_t // self.kv_block)
            tables_rows = self._tables_scratch[:width, :nbt]
            for j, slot in enumerate(slots):
                tables_rows[j] = self._tables_np[slot, :nbt]
            tables_rows[len(slots):] = 0  # pad rows must stay null
            (firsts, k_pools, v_pools, self._tokens, self._lengths,
             self._context, *state) = self._extend_fn(chunk, width)(
                self.params, self.pool.k_pools, self.pool.v_pools,
                self._tokens, self._lengths, self._context,
                jnp.asarray(chunk_tokens), jnp.asarray(offsets),
                jnp.asarray(slots + pad_slots, jnp.int32),
                jnp.asarray(valid), jnp.asarray(finish_arr),
                jnp.asarray(final_idx), self._table_rows_device(tables_rows),
                *self._state_args(), t_cap=self._cache_t)
            self.pool.k_pools, self.pool.v_pools = k_pools, v_pools
            self._state_back(state, int((offsets[:n] == 0).sum()))
        else:
            (firsts, self._k, self._v, self._tokens, self._lengths,
             self._context) = self._extend_fn(chunk, width)(
                self.params, self._k, self._v, self._tokens,
                self._lengths, self._context, jnp.asarray(chunk_tokens),
                jnp.asarray(offsets),
                jnp.asarray(slots + pad_slots, jnp.int32),
                jnp.asarray(valid), jnp.asarray(finish_arr),
                jnp.asarray(final_idx))
        wave = []
        for j, (slot, request, offset, finish) in enumerate(batch):
            new_pos = len(request.prompt) if finish else offset + chunk
            self.stats["tokens_prefill"] += max(
                0, new_pos - request.prefill_pos)
            request.prefill_pos = new_pos
            if request.progress_callback is not None:
                # chunk streaming (ISSUE 17): the runtime harvests +
                # ships the chunk's finished blocks NOW — paged
                # harvest is a refcount bump, so this stays a host-
                # side table walk on the prefill hot path
                try:
                    request.progress_callback(request, bool(finish))
                except Exception:
                    self.logger.exception(
                        "progress callback failed for %s",
                        request.request_id)
            if request.journey is not None:
                request.journey.wave("extend")
            if finish:
                request.prefilling = False
                request.generated = []    # first token owed (wave)
                wave.append((j, request))
            self.stats["prefill_chunks"] += 1
            self._round_prefill_tokens += chunk
            self._round_prefix_tokens += offset
        self._round_prefill_pieces += 1
        if wave:
            # the finish rows' first tokens resolve at the NEXT round's
            # sync — the extend program runs behind the decode scan
            self._admit_waves.append((firsts, wave))

    @staticmethod
    def _next_pow2(n: int) -> int:
        return 1 << max(0, (n - 1).bit_length())

    # -- paged block tables (ISSUE 15) -------------------------------------
    def _ensure_coverage(self, slot: int, upto: int,
                         tenant: str | None = None) -> None:
        """Extend `slot`'s block table to cover positions [0, upto):
        allocate fresh pool blocks for the uncovered tail.  A no-op
        when already covered — the common decode round allocates one
        block only when the context crosses a block boundary.
        `tenant` attributes the allocation in the KV ledger; it
        defaults from the slot's request (admit-group callers pass it
        explicitly — the slot is not assigned yet there)."""
        block = self.kv_block
        need = min(-(-max(0, upto) // block), self._table_blocks)
        owned = self._slot_blocks[slot]
        if len(owned) >= need:
            return
        if tenant is None:
            request = self._slots[slot]
            tenant = request.tenant if request is not None else ""
        fresh = self.pool.alloc_blocks(need - len(owned),
                                       tenant=tenant)
        row = self._tables_np[slot]
        for j, block_id in enumerate(fresh, start=len(owned)):
            row[j] = block_id
        owned.extend(fresh)
        self._tables_dirty = True

    def _copy_on_write(self, slot: int, start: int, stop: int) -> list:
        """Make every block covering positions [start, stop) of `slot`
        exclusively owned before a write lands there: a SHARED block
        (refs > 1 — aliased by the prefix cache or another slot) is
        copied to a fresh block and the table repointed, so aliased
        readers never observe the mutation.  Returns (src, dst) pairs
        for the batched device copy.  The near-seq-cap final-chunk
        slide-back into a cached region is the one live writer of
        shared blocks; the common extend writes only owned tail
        blocks and copies nothing."""
        block = self.kv_block
        owned = self._slot_blocks[slot]
        row = self._tables_np[slot]
        request = self._slots[slot]
        tenant = request.tenant if request is not None else ""
        pairs = []
        for j in range(start // block,
                       min(-(-stop // block), len(owned))):
            old = owned[j]
            if self.pool.refs(old) <= 1:
                continue
            new = self.pool.alloc_blocks(1, tenant=tenant)[0]
            pairs.append((old, new))
            owned[j] = new
            row[j] = new
            self.pool.release_blocks([old], tenant=tenant)
            self._tables_dirty = True
        return pairs

    def _prepare_round_tables(self, occupied, num_steps: int):
        """Round prologue for the paged scan: extend every scanned
        slot's table to cover the positions this round's merge can
        write (entry length + num_steps tokens — per verify-block
        width in speculative mode), then hand back the device tables,
        whole: the step cuts them to its width itself."""
        per_step = 1 + self.speculate_k
        cap = self.max_seq if self.speculate_k \
            else self.max_seq + self.steps_per_sync
        for slot in occupied:
            request = self._slots[slot]
            owed = 0 if request.generated else 1
            current = len(request.prompt) + len(request.generated) \
                + owed
            self._ensure_coverage(
                slot, min(current + num_steps * per_step, cap))
        return self._tables_device()

    def _tables_device(self):
        """The device block tables [S, table width] the step gathers
        and merges through, at their full and constant shape: uploaded
        again only when the host tables changed (one small int32
        transfer), never because a round attends at another width."""
        if self._tables_dirty:
            self._tables_dev = jnp.asarray(self._tables_np)
            self._tables_dirty = False
        return self._tables_dev

    def _weights_on_one_device(self) -> bool:
        """The decoder holds no mesh: a tensor-parallel decoder is one
        whose weights came in sharded, and a pallas_call over the
        heads-sharded pool such a decoder's programs make needs a
        shard_map of its own."""
        return all(len(leaf.sharding.device_set) == 1
                   for leaf in jax.tree_util.tree_leaves(self.params)
                   if isinstance(leaf, jax.Array))

    def _attend_width(self, required_t: int) -> int:
        """The smallest width of the ladder that covers `required_t`,
        the cap where none does (a context within a round of max_seq:
        the positions past the cap are merge headroom, written through
        the table and attended by nobody)."""
        for width in self._attend_widths:
            if required_t <= width:
                return width
        return self._attend_widths[-1]

    def _step_program(self, num_steps: int, width: int, eos: int,
                      args: tuple):
        """The executable of the paged step at `num_steps` and `width`
        for `args` as they are placed now.  The first dispatch of a
        step count compiles it at EVERY width of the ladder, from the
        live arguments' types (nothing is allocated), so that no later
        round compiles: a context that grows into the next width meets
        its program.  (The plain step has one step count, the longest
        round's: pump cuts a shorter round by its budgets.  The
        speculative step has one a round length.)  State that comes
        back placed otherwise (a tensor-parallel program returns it
        sharded) or a pool that grew is another key, as it is another
        trace of the jitted step."""
        from .serving_paged import compiled_step, placements
        # the weights (args[0]) stay as they were placed at construction
        key = (num_steps, width, self.pool.num_blocks,
               placements(args[1:]))
        program = self._step_programs.get(key)
        if program is None:
            for each in self._attend_widths:
                self._step_programs[(num_steps, each) + key[2:]] = \
                    compiled_step(self._step, args, num_steps=num_steps,
                                  eos=eos, t_cap=each)
            program = self._step_programs[key]
        return program

    @staticmethod
    def _table_rows_device(tables_rows):
        """The scratch rows as a device array that OWNS its values.
        `jnp.array` of a numpy array does not copy on the host, and one
        row of the scratch is contiguous, so on the CPU the program's
        argument aliased the scratch itself: the next dispatch rewrote
        it under a prefill program that had not run yet (no round syncs
        on an admit or an extend), which then read another request's
        blocks.  Seen as a flaky token under load with programs as slow
        as a recurrent model's on the CPU (ISSUE 33); a chip gets a
        transfer either way."""
        return jnp.asarray(tables_rows.copy())

    def _state_args(self) -> tuple:
        """The slot state as a program's argument: nothing where the
        model keeps none."""
        return () if self.slot_state is None else (self.slot_state.arrays,)

    def _state_back(self, returned: list, started: int) -> None:
        """Take the slot state a program handed back; `started` requests
        began in it from zeros."""
        if self.slot_state is not None:
            (self.slot_state.arrays,) = returned
            self.stats["slot_states_zeroed"] += started

    def _release_slot_blocks(self, slot: int,
                             tenant: str | None = None) -> None:
        """Drop the slot's refs on every table block at retire.
        Blocks the harvest registered stay alive through the cache's
        own refs; purely-owned blocks return to the free list.
        `tenant` attributes the release in the KV ledger; it defaults
        from the slot's request (the admit-group unwind passes it —
        the slot was never assigned there)."""
        owned = self._slot_blocks[slot]
        if owned:
            if tenant is None:
                request = self._slots[slot]
                tenant = request.tenant if request is not None else ""
            self.pool.release_blocks(owned, tenant=tenant)
            self._slot_blocks[slot] = []
            self._tables_np[slot, :len(owned)] = 0
            self._tables_dirty = True

    def kv_wire_layout(self) -> tuple:
        """The storage layout as wire-safe string fields — what a
        cacheless paged decoder matches a KV transfer's declared donor
        layout against (PrefixKVCache.wire_layout's twin)."""
        self._require("kv_wire", "the disaggregated KV wire layout")
        return tuple(str(f) for f in self._kv_layout)

    def install_shipped_blocks(self, tokens, start_block: int,
                               blocks, tenant: str = "") -> tuple:
        """Direct slot-table install (ISSUE 15 satellite): write
        shipped chain blocks straight into fresh pool blocks and hand
        the ids to the caller for submit(..) via DecodeRequest
        aliasing — the cacheless decode pool's KV landing (no
        PrefixKVCache required).  Returns (covered_tokens, ids) for
        THESE blocks; ownership of the ids transfers to the caller
        (release on a refused submit).  `start_block` > 0 is the
        chunk-streamed accumulation path (ISSUE 17): the caller holds
        the ids for blocks [0, start_block) from earlier chunks and
        owns contiguity (the client's ordered-cursor guard) — this
        method only installs and sizes the given span.  Raises
        ValueError on geometry mismatch, before any row lands."""
        if not self.paged:
            raise ValueError(
                "install_shipped_blocks needs a paged decoder")
        self._require("kv_wire", "the install of shipped KV blocks")
        start = int(start_block)
        if start < 0:
            raise ValueError(f"negative start_block {start}")
        block = self.kv_block
        count = min(len(blocks),
                    max(0, len(tokens) // block - start))
        entries = blocks[:count]
        for entry in entries:
            check_block_geometry(self._kv_layout, block, entry)
        if not entries:
            return 0, []
        ids = self.pool.alloc_blocks(len(entries), tenant=tenant)
        layers = self.config.num_layers
        self.pool.write_blocks(
            ids,
            [_stack_block_leaves([entry["k"][i] for entry in entries])
             for i in range(layers)],
            [_stack_block_leaves([entry["v"][i] for entry in entries])
             for i in range(layers)])
        return count * block, ids

    def _zero_caches(self, t: int | None = None) -> list:
        """Fresh per-layer slot caches at time extent `t` (default: the
        current serving extent) in the decoder's storage layout — plain
        [S, H, T, D] arrays, or {"q" int8, "s" f32 [S, H, T]} dicts in
        int8 mode."""
        config = self.config
        shape = (self.max_slots, config.num_kv_heads,
                 t or self._cache_t, config.head_dim)
        if self.kv_int8:
            return [{"q": jnp.zeros(shape, jnp.int8),
                     "s": jnp.zeros(shape[:3], jnp.float32)}
                    for _ in range(config.num_layers)]
        return [jnp.zeros(shape, config.dtype)
                for _ in range(config.num_layers)]

    def kv_cache_bytes(self) -> int:
        """Bytes currently allocated to the slot KV caches (values +
        scales) — the number kv_cache_dtype='int8' halves.  In paged
        mode this models the POOL: block arrays plus the int32
        tables (ISSUE 15) — shared prefixes are counted once, which is
        the capacity win block aliasing buys."""
        if self.paged:
            return self.pool.nbytes() + int(self._tables_np.nbytes)
        return int(sum(
            leaf.size * jnp.dtype(leaf.dtype).itemsize
            for cache in self._k + self._v
            for leaf in jax.tree_util.tree_leaves(cache)))

    def _fit_caches(self, required_t: int) -> None:
        """Resize the cache time axis to the t_block multiple covering
        `required_t` (clamped to max_seq — plus steps_per_sync scratch
        headroom, so a round-end side-buffer merge near the seq cap
        never clamps into a misaligned overwrite; the headroom cells
        are never attended.  The speculative merge scatters at
        absolute positions with out-of-bounds drop, so it needs no
        headroom).  A grow pads with zeros, a shrink slices —
        one whole-cache copy, amortized over the many rounds run at
        the new size.  No-op when already sized."""
        if self.paged:
            # nothing to resize: the pool allocates by the block, the
            # tables, extends and admits keep max_seq, and the step
            # takes its width round by round (_attend_width)
            return
        if self.speculate_k:
            cap = self.max_seq
        else:
            cap = self.max_seq + self.steps_per_sync
        new_t = min(cap, -(-required_t // self.t_block) * self.t_block)
        if new_t == self._cache_t:
            return
        key = (self._cache_t, new_t)
        if key not in self._resize_fns:
            if new_t > self._cache_t:
                pad = new_t - self._cache_t

                def grow_leaf(c, pad=pad):
                    # time axis is axis 2 for values [S,H,T,D] AND
                    # scales [S,H,T]
                    spec = [(0, 0)] * c.ndim
                    spec[2] = (0, pad)
                    return jnp.pad(c, spec)

                def resize(caches):
                    return [jax.tree.map(grow_leaf, c) for c in caches]
            else:
                def resize(caches, t=new_t):
                    return [jax.tree.map(lambda c: c[:, :, :t], cache)
                            for cache in caches]
            self._resize_fns[key] = jax.jit(resize,
                                            donate_argnums=(0,))
        self._k = self._resize_fns[key](self._k)
        self._v = self._resize_fns[key](self._v)
        self._cache_t = new_t

    def _admit_pending(self) -> None:
        """Admit as many pending requests as there are free slots, in
        FIFO order.  With a prefix cache bound (ISSUE 13), each request
        is longest-prefix-matched FIRST: a hit claims a slot, copies
        the cached K/V chain in (no forward pass), and streams only the
        uncached suffix via _advance_prefills.  Cold short prompts go
        through bucketed single-shot prefill groups; cold prompts
        longer than the largest bucket (only when prefill_chunk is set)
        claim a slot here and stream in chunks.  With prefill_budget
        set, bucketed admission stops for the round once the budget is
        spent — arrivals defer rather than stall active decode slots
        (prefix copies are exempt: they move bytes, not FLOPs)."""
        if self.prefix_cache is not None and \
                self.prefix_cache.promotions_ready:
            # land staged async promotions FIRST (ISSUE 17): a
            # prefetch kicked rounds ago becomes a plain cache hit
            # for the probes below — the hot-session admit stays a
            # table edit
            self.prefix_cache.poll_promotions()
        free = [s for s in range(self.max_slots)
                if self._slots[s] is None]
        if not free or not self._pending:
            return
        groups: dict[int, list[DecodeRequest]] = {}
        chunked: list[DecodeRequest] = []
        cached: list[DecodeRequest] = []
        deferred: list[DecodeRequest] = []      # in-flight dedup waits
        taken = 0
        index = 0
        pending = self._pending
        while index < len(pending):
            request = pending[index]
            if taken >= len(free):
                break
            if request.kv_block_ids:
                # direct slot-table install (ISSUE 15): the blocks are
                # already pool-resident — admit is a table edit plus
                # the suffix prefill, no cache probe involved
                cached.append(request)
                taken += 1
                index += 1
                continue
            if self.prefix_cache is not None and request.dedup_wait:
                # in-flight prefix dedup window (ISSUE 14 satellite,
                # PR 13 residue d): this request deferred behind a
                # same-batch duplicate whose prompt is prefilling NOW.
                # Its leader's prompt blocks land at the leader's
                # FIRST TOKEN (early harvest below), so the wait is a
                # couple of rounds, not a generation; a leader that
                # left without inserting (budget refusal, failure)
                # releases the follower to prefill cold.
                if self.prefix_cache.has(request.dedup_wait) or \
                        request.dedup_wait not in self._inflight_chains:
                    if self.prefix_cache.has(request.dedup_wait):
                        self.stats["dedup_shared"] += 1
                    request.dedup_wait = ""     # probe sees the truth
                else:
                    deferred.append(request)    # keeps its FIFO rank,
                    index += 1                  # consumes no slot
                    continue
            if self.prefix_cache is not None and \
                    not request.prefix_probed:
                if self.prefix_cache.tiered:
                    # sync promotion fallback (ISSUE 17): whatever of
                    # this prompt's chain still lives on the host
                    # tier must be device-resident BEFORE the probe —
                    # a staged prefetch installs instantly, an
                    # unkicked one stages inline; either way the
                    # acquire below sees the full chain
                    self.prefix_cache.promote_for(
                        request.tenant, request.prompt)
                block = self.prefix_cache.block_tokens
                if len(request.prompt) > block:
                    lead = self.prefix_cache.keys_for(
                        request.tenant, request.prompt[:block])[0]
                    leader = self._inflight_chains.get(lead)
                    if leader is not None and leader is not request \
                            and not self.prefix_cache.has(lead):
                        if leader.generated and leader.slot >= 0 and \
                                self._slots[leader.slot] is leader:
                            # the leader is PAST its first token: its
                            # prompt rows are device-written, so
                            # harvest NOW and let this request probe a
                            # hit this very round — a follower that
                            # arrives mid-generation must not wait out
                            # the leader's whole generation (review
                            # finding: dedup_hot is only consulted at
                            # the leader's first token)
                            try:
                                self._prefix_harvest_prompt(
                                    leader.slot, leader)
                            except Exception:
                                self.logger.exception(
                                    "late prompt harvest failed for "
                                    "%s", leader.request_id)
                        if not self.prefix_cache.has(lead):
                            # duplicate of an in-flight prompt: wait
                            # for the leader's early prompt harvest
                            # (at its first token) instead of missing
                            # the cache and prefilling it twice — the
                            # probe (and its hit/miss metrics) runs
                            # once, at the real admit
                            leader.dedup_hot = True
                            request.dedup_wait = lead
                            self.stats["dedup_deferred"] += 1
                            deferred.append(request)
                            index += 1
                            continue
                        self.stats["dedup_shared"] += 1
                request.prefix_probed = True
                keys, hit = self.prefix_cache.acquire(
                    request.tenant, request.prompt,
                    limit=len(request.prompt) - 1)
                if hit:
                    request.prefix_nodes = list(keys)
                    request.prefix_hit = hit
            if request.prefix_hit:
                cached.append(request)
            elif self.prefill_chunk and \
                    len(request.prompt) > self.prefill_buckets[-1]:
                chunked.append(request)
            else:
                bucket = self._bucket_for(len(request.prompt))
                if self.prefill_budget is not None and \
                        self._round_prefill_tokens > 0 and \
                        self._round_prefill_tokens + bucket > \
                        self.prefill_budget:
                    break        # FIFO: defer, don't reorder past it
                self._round_prefill_tokens += bucket
                groups.setdefault(bucket, []).append(request)
            if self.prefix_cache is not None and \
                    not request.prefix_hit and \
                    len(request.prompt) >= self.prefix_cache.block_tokens:
                # cold prompt with >= 1 complete block: register as a
                # potential dedup leader until its blocks are cached
                # (early harvest) or it retires
                request.inflight_key = self.prefix_cache.keys_for(
                    request.tenant,
                    request.prompt[:self.prefix_cache.block_tokens])[0]
                self._inflight_chains[request.inflight_key] = request
            taken += 1
            index += 1
        self._pending = deferred + pending[index:]
        admit_t = time.monotonic() if (chunked or groups or cached) \
            else 0.0
        if cached:
            self._fit_caches(max(max(self._prefix_write_len(r)
                                     for r in cached), self._cache_t))
            start = time.perf_counter()
            for request in cached:
                self._prefix_admit(free.pop(0), request, admit_t)
            self.stats["prefill_s"] += time.perf_counter() - start
        for request in chunked:
            slot = free.pop(0)
            request.slot = slot
            request.prefilling = True
            request.prefill_pos = 0
            self._slots[slot] = request
            self.stats["chunk_admits"] += 1
            if request.journey is not None:
                request.journey.admitted(admit_t, slot, "chunk-admit")
        if not groups:
            return
        # grow-only here (admits scatter [:bucket]); the round planner
        # owns shrinking, with full knowledge of every active context
        self._fit_caches(max(max(groups), self._cache_t))
        start = time.perf_counter()
        before = self.stats["tokens_prefill"]
        for bucket, requests in groups.items():
            while requests:
                width = min(self.max_slots,
                            self._next_pow2(len(requests)))
                chunk, requests = requests[:width], requests[width:]
                self._admit_group(bucket, width, chunk, free)
        elapsed = time.perf_counter() - start
        self.stats["prefill_s"] += elapsed
        self._note_prefill_rate(self.stats["tokens_prefill"] - before,
                                elapsed)

    # -- prefix/KV reuse (ISSUE 13) ----------------------------------------
    def _prefix_write_len(self, request: DecodeRequest) -> int:
        """Copy-in write extent for a hit: the chain's tokens padded up
        to a pow2 block count (bounded compile variants), capped at
        max_seq — near the cap the exact length compiles instead.
        (Paged admits move no KV rows at all; this extent then sizes
        only the speculative-context seed.)"""
        blocks = request.prefix_hit // self.kv_block
        padded = self._next_pow2(blocks) * self.kv_block
        return padded if padded <= self.max_seq else request.prefix_hit

    def _prefix_zero_block(self):
        """One shared zero pad block in the cache storage layout."""
        if self._prefix_pad is None:
            config = self.config
            shape = (config.num_kv_heads,
                     self.prefix_cache.block_tokens, config.head_dim)
            # memoized: allocates exactly once, then every pad reuses
            # the cached block
            if self.kv_int8:
                self._prefix_pad = {  # graft: disable=lint-hot-alloc
                    "q": jnp.zeros(shape, jnp.int8),
                    "s": jnp.zeros(shape[:2], jnp.float32)}
            else:
                self._prefix_pad = jnp.zeros(shape, config.dtype)  # graft: disable=lint-hot-alloc
        return self._prefix_pad

    def _prefix_admit(self, slot: int, request: DecodeRequest,
                      admit_t: float) -> None:
        """Admit a prefix-hit request: copy the pinned chain's K/V rows
        into the slot cache (one scatter program, queued behind the
        decode scan like every other prefill dispatch), seed the
        speculative context with the cached prompt tokens, and leave
        the slot mid-prefill at the hit boundary — _advance_prefills
        runs the uncached suffix, and the finish extend produces the
        first token exactly like a chunked admit.

        PAGED (ISSUE 15): no rows move at all — the chain's pool
        blocks alias into the slot's table (retain refs, host-side
        edit), the one device write left being the speculative-context
        seed.  prefix_copy_bytes stays 0; that delta vs the dense copy
        is the A/B the bench quotes."""
        if self.paged:
            self._prefix_admit_paged(slot, request, admit_t)
            return
        cache = self.prefix_cache
        config = self.config
        t_write = self._prefix_write_len(request)
        pad = (t_write - request.prefix_hit) // cache.block_tokens
        chain = cache.nodes(request.prefix_nodes)
        k_rows, v_rows = [], []
        for i in range(config.num_layers):
            k_blocks = [node.k_rows[i] for node in chain]
            v_blocks = [node.v_rows[i] for node in chain]
            if pad:
                zero = self._prefix_zero_block()
                k_blocks = k_blocks + [zero] * pad
                v_blocks = v_blocks + [zero] * pad
            k_rows.append(L.concat_kv_rows(k_blocks))
            v_rows.append(L.concat_kv_rows(v_blocks))
        # one context-row stage per prefix admit, straight to device
        ctx = np.zeros((t_write,), np.int32)  # graft: disable=lint-hot-alloc
        ctx[:request.prefix_hit] = request.prompt[:request.prefix_hit]
        fn = _prefix_copy_fn_for(config, t_write, self.kv_int8,
                                 bool(self.speculate_k))
        self._k, self._v, self._context = fn(
            self._k, self._v, self._context, k_rows, v_rows,
            jnp.asarray(slot, jnp.int32), jnp.asarray(ctx))
        # the copy writes t_write rows of K+V per layer — bytes, the
        # whole point: no weight stream, no FLOPs
        copy_bytes = t_write * self._kv_bytes_per_t // self.max_slots
        self.stats["prefix_copy_bytes"] += copy_bytes
        request.slot = slot
        request.prefilling = True
        request.prefill_pos = request.prefix_hit
        self._slots[slot] = request
        self.stats["prefix_admits"] += 1
        if request.journey is not None:
            request.journey.prefix_hit_tokens = request.prefix_hit
            request.journey.admitted(admit_t, slot, "prefix-admit")

    def _prefix_admit_paged(self, slot: int, request: DecodeRequest,
                            admit_t: float) -> None:
        """Paged hit admit: alias the chain's pool blocks into the
        slot's block table.  Cache hits retain one pool ref per block
        for the slot; direct installs (kv_block_ids) transfer the
        caller's refs outright.  Zero KV bytes move — only the
        speculative drafter's context buffer still needs the cached
        prompt tokens written."""
        block = self.kv_block
        count = request.prefix_hit // block
        if request.kv_block_ids:
            ids = request.kv_block_ids[:count]
            request.kv_block_ids = []
        else:
            chain = self.prefix_cache.nodes(request.prefix_nodes)
            ids = [node.pool_id for node in chain[:count]]
            self.pool.retain(ids)
        row = self._tables_np[slot]
        for j, block_id in enumerate(ids):
            row[j] = block_id
        self._slot_blocks[slot] = list(ids)
        self._tables_dirty = True
        if self.speculate_k:
            t_write = self._prefix_write_len(request)
            # one context-row stage per prefix admit, straight to device
            ctx = np.zeros((t_write,), np.int32)  # graft: disable=lint-hot-alloc
            ctx[:request.prefix_hit] = \
                request.prompt[:request.prefix_hit]
            from .serving_paged import _paged_ctx_fn_for
            self._context = _paged_ctx_fn_for(t_write)(
                self._context, jnp.asarray(slot, jnp.int32),
                jnp.asarray(ctx))
        request.slot = slot
        request.prefilling = True
        request.prefill_pos = request.prefix_hit
        self._slots[slot] = request
        self.stats["prefix_admits"] += 1
        if request.journey is not None:
            request.journey.prefix_hit_tokens = request.prefix_hit
            request.journey.admitted(admit_t, slot, "prefix-admit")

    def _prefix_harvest(self, slot: int, request: DecodeRequest) -> None:
        """Register a retiring request's K/V rows as cache blocks: the
        prompt plus every generated token but the LAST (an emitted
        token's K/V lands only when it is consumed as the next input,
        so the final token's rows are never written).  Already-cached
        blocks are skipped by key — no device work; the chain extends
        the request's own hit, so a conversation's next turn
        longest-matches its entire history (ISSUE 13)."""
        self._harvest_rows(slot, request.tenant,
                           list(request.prompt) +
                           [int(t) for t in request.generated[:-1]])

    def _prefix_harvest_prompt(self, slot: int,
                               request: DecodeRequest) -> None:
        """Early prompt harvest (ISSUE 14 satellite, PR 13 residue d):
        the moment a dedup-hot leader's first token resolves, its
        prompt rows are device-written — insert the prompt blocks NOW
        so same-batch duplicates share the prefill instead of waiting
        for the whole generation to retire.  The generated tokens
        still harvest at retire, as before."""
        self._harvest_rows(slot, request.tenant, list(request.prompt))
        request.dedup_hot = False
        if request.inflight_key and \
                self._inflight_chains.get(request.inflight_key) \
                is request:
            self._inflight_chains.pop(request.inflight_key, None)
            request.inflight_key = ""

    def harvest_progress(self, request: DecodeRequest) -> int:
        """Mid-prefill prompt harvest (ISSUE 17): register the
        complete blocks written so far ([0, prefill_pos)) with the
        prefix cache NOW, without waiting for retire — the chunk-
        streaming shipper reads them the moment the chunk's extend is
        dispatched.  Idempotent (already-cached keys skip); returns
        complete prompt blocks at the current position."""
        if self.prefix_cache is None or request.slot < 0 or \
                self._slots[request.slot] is not request:
            return 0
        pos = int(request.prefill_pos)
        self._harvest_rows(request.slot, request.tenant,
                           list(request.prompt[:pos]))
        return pos // self.prefix_cache.block_tokens

    def _harvest_rows(self, slot: int, tenant: str, tokens) -> None:
        cache = self.prefix_cache
        block = cache.block_tokens
        count = len(tokens) // block
        if count == 0:
            return
        keys = cache.keys_for(tenant, tokens[:count * block])
        start = 0
        while start < count and cache.has(keys[start]):
            start += 1
        if start >= count:
            return
        if self.paged:
            # zero-copy harvest (ISSUE 15): the slot's own pool blocks
            # BECOME the cache entries — retain + record key, no row
            # movement (the dense path's slice-out copy AND the hit's
            # later copy-in are both gone; the double write was
            # ROADMAP item 3 residue c)
            owned = self._slot_blocks[slot]
            parent = keys[start - 1] if start else ""
            for j in range(start, min(count, len(owned))):
                if not cache.insert_block(tenant, parent, keys[j],
                                          owned[j]):
                    break    # budget refused: stop, or children dangle
                parent = keys[j]
            return
        base, end = start * block, count * block
        layers = self.config.num_layers
        k_splits = [L.split_kv_blocks(
            L.slice_kv_rows(self._k[i], slot, base, end), block)
            for i in range(layers)]
        v_splits = [L.split_kv_blocks(
            L.slice_kv_rows(self._v[i], slot, base, end), block)
            for i in range(layers)]
        self.stats["harvest_copy_bytes"] += \
            (end - base) * self._kv_bytes_per_t // self.max_slots
        parent = keys[start - 1] if start else ""
        for j in range(start, count):
            inserted = cache.insert(
                tenant, parent, keys[j],
                [k_splits[i][j - start] for i in range(layers)],
                [v_splits[i][j - start] for i in range(layers)])
            if not inserted:
                break        # budget refused: stop, or children dangle
            parent = keys[j]

    def _admit_group(self, bucket: int, width: int,
                     chunk: list, free: list) -> None:
        n = len(chunk)
        slots = [free.pop(0) for _ in range(n)]
        # pad rows need DISTINCT slot ids (scatter order is unspecified
        # on collision): remaining free slots first, then occupied ones
        # — either way the pad row rewrites that slot's own content
        used = set(slots)
        spare = [s for s in range(self.max_slots) if s not in used]
        pad_slots = spare[:width - n]
        # per-admit staging vectors: same discipline as _extend_group —
        # rewritten in full, fed straight to jnp.asarray, alloc cost is
        # noise next to the transfer (table gather reuses scratch)
        prompts = np.zeros((width, bucket), np.int32)  # graft: disable=lint-hot-alloc
        true_lens = np.zeros((width,), np.int32)  # graft: disable=lint-hot-alloc
        valid = np.zeros((width,), bool)  # graft: disable=lint-hot-alloc
        for j, request in enumerate(chunk):
            prompts[j, :len(request.prompt)] = request.prompt
            true_lens[j] = len(request.prompt)
            valid[j] = True
        if self.paged:
            # each admitted slot gets fresh pool blocks padded to the
            # block boundary (dead cells past the prompt, same
            # invariant as the dense scatter's padding); pad rows stay
            # all-null and their writes drop inside the program
            nbb = -(-bucket // self.kv_block)
            tables_rows = self._tables_scratch[:width, :nbb]
            try:
                for j, slot in enumerate(slots):
                    self._ensure_coverage(slot, nbb * self.kv_block,
                                          tenant=chunk[j].tenant)
                    tables_rows[j] = self._tables_np[slot, :nbb]
            except Exception:
                # pool growth refused (HBM exhaustion, injected chaos
                # fault) before any slot was assigned: release what
                # the aborted wave already claimed and put the chunk
                # back at the HEAD of the queue — the escalation path
                # (alert -> drain) then evacuates these requests as
                # descriptors instead of silently losing them
                for slot, request in zip(slots, chunk):
                    self._release_slot_blocks(slot,
                                              tenant=request.tenant)
                free[:0] = slots
                self._pending[:0] = chunk
                raise
            tables_rows[len(slots):] = 0  # pad rows must stay null
            (firsts, k_pools, v_pools, self._tokens, self._lengths,
             self._context, *state) = self._admit_fn(bucket, width)(
                self.params, self.pool.k_pools, self.pool.v_pools,
                self._tokens, self._lengths, self._context,
                jnp.asarray(prompts), jnp.asarray(true_lens),
                jnp.asarray(slots + pad_slots, jnp.int32),
                jnp.asarray(valid), self._table_rows_device(tables_rows),
                *self._state_args())
            self.pool.k_pools, self.pool.v_pools = k_pools, v_pools
            self._state_back(state, n)
        else:
            (firsts, self._k, self._v, self._tokens, self._lengths,
             self._context) = self._admit_fn(bucket, width)(
                self.params, self._k, self._v, self._tokens,
                self._lengths, self._context, jnp.asarray(prompts),
                jnp.asarray(true_lens),
                jnp.asarray(slots + pad_slots, jnp.int32),
                jnp.asarray(valid))
        # NO host sync here: the dispatch is async and queued BEHIND
        # this round's decode scan — fetching `firsts` now would stall
        # the host on prefill.  The request is live (slot assigned)
        # with its first token OWED; the stashed wave resolves it at
        # the NEXT round's sync, by which point the admit program has
        # run in the gap between scans.
        wave = []
        admit_t = time.monotonic()
        for j, request in enumerate(chunk):
            request.slot = slots[j]
            request.generated = []            # first token pending
            self._slots[slots[j]] = request
            self.stats["prefills"] += 1
            self.stats["tokens_prefill"] += len(request.prompt)
            if request.journey is not None:
                request.journey.admitted(admit_t, slots[j], "admit")
            wave.append((j, request))
        self._round_prefill_pieces += 1
        self._admit_waves.append((firsts, wave))

    def _finished(self, request: DecodeRequest, token: int) -> bool:
        return (self.eos_token is not None and token == self.eos_token) \
            or len(request.generated) >= request.max_new_tokens \
            or len(request.prompt) + len(request.generated) >= \
            self.max_seq - 1

    def _retire(self, slot: int) -> None:
        request = self._slots[slot]
        journey = request.journey
        if request.inflight_key and \
                self._inflight_chains.get(request.inflight_key) \
                is request:
            # dedup-leader registration ends with the request; a
            # follower still waiting re-probes and goes cold if the
            # harvest below is refused by the byte budget
            self._inflight_chains.pop(request.inflight_key, None)
        if self.prefix_cache is not None:
            # harvest BEFORE releasing the request's own pins: the hit
            # chain must stay resident while the new blocks link to it
            try:
                self._prefix_harvest(slot, request)
            except Exception:
                self.logger.exception("prefix harvest failed for %s",
                                      request.request_id)
            if request.prefix_nodes:
                self.prefix_cache.release(request.prefix_nodes)
                request.prefix_nodes = []
        if self.paged:
            # after the harvest retained what it keeps: drop the
            # slot's refs — cache-held blocks live on, purely-owned
            # ones return to the free list (the drain leak audit
            # asserts this reaches zero live blocks)
            self._release_slot_blocks(slot)
        self._slots[slot] = None
        self._note_active()
        self.stats["completed"] += 1
        count = len(request.generated)
        if count >= 2 and request.last_time > request.first_time:
            itl = (request.last_time - request.first_time) / (count - 1)
            self.itl_samples.append(itl)
            self._slo_sketch(
                "itl", journey.tenant if journey else "").observe(
                itl, exemplar=(journey.trace_id or request.request_id)
                if journey else None)
        if request.max_gap > 0:
            self.gap_samples.append(request.max_gap)
        if journey is not None:
            # completion closes the journey: deadline margin computed,
            # outcome counted per tenant, spans emitted under the
            # frame's trace id (flight-dumpable); the round that handed
            # over its last token is the one that is open
            journey.last_round = self.profiler.seq
            self.journeys.finish(journey, request.last_time
                                 or time.monotonic())
        generated = request.generated
        if self.eos_token is not None and generated and \
                generated[-1] == self.eos_token:
            generated = generated[:-1]
        try:
            request.callback(request.request_id, generated)
        except Exception:
            self.logger.exception("callback failed for %s",
                                  request.request_id)

    def _round_plan(self, occupied) -> tuple:   # graft: hot-path
        """(num_steps, required_t, budgets): how long to run before the
        next host sync, the cache time-axis extent this round needs,
        and how many tokens each slot may still emit.

        num_steps is retire-aligned: with requests waiting, the round
        ends near the earliest slot retirement so the freed slot
        refills immediately instead of burning MXU lanes on a finished
        request.  With an empty queue it runs to the longest remaining
        budget — early exit would free lanes nothing is waiting for.
        The value is pow2-CEILed (jit cache stays at log2 variants;
        the in-scan budget mask absorbs the overshoot) — flooring
        would instead fragment a cycle's tail into extra host syncs,
        each a full dispatch+sync round trip."""
        budgets = self._budgets_np                # preallocated (hot)
        budgets.fill(0)
        entry = self._entry_np                    # a fixed array a slot
        max_len = 0
        # tokens one scan iteration can yield: 1, or the whole
        # speculative block when every draft lands
        per_step = 1 + self.speculate_k
        for slot in occupied:
            request = self._slots[slot]
            # a just-admitted slot still OWES its first token (resolved
            # from its admit wave at this round's sync): account for it
            # now or the device generates one extra token per request
            # that the host discards — phantom "useful" work
            owed = 0 if request.generated else 1
            generated = len(request.generated) + owed
            current = len(request.prompt) + generated
            # budget 0 is legal: a deferred admit whose OWED first token
            # already satisfies the request (max_new_tokens=1, or prompt
            # at the seq cap) needs no scan at all — pump() masks it out
            # so its extra device emissions are never counted as useful
            budgets[slot] = max(0, min(
                request.max_new_tokens - generated,
                self.max_seq - 1 - current))
            max_len = max(max_len, current)
            # the device's length of the slot: the last token is the
            # step's input, not yet a row of the pool
            entry[slot] = current - 1
        remaining = budgets[occupied]
        cap = int(remaining.min()) if self._pending \
            else int(remaining.max())
        num_steps = min(self.steps_per_sync,
                        self._next_pow2(max(1, -(-cap // per_step))))
        return (num_steps, max_len + num_steps * per_step + 1, budgets)

    def pump(self) -> None:   # graft: hot-path
        """One scheduling round, decode-first (ISSUE 7): dispatch the
        decode scan, THEN dispatch prefill work (admits + chunk
        extends) so it queues behind the scan on the device's in-order
        stream and executes while the host syncs the scan and resolves
        tokens — a decode round's sync never waits on prefill.  First
        tokens of slots admitted in EARLIER rounds resolve from their
        stashed admit outputs (device-complete by now), then this
        round's scan emissions deliver, then retirements fire."""
        if self._draining and not self._drained:
            # drain tick (ISSUE 19), at the round boundary: past the
            # deadline every live slot checkpoints (harvest + evacuate)
            # instead of decoding on; once idle the drain completes —
            # exactly once, before any new round is planned
            if self._drain_deadline is not None and \
                    time.monotonic() >= self._drain_deadline and \
                    self.active_count:
                self._drain_checkpoint()
            if self.idle:
                self._drain_finish()
        self._round_prefill_tokens = 0
        self._round_prefill_pieces = self._round_prefix_tokens = 0
        # ONE set of phase boundaries: each profiler.enter() below is a
        # sum, a field of the round's record and (while a profiler
        # session runs) a span on the device trace's clock
        profiler = self.profiler
        profiler.begin_round()                    # the round opens in "plan"
        # mid-prefill slots hold a slot but don't decode yet
        active = self._active_np                  # preallocated (hot)
        any_active = False
        for slot in range(self.max_slots):
            request = self._slots[slot]
            live = request is not None and not request.prefilling
            active[slot] = live
            any_active = any_active or live
        waves_due = self._admit_waves
        self._admit_waves = []
        scanned = False
        num_steps = scanned_slots = attend_width = attended = ahead = 0
        model_counts = []     # what the model's step counted, if it counts
        if any_active:
            occupied = [s for s in range(self.max_slots) if active[s]]
            num_steps, required_t, budgets = self._round_plan(occupied)
            if self.paged:
                # the width need cover only the slots this round scans:
                # a slot in mid-prefill is masked out of the step and
                # dropped by its merge, and no resize can cut its rows
                attend_width = self._attend_width(required_t)
            else:
                # never shrink the cache below a mid-prefill slot's
                # written extent — the decode slots alone may need less
                for request in self._slots:
                    if request is not None and request.prefilling:
                        required_t = max(required_t, request.prefill_pos)
                self._fit_caches(required_t)
                attend_width = self._cache_t
            # a slot with budget 0 (request satisfied by its owed first
            # token) needs no decode: masking it out of the scan keeps
            # its discarded emissions out of useful_steps
            scan_active = active & (budgets > 0)
            scanned_slots = int(scan_active.sum())
            scanned = scanned_slots > 0
            # what the round's record says the step attended at: the
            # width of its views, or what the kernel walks of the
            # scanned slots (their mean: it has no width)
            attended = attend_width
            if scanned and self._walks_live:
                from .ops.paged_attention import walk_positions
                attended = float(walk_positions(
                    self._entry_np[scan_active], self.kv_block).mean())
        if scanned:
            profiler.enter("spec_verify" if self.speculate_k
                           else "scan_dispatch")
            self.stats["rounds"] += 1
            self.stats["occupancy_sum"] += float(active.mean())
            # what was dispatched since the last step stands ahead of
            # this one on the device's in-order stream
            ahead, self._prefill_ahead = self._prefill_ahead, 0
            decode_start = time.perf_counter()
            eos = -1 if self.eos_token is None else int(self.eos_token)
            if self.paged:
                # every scanned slot's table must own the blocks this
                # round's merge will write (the common round allocates
                # only at block-boundary crossings); then one small
                # int32 transfer refreshes the device tables if dirty
                tables = self._prepare_round_tables(occupied,
                                                    num_steps)
                program_steps = num_steps
                if not self.speculate_k:
                    # every round runs the program of the longest one,
                    # cut to its own length by the budgets: the step's
                    # loop ends when no slot has a token left to emit,
                    # so a round length is no program of its own
                    np.minimum(budgets, num_steps, out=budgets)
                    program_steps = self.steps_per_sync
                args = (self.params, self._tokens, self._lengths,
                        jnp.array(scan_active), jnp.array(budgets)) + \
                    ((self._context,) if self.speculate_k else ()) + \
                    (self.pool.k_pools, self.pool.v_pools, tables) + \
                    self._state_args()
                step = self._step_program(program_steps, attend_width,
                                          eos, args)
                if self.speculate_k:
                    (emitted, emit_mask, self._tokens, self._lengths,
                     self._context, k_pools, v_pools) = step(*args)
                else:
                    (emitted, emitted_active, self._tokens,
                     self._lengths, k_pools, v_pools,
                     *model_counts) = step(*args)
                    if self.slot_state is not None:
                        self._state_back([model_counts.pop()], 0)
                self.pool.k_pools, self.pool.v_pools = k_pools, v_pools
            elif self.speculate_k:
                (emitted, emit_mask, self._tokens, self._lengths,
                 self._context, self._k, self._v) = self._step(
                    self.params, self._tokens, self._lengths,
                    jnp.array(scan_active), jnp.array(budgets),
                    self._context, self._k, self._v,
                    num_steps=num_steps, eos=eos)
            else:
                (emitted, emitted_active, self._tokens, self._lengths,
                 self._k, self._v) = self._step(
                    self.params, self._tokens, self._lengths,
                    jnp.array(scan_active), jnp.array(budgets),
                    self._k, self._v, num_steps=num_steps, eos=eos)
            self.stats["steps"] += num_steps
        # prefill rides BETWEEN decode scans: dispatched after the scan,
        # it runs on device while the host below waits out the scan
        # sync and walks the emissions — off the decode critical path,
        # rationed by prefill_budget
        profiler.enter("admit_dispatch")
        self._admit_pending()
        profiler.enter("extend_dispatch")
        self._advance_prefills()
        profiler.enter("host_sync")
        self._prefill_ahead += self._round_prefill_tokens
        if self._round_prefill_tokens > \
                self.stats["round_prefill_tokens_max"]:
            self.stats["round_prefill_tokens_max"] = \
                self._round_prefill_tokens
        # ONE host transfer for the whole round: scan sync arrays AND
        # every due admit wave's firsts ride one device_get — separate
        # np.asarray calls pay one host round trip each, per wave per
        # round
        wave_firsts = [firsts for firsts, _ in waves_due]
        if scanned:
            if self.speculate_k:
                emitted, emit_mask, wave_firsts = jax.device_get(
                    (emitted, emit_mask, wave_firsts))
            else:
                emitted, emitted_active, wave_firsts, model_counts = \
                    jax.device_get((emitted, emitted_active, wave_firsts,
                                    model_counts))
                for counted in model_counts:
                    for name, value in zip(self._model.counters, counted):
                        self.stats[name] += int(value)
            self.stats["decode_s"] += time.perf_counter() - decode_start
        elif wave_firsts:
            wave_firsts = jax.device_get(wave_firsts)
        profiler.enter("wave_resolve")
        # resolve deferred admits from EARLIER rounds: their prefill
        # programs ran before this round's scan on the in-order device
        # stream, so the fetch never waits on fresh work
        now = time.monotonic()
        for firsts, (_, wave) in zip(wave_firsts, waves_due):
            for j, request in wave:
                if self._slots[request.slot] is request and \
                        not request.generated:
                    self._deliver(request.slot, int(firsts[j]), now)
        if scanned:
            profiler.enter("deliver")
            if self.speculate_k:
                self._deliver_spec(emitted, emit_mask, occupied,
                                   num_steps, now)
            else:
                # useful/wasted account DEVICE work (scan emissions the
                # host meant to use); tokens_decode counts what was
                # actually DELIVERED — they differ when a wave-resolved
                # first token retires the slot before its scan
                # emissions land (EOS as prefill argmax)
                useful = int(emitted_active[:, occupied].sum())
                self.stats["useful_steps"] += useful
                self.stats["wasted_steps"] += \
                    num_steps * len(occupied) - useful
                delivered = 0
                for k in range(emitted.shape[0]):
                    for slot in occupied:
                        request = self._slots[slot]
                        if request is None or not emitted_active[k, slot]:
                            continue
                        self._deliver(slot, int(emitted[k, slot]), now)
                        delivered += 1
                self.stats["tokens_decode"] += delivered
        if scanned or wave_firsts or self._round_prefill_tokens:
            # working rounds only: idle pump ticks would drag the EWMA
            # toward the timer period and break the admission estimate
            # (and would dilute the profiler's phase attribution the
            # same way — idle ticks are abandoned, not committed)
            record = profiler.commit_round(
                self.stats["rounds"], num_steps if scanned else 0,
                scanned_slots, self._round_prefill_tokens,
                len(self._pending), attended if scanned else 0, ahead,
                self._round_prefill_pieces, self._round_prefix_tokens)
            # what remains of a stall in a run nobody traced
            slow = slow_round(record, self._round_ewma)
            if slow is not None:
                self.logger.warning("%s", slow)
            elapsed = record[_WALL_S]
            self._round_ewma = elapsed if self._round_ewma is None \
                else 0.7 * self._round_ewma + 0.3 * elapsed
        else:
            profiler.abandon_round()
            if self.pool is not None and self.idle:
                # idle-watermark pool release (ISSUE 16 satellite):
                # a shrink retraces the paged program family, so it
                # only ever fires on an idle tick — never inside a
                # serving window
                self.pool.maybe_shrink()
        if self.idle:
            profiler.idle = True      # the next round follows an idle decoder
            if self.on_idle is not None:
                self.on_idle()

    def _deliver_spec(self, emitted, emit_mask, occupied,
                      num_steps: int, now: float) -> None:
        """Walk a speculative round's [K, S, 1+k] emissions: per slot,
        the masked tokens in (iteration, position) order are exactly
        the greedy stream.  Also settles the speculation counters —
        spec_proposed/spec_accepted feed accept_rate(), and
        accepted_per_step is the mean tokens one verify iteration
        yielded (1.0 = speculation never helped)."""
        counts = emit_mask.sum(axis=2)[:, occupied]     # [K, |occ|]
        verify_steps = int((counts > 0).sum())
        self.stats["useful_steps"] += verify_steps
        self.stats["wasted_steps"] += \
            num_steps * len(occupied) - verify_steps
        self.stats["spec_proposed"] += self.speculate_k * verify_steps
        self.stats["spec_accepted"] += int(
            np.maximum(counts - 1, 0).sum())
        # tokens_decode counts DELIVERED tokens (a wave-resolved EOS
        # first token can retire the slot before its scan emissions
        # land — those are device work, not token flow)
        delivered = 0
        for slot in occupied:
            mask_slot = emit_mask[:, slot, :]
            if not mask_slot.any():
                continue
            for token in emitted[:, slot, :][mask_slot]:
                request = self._slots[slot]
                if request is None:
                    break                 # retired mid-burst (EOS)
                self._deliver(slot, int(token), now)
                delivered += 1
        self.stats["tokens_decode"] += delivered
        if self.stats["useful_steps"]:
            # mean tokens one emitting verify iteration yielded —
            # derived straight from the two source counters so it can
            # never drift from them
            self.stats["accepted_per_step"] = (
                self.stats["tokens_decode"] /
                self.stats["useful_steps"])

    def accept_rate(self) -> float:
        """Fraction of proposed draft tokens the verify step accepted
        (speculation quality; 0.0 when speculation is off or no drafts
        were scored)."""
        proposed = self.stats["spec_proposed"]
        return self.stats["spec_accepted"] / proposed if proposed \
            else 0.0

    def _deliver(self, slot: int, token: int, now: float) -> None:
        """Append one resolved token, stamping SLO timestamps: tokens
        land in per-sync bursts, so TTFT is submit→first burst and the
        stall metric is the worst gap BETWEEN bursts (same-burst tokens
        contribute no gap)."""
        request = self._slots[slot]
        journey = request.journey
        if not request.generated:
            request.first_time = now
            ttft = now - request.submit_time
            self.ttft_samples.append(ttft)
            if journey is not None:
                journey.first_round = self.profiler.seq
            # mergeable SLO surface (ISSUE 12): the same number the
            # deque keeps, but fleet-mergeable and carrying the worst
            # requests' trace ids as exemplars.  Split the population
            # by the prefill label (ISSUE 13/14): cached/cold from the
            # prefix probe, or an explicit override ("remote" for
            # disaggregated prefill) so each serving mode's attainment
            # is quotable on its own — a cache or a prefill pool that
            # only helps one population must not hide behind a blended
            # percentile.
            self._slo_sketch(
                "ttft", journey.tenant if journey else "",
                request.prefill_label or
                ("cached" if request.prefix_hit else "cold")).observe(
                ttft, exemplar=(journey.trace_id or request.request_id)
                if journey else None)
            if request.dedup_hot and self.prefix_cache is not None:
                # a same-batch duplicate is waiting on this prompt:
                # its rows are device-written now (the first token
                # resolved), so harvest them early instead of at
                # retire (ISSUE 14 satellite)
                try:
                    self._prefix_harvest_prompt(slot, request)
                except Exception:
                    self.logger.exception(
                        "early prompt harvest failed for %s",
                        request.request_id)
        elif now > request.last_time:
            request.max_gap = max(request.max_gap,
                                  now - request.last_time)
        if journey is not None:
            journey.token(now)
        request.generated.append(token)
        request.last_time = now
        if self.on_token is not None:
            try:
                self.on_token(request.request_id, slot, token, now)
            except Exception:
                self.logger.exception("on_token failed for %s",
                                      request.request_id)
        if self._finished(request, token):
            self._retire(slot)

    def slo_stats(self) -> dict:
        """Measured per-request latency SLOs (milliseconds): TTFT
        (submit → first token burst), per-request mean inter-token
        latency, and the p95 of each request's worst inter-burst stall
        (what chunked prefill bounds)."""
        def pct(samples, q):
            return float(np.percentile(np.fromiter(samples, float),
                                       q)) * 1000.0 if samples else None
        return {
            "ttft_p50_ms": pct(self.ttft_samples, 50),
            "ttft_p95_ms": pct(self.ttft_samples, 95),
            "itl_p50_ms": pct(self.itl_samples, 50),
            "itl_p95_ms": pct(self.itl_samples, 95),
            "stall_p95_ms": pct(self.gap_samples, 95),
            "ttft_count": len(self.ttft_samples),
            "itl_count": len(self.itl_samples),
        }

    def slo_sketch_stats(self, prefill: str | None = None,
                         tenant: str | None = None) -> dict:
        """The SAME latency SLOs as slo_stats, but read from the
        mergeable sketches (ISSUE 12): p50/p95/p99 per kind merged
        across this decoder's tenants, plus the worst exemplar ids.
        This is the form the bench artifact quotes (lat_llama_ttft_*)
        — fleet-aggregatable, with per-request attribution behind
        every percentile.  `prefill` ("cached"/"cold"/"remote")
        restricts the TTFT merge to one population (ISSUE 13/14 — the
        conversation and disagg rungs' A/B surfaces); ITL has no
        prefill split.  `tenant` restricts BOTH kinds to one tenant's
        sketches (the disagg rung isolates its decode-stream ITL from
        the burst population this way)."""
        from .observe.sketch import merge_sketches
        out: dict = {}
        for kind in ("ttft", "itl"):
            merged = merge_sketches(
                sketch for (sketch_kind, sketch_tenant, sketch_prefill),
                sketch in self._slo_sketches.items()
                if sketch_kind == kind and
                (tenant is None or sketch_tenant == tenant) and
                (prefill is None or kind != "ttft" or
                 sketch_prefill == prefill))
            for q, suffix in ((0.5, "p50"), (0.95, "p95"),
                              (0.99, "p99")):
                value = merged.quantile(q) if merged is not None \
                    else None
                out[f"{kind}_{suffix}_ms"] = \
                    None if value is None else value * 1000.0
            out[f"{kind}_exemplars"] = [] if merged is None else \
                [e[1] for e in merged.worst_exemplars(4)]
        return out

    def clear_slo_sketches(self) -> None:
        """Drop sketch observations and exemplars (bench warmup
        boundary — compile-time TTFTs must not contaminate the
        measured percentiles, same rule as the sample deques)."""
        for sketch in self._slo_sketches.values():
            sketch.clear()

    def wasted_fraction(self) -> float:
        total = self.stats["useful_steps"] + self.stats["wasted_steps"]
        return self.stats["wasted_steps"] / total if total else 0.0

    def mean_occupancy(self) -> float:
        rounds = max(self.stats["rounds"], 1)
        return self.stats["occupancy_sum"] / rounds


@functools.lru_cache(maxsize=64)
def _admit_fn_for(config: LlamaConfig, bucket: int, width: int,
                  kv_int8: bool, speculative: bool):
    """Builder behind ContinuousDecoder._admit_fn (process-wide cache:
    decoders sharing a geometry share the jit object and its compiled
    executables)."""
    from .models.llama import init_llama_caches, llama_hidden

    def admit(params, k_caches, v_caches, tokens, lengths, context,
              prompts, true_lens, slots, valid):
        # prompts: [A, bucket]; slots: [A] DISTINCT slot ids (pad
        # rows point at other distinct slots and write back their
        # own current content — a no-op); valid: [A] bool.
        caches = init_llama_caches(config, width, bucket)
        hidden, caches = llama_hidden(params, config, prompts, caches)
        idx = jnp.maximum(true_lens - 1, 0)
        # select each prompt's last position BEFORE the vocab
        # projection: full prefill logits are [A, bucket, vocab] —
        # gigabytes at serving widths
        last_hidden = jnp.take_along_axis(
            hidden, idx[:, None, None], axis=1)[:, 0]
        last = L.linear_logits(params["lm_head"], last_hidden)
        firsts = jnp.argmax(last, axis=-1).astype(jnp.int32)
        mask = valid[:, None, None, None]
        mask_s = valid[:, None, None]
        for i, cache in enumerate(caches):
            if kv_int8:
                # quantize the exact prefill K/V once, scatter the
                # int8 rows + per-(row, head, position) scales
                kq = L.quantize_kv_cache(cache["k"])
                vq = L.quantize_kv_cache(cache["v"])
                k_caches[i] = {
                    "q": k_caches[i]["q"].at[slots, :, :bucket].set(
                        jnp.where(mask, kq["q"],
                                  k_caches[i]["q"][slots]
                                  [:, :, :bucket])),
                    "s": k_caches[i]["s"].at[slots, :, :bucket].set(
                        jnp.where(mask_s, kq["s"],
                                  k_caches[i]["s"][slots]
                                  [:, :, :bucket]))}
                v_caches[i] = {
                    "q": v_caches[i]["q"].at[slots, :, :bucket].set(
                        jnp.where(mask, vq["q"],
                                  v_caches[i]["q"][slots]
                                  [:, :, :bucket])),
                    "s": v_caches[i]["s"].at[slots, :, :bucket].set(
                        jnp.where(mask_s, vq["s"],
                                  v_caches[i]["s"][slots]
                                  [:, :, :bucket]))}
            else:
                cur_k = k_caches[i][slots][:, :, :bucket]
                cur_v = v_caches[i][slots][:, :, :bucket]
                k_caches[i] = k_caches[i].at[slots, :, :bucket].set(
                    jnp.where(mask, cache["k"], cur_k))
                v_caches[i] = v_caches[i].at[slots, :, :bucket].set(
                    jnp.where(mask, cache["v"], cur_v))
        tokens = tokens.at[slots].set(
            jnp.where(valid, firsts, tokens[slots]))
        lengths = lengths.at[slots].set(
            jnp.where(valid, true_lens, lengths[slots]))
        if speculative:
            # seed the drafter's history with the prompt itself
            context = context.at[slots, :bucket].set(
                jnp.where(valid[:, None], prompts,
                          context[slots][:, :bucket]))
        return firsts, k_caches, v_caches, tokens, lengths, context

    return jax.jit(
        admit, donate_argnames=("k_caches", "v_caches", "tokens",
                                "lengths", "context"))


@functools.lru_cache(maxsize=64)
def _prefix_copy_fn_for(config: LlamaConfig, t_write: int,
                        kv_int8: bool, speculative: bool):
    """Builder for the prefix-hit admit copy: writes a cached chain's
    concatenated K/V rows into ONE slot's cache rows [0, t_write) and
    seeds the speculative context with the cached prompt tokens.
    Compiled once per (geometry, pow2-padded write length) — pad rows
    are zeros landing at positions >= the hit, dead cells under the
    same overwrite-before-attend invariant as the admit scatter's
    padding.  No forward pass at all: a full-block hit costs one
    scatter where a cold admit costs a prefill."""

    def copy(k_caches, v_caches, context, k_rows, v_rows, slot,
             ctx_tokens):
        for i in range(config.num_layers):
            if kv_int8:
                k_caches[i] = {
                    "q": k_caches[i]["q"].at[slot, :, :t_write].set(
                        k_rows[i]["q"]),
                    "s": k_caches[i]["s"].at[slot, :, :t_write].set(
                        k_rows[i]["s"])}
                v_caches[i] = {
                    "q": v_caches[i]["q"].at[slot, :, :t_write].set(
                        v_rows[i]["q"]),
                    "s": v_caches[i]["s"].at[slot, :, :t_write].set(
                        v_rows[i]["s"])}
            else:
                k_caches[i] = k_caches[i].at[slot, :, :t_write].set(
                    k_rows[i])
                v_caches[i] = v_caches[i].at[slot, :, :t_write].set(
                    v_rows[i])
        if speculative:
            context = context.at[slot, :t_write].set(ctx_tokens)
        return k_caches, v_caches, context

    return jax.jit(copy, donate_argnames=("k_caches", "v_caches",
                                          "context"))


@functools.lru_cache(maxsize=64)
def _extend_fn_for(config: LlamaConfig, chunk_len: int, width: int,
                   kv_int8: bool, speculative: bool):
    """Builder behind ContinuousDecoder._extend_fn: advances up to
    `width` mid-prefill slots by one `chunk_len`-token chunk of their
    prompt — computes the chunk's K/V against the already-written
    cache prefix and scatters it in at each row's own offset.  Rows
    flagged `finish` also run the lm_head on their prompt's last
    position and land their first token + length in the device
    buffers, exactly like a single-shot admit — the first token then
    resolves from the stashed wave at the next round's sync.

    No reference counterpart: the reference's pipeline blocks a
    whole stream per frame (reference pipeline.py:650-712); chunked
    prefill is how an iteration-level scheduler keeps decode ITL
    flat under prompt-heavy load."""
    cos, sin = L.rope_frequencies(config.head_dim,
                                  config.max_seq_len,
                                  config.rope_theta)
    num_heads, num_kv = config.num_heads, config.num_kv_heads
    group = num_heads // num_kv

    def extend(params, k_caches, v_caches, tokens, lengths, context,
               chunk_tokens, offsets, slots, valid, finish,
               final_idx):
        # chunk_tokens: [A, C]; offsets/slots/final_idx: [A];
        # valid/finish: [A] bool.  Pad rows (valid=False) point at
        # DISTINCT spare slots and write back their own content.
        x = L.embedding(params["embed"],
                        chunk_tokens).astype(config.dtype)
        t_cap = _cache_time(k_caches[0])
        # causal over prefix + chunk: query j (absolute position
        # offsets+j) sees cache positions <= offsets+j — earlier
        # chunks' rows are already in the cache, this chunk's are
        # written below before attending
        q_pos = offsets[:, None] + jnp.arange(chunk_len)[None, :]
        mask = (jnp.arange(t_cap)[None, None, :] <=
                q_pos[:, :, None])[:, None, None]   # [A,1,1,C,T]
        scale = 1.0 / jnp.sqrt(jnp.asarray(config.head_dim,
                                           jnp.float32))

        def write_rows(rows, chunk_kv, offs):
            # per-row dynamic_update_slice (vmapped): offsets stay
            # in-bounds by construction — the host slides a final
            # chunk BACK (recomputing overlap, idempotent) so
            # offset+C never exceeds the prompt length
            return jax.vmap(
                lambda row, kv, off: jax.lax.dynamic_update_slice(
                    row, kv, (0, off, 0)))(rows, chunk_kv, offs)

        def write_scales(rows, chunk_s, offs):
            return jax.vmap(
                lambda row, s, off: jax.lax.dynamic_update_slice(
                    row, s, (0, off)))(rows, chunk_s, offs)

        for i, layer in enumerate(params["layers"]):
            normed = L.rms_norm(layer["ln_attn"], x)
            q = L._split_heads(L.linear(layer["attn"]["q"], normed),
                               num_heads)
            k = L._split_heads(L.linear(layer["attn"]["k"], normed),
                               num_kv)
            v = L._split_heads(L.linear(layer["attn"]["v"], normed),
                               num_kv)
            q = L.apply_rope(q, cos, sin, offsets)
            k = L.apply_rope(k, cos, sin, offsets)
            if kv_int8:
                # attend over the DEQUANTIZED prefix (exactly the
                # int8-rounded values decode will read) + the
                # exact current chunk; store the chunk quantized.
                # Untouched positions keep their original q/s —
                # re-quantizing them would double-round.
                orig_kq = k_caches[i]["q"][slots]
                orig_ks = k_caches[i]["s"][slots]
                orig_vq = v_caches[i]["q"][slots]
                orig_vs = v_caches[i]["s"][slots]
                k_rows = write_rows(L.dequantize_kv_cache(
                    {"q": orig_kq, "s": orig_ks}, x.dtype), k, offsets)
                v_rows = write_rows(L.dequantize_kv_cache(
                    {"q": orig_vq, "s": orig_vs}, x.dtype), v, offsets)
            else:
                orig_k = k_caches[i][slots]    # [A, kv, T, D]
                orig_v = v_caches[i][slots]
                k_rows = write_rows(orig_k, k, offsets)
                v_rows = write_rows(orig_v, v, offsets)
            q_grouped = q.reshape(q.shape[0], num_kv, group,
                                  chunk_len, config.head_dim)
            scores = jnp.einsum(
                "akgcd,aktd->akgct", q_grouped, k_rows,
                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(mask, scores, -1e30)
            weights = jax.nn.softmax(
                scores, axis=-1).astype(v_rows.dtype)
            out = jnp.einsum("akgct,aktd->akgcd", weights, v_rows,
                             preferred_element_type=jnp.float32)
            out = out.reshape(out.shape[0], num_heads, chunk_len,
                              config.head_dim).astype(x.dtype)
            x = x + L.linear(layer["attn"]["o"], L._merge_heads(out))
            x = x + llama_ffn(layer, config,
                              L.rms_norm(layer["ln_mlp"], x))
            keep = valid[:, None, None, None]
            if kv_int8:
                keep_s = valid[:, None, None]
                kq = L.quantize_kv_cache(k)
                vq = L.quantize_kv_cache(v)
                k_caches[i] = {
                    "q": k_caches[i]["q"].at[slots].set(
                        jnp.where(keep, write_rows(
                            orig_kq, kq["q"], offsets), orig_kq)),
                    "s": k_caches[i]["s"].at[slots].set(
                        jnp.where(keep_s, write_scales(
                            orig_ks, kq["s"], offsets), orig_ks))}
                v_caches[i] = {
                    "q": v_caches[i]["q"].at[slots].set(
                        jnp.where(keep, write_rows(
                            orig_vq, vq["q"], offsets), orig_vq)),
                    "s": v_caches[i]["s"].at[slots].set(
                        jnp.where(keep_s, write_scales(
                            orig_vs, vq["s"], offsets), orig_vs))}
            else:
                k_caches[i] = k_caches[i].at[slots].set(
                    jnp.where(keep, k_rows, orig_k))
                v_caches[i] = v_caches[i].at[slots].set(
                    jnp.where(keep, v_rows, orig_v))
        x = L.rms_norm(params["ln_out"], x)
        last_hidden = jnp.take_along_axis(
            x, final_idx[:, None, None], axis=1)[:, 0]
        last = L.linear_logits(params["lm_head"], last_hidden)
        firsts = jnp.argmax(last, axis=-1).astype(jnp.int32)
        apply = valid & finish
        tokens = tokens.at[slots].set(
            jnp.where(apply, firsts, tokens[slots]))
        lengths = lengths.at[slots].set(
            jnp.where(apply, offsets + final_idx + 1,
                      lengths[slots]))
        if speculative:
            ctx_rows = context[slots]               # [A, ctx]
            written = jax.vmap(
                lambda row, blk, off: jax.lax.dynamic_update_slice(
                    row, blk, (off,)))(ctx_rows, chunk_tokens,
                                       offsets)
            context = context.at[slots].set(
                jnp.where(valid[:, None], written, ctx_rows))
        return firsts, k_caches, v_caches, tokens, lengths, context

    return jax.jit(
        extend, donate_argnames=("k_caches", "v_caches", "tokens",
                                 "lengths", "context"))
