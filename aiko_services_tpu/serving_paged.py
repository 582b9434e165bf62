# Paged KV block pool for continuous-batching serving (ISSUE 15,
# ROADMAP item 3 residue c).
#
# The dense slot cache ([S, H, T, D] per layer) made three subsystems
# move KV by COPY: a prefix-cache hit copied the cached chain's rows
# into the slot, harvest copied them back out at retire, and the
# disaggregated install paid the same copy on top of the wire transfer.
# vLLM's PagedAttention (Kwon et al., SOSP 2023) is the fix: ONE pool
# of fixed-size token blocks per layer plus per-slot int32 block
# tables, so "this slot holds that prefix" is a table edit over
# refcounted blocks, not a row movement —
#
#   * a prefix hit ALIASES the cached chain's pool blocks into the
#     slot's table (retain refs; zero bytes move);
#   * harvest is "retain + record key" — the slot's own blocks BECOME
#     the cache entries (the double write is gone);
#   * the disaggregated install (DistServe, OSDI 2024) writes shipped
#     blocks straight into pool blocks once — later admits are table
#     edits;
#   * copy-on-extend: writing into a SHARED block (refs > 1 — e.g. the
#     near-seq-cap final-chunk slide-back into a cached region) first
#     copies it to a fresh block, so aliased readers never see a
#     mutation.  At most one partial block copies per such write; the
#     common hit path copies nothing.
#
# Device-side discipline, the gather path (the CPU's, the parity
# oracle, a tensor-parallel decoder's; a decoder on one chip whose pool
# the paged kernel walks by hand attends its step through the kernel
# instead and builds no views, see "pallas kernel attention" below):
# the compiled step GATHERS a slot-major
# [S, H, T, D] view from the pool once per round (the main cache is
# read-only through the scan, so the gather hoists out of it) and runs
# the SAME attention bodies (_slot_attention_block /
# _slot_attention_spec) — the gathered view is value-identical to the
# dense slot cache over every live position and the tail past them is
# masked to exact zeros, so paged greedy output is identical to dense
# (the parity matrix of tests/test_paged_kv.py) at whatever width T
# the view is built: the decoder takes T from a short ladder of widths,
# the smallest that covers the round's longest live context (ISSUE 28;
# serving.ContinuousDecoder._attend_width), so the views and the
# attention over them move what is live and not max_seq.  Round-end
# side-buffer merges scatter to (block, offset) pairs computed from the
# tables, with out-of-range ids dropping exactly like the dense path's
# _POS_INVALID entries.  This module owns the pool allocator and the
# paged compiled-program builders; serving.ContinuousDecoder(
# paged_kv=True) is the integration point; the dense path stays the
# default and the parity oracle.

from __future__ import annotations

import dataclasses
import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from .models import layers as L
from .models.llama import (SCOPE_ATTN_CORE, SCOPE_ATTN_PROJ, SCOPE_HEAD,
                           SCOPE_KV_MERGE, SCOPE_KV_VIEW, SCOPE_MLP,
                           LlamaConfig, llama_ffn)
from .utils import get_logger

__all__ = ["BlockPool", "PagedModel", "SlotState"]


@dataclasses.dataclass(frozen=True)
class PagedModel:
    """What a model declares so that the paged decoder can serve it
    (ISSUE 31): `config.paged_model()` returns one, and beside it the
    configuration says what a layer keeps of a token,
    `config.cache_leaves`: one (heads, lanes) a pool side, K then V for
    grouped-query attention, a single shared row for latent attention.
    The builders below own the loop, the side buffers, the merge, the
    embedding and the head; everything between is the model's, layer by
    layer.  serving.py and this module read these fields and test no
    model's name or class.

    rope(config) -> (cos, sin) tables
    token_block_argmax(params, config, token_block, attend, live)
        -> (tokens [S, W], counts): the pass over a [S, W] block of
        the decode step; attend(i, layer, normed) -> attention output;
        `live` [S] are the slots that decode; counts is an int32 vector
        in `counters`' order, or () where the model counts nothing
    step_attention(kernel) -> attend(tables, layer, config, x, cos, sin,
        leaves, views, sides, entry_lengths, lengths, step_index,
        entry_active, state, active) -> (attention output, the sides
        rewritten, the layer's slot state after the token, counts in
        `counters`' order or None): `leaves` are the layer's pool
        leaves, `views` their gathered slot-major views (None for the
        kernel), `sides` this round's side buffers, one a pool side,
        `state` the layer's slot state (() where the model keeps none,
        and () comes back), `active` [S] the slots that decode this
        token
    prefill(params, config, prompts, valid, true_lens) -> (hidden after
        the last norm [A, T, dim], per layer the rows [A, heads, T,
        lanes] of each pool side): an admit's compute over prompts
        [A, T] of which row a is real where valid[a], up to
        true_lens[a]
    extend_prepare(config, chunk, kernel, ctx) -> whatever the layers
        share (masks, a cut table); ctx holds offsets, q_pos, valid,
        finish, final_idx, tables_rows, t_cap, block_tokens
    extend_layer(kernel) -> layer(layer, config, x, cos, sin, leaves,
        ctx, prepared) -> (x after the layer, the chunk's rows of each
        pool side)
    walks(config, kv_int8, interpret) -> who reads a slot's live blocks
        in the step: "kernel" where the pallas kernel walks this model's
        pool by hand (ops.paged_attention), "model" where the model
        reads the pool itself in every program, kernel or not (the step
        then gathers no views and has one width), None where neither
        does and the step attends gathered views
    step_kernel(config, interpret) -> whether the model's OWN step has
        a pallas form at this geometry: a recurrence over its slot state
        (ISSUE 34), its own walk of the pool (ISSUE 39); None where the
        model has none.  Like "kernel" above it is the decoder that
        decides, and step_attention's `kernel` that says so
    scan_kernel(config, interpret) -> whether a prompt's piece (an admit,
        an extend) runs the model's recurrence over slot state as a
        pallas kernel (ISSUE 41: ops/delta_chunk, the delta rule's WY
        form; ISSUE 46: ops/ssm_chunk, Mamba-2's decayed rule with B and
        C shared by every head); None where the model has none.  The
        MODEL decides, at trace time, from what it can observe; the
        decoder only logs the answer at set-up
    counters: names of the step's counts, added to decoder.stats
    supports: the serving paths this model's pool is carried through;
        the decoder refuses the others at construction
    block_multiple: the tokens that a pool block holds a whole multiple
        of (a model that reads a leaf by whole tiles of rows); the
        decoder refuses another `kv_block` at construction

    A configuration may declare its leaves LAYER BY LAYER
    (`config.layer_cache_leaves`: per layer a tuple of (heads, lanes,
    tokens a row), possibly empty; see layer_leaves) and per-slot STATE
    (`config.slot_state`: per layer a tuple of (shape, dtype); see
    SlotState).  A model with slot state takes and returns it (ISSUE 33):
        step_attention's attend: where `active` [S] is False the slot
            decodes nothing and its state comes back unchanged
        prefill -> (hidden, rows, per layer the state after each row's
            true length)
        extend_layer's layer(..., prepared, state) -> (x, rows, state)
    residual_in(config, x), final_norm(params, config, x): what an
        extend does to the embedding before the first layer and instead
        of the last norm, where the residual is not one stream (or the
        embedding is scaled, or the norm's epsilon is the model's own)
    head(params, config, hidden) -> f32 logits of an admit's or a
        chunk's last position, where the head is not `lm_head` (ISSUE
        45: the embedding itself, tied)

    A layer may keep MORE than two leaves of a token (ISSUE 38: K, V and
    an indexer key): `sides` and `leaves` then have one entry a leaf, and
    the pool's `v_pools` holds every leaf after the first, leaf after
    leaf (see BlockPool, _pool_sides)."""
    rope: object
    token_block_argmax: object
    step_attention: object
    prefill: object
    extend_prepare: object
    extend_layer: object
    walks: object
    step_kernel: object = None
    scan_kernel: object = None
    counters: tuple = ()
    supports: frozenset = frozenset()
    block_multiple: int = 1
    residual_in: object = None
    final_norm: object = None
    head: object = None


def reads_own_pool(config) -> bool:
    """Whether the model reads its pool itself in every program (its
    `walks` says "model" whatever the pool's precision or backend)."""
    return config.paged_model().walks(config, False, True) == "model"


def layer_leaves(config) -> tuple:
    """Layer by layer, the (heads, lanes, tokens a row) of each leaf a
    layer keeps of a token: what the configuration declares
    (`layer_cache_leaves`), or `cache_leaves` for every layer, a row a
    token."""
    declared = getattr(config, "layer_cache_leaves", None)
    if declared is None:
        declared = (config.cache_leaves,) * config.num_layers
    return tuple(tuple((tuple(leaf) + (1,))[:3] for leaf in layer)
                 for layer in declared)


def first_leaf(config) -> tuple:
    """(heads, lanes) of the first leaf of the first layer that keeps
    any: what the decoder's layout tuple, its refusals and its log speak
    of."""
    return next(layer[0][:2] for layer in layer_leaves(config) if layer)


def token_nbytes(config, kv_int8: bool = False) -> float:
    """Bytes the pool keeps of ONE token over all layers and leaves."""
    itemsize = jnp.dtype(config.dtype).itemsize
    return sum(heads * ((lanes + 4) if kv_int8 else lanes * itemsize)
               / every
               for layer in layer_leaves(config)
               for heads, lanes, every in layer)


class SlotState:
    """What a model keeps of a SLOT and not of a token (ISSUE 33): device
    arrays [max_slots, ...], layer by layer as `config.slot_state`
    declares them, beside the block pool.  Step, admit and extend take
    `arrays` and hand them back rewritten; a request's first program (an
    admit, or the chunk at offset 0) starts its slot from zeros whatever
    the last request left there (the decoder counts those:
    `stats["slot_states_zeroed"]`)."""

    def __init__(self, config, max_slots: int):
        self.arrays = [tuple(jnp.zeros((max_slots,) + tuple(shape), dtype)
                             for shape, dtype in layer)
                       for layer in config.slot_state]

    def nbytes(self) -> int:
        return int(sum(leaf.nbytes
                       for leaf in jax.tree_util.tree_leaves(self.arrays)))


def _state_rows(state, slots, fresh):
    """The rows of `slots` of every state leaf; zeros where `fresh`."""
    def rows(leaf):
        taken = leaf[slots]
        return jnp.where(fresh.reshape((-1,) + (1,) * (leaf.ndim - 1)),
                         jnp.zeros_like(taken), taken)
    return [tuple(rows(leaf) for leaf in layer) for layer in state]


def _state_store(state, slots, valid, rows):
    """`rows` written back to `slots` where `valid` (a row that is not
    carries an out-of-range slot and drops)."""
    def store(leaf, new):
        return leaf.at[jnp.where(valid, slots, leaf.shape[0])].set(
            new.astype(leaf.dtype), mode="drop")
    return [tuple(store(leaf, new) for leaf, new in zip(layer, fresh))
            for layer, fresh in zip(state, rows)]


class BlockPool:
    """Device-resident paged KV block pool + host-side refcounting
    allocator.

    One pool id addresses one `block_tokens`-token block ACROSS the
    whole model: k_pools[i][id] / v_pools[i][id] are layer i's K/V rows
    for that block ([H, B, D] native, or the int8 serving form
    {"q" i8 [H, B, D], "s" f32 [H, B]}).  Block 0 is the reserved NULL
    block (all zeros, never allocated): unfilled table entries point at
    it, so gathers stay in bounds and read only masked positions.

    Refcounts count LOGICAL OWNERS — slot tables, prefix-cache nodes,
    in-flight installs.  alloc_blocks() hands out refs=1 ids (growing
    the device arrays in `grow_blocks` steps when the free list runs
    dry — the paged sibling of _fit_caches' grow); retain()/
    release_blocks() move ownership; refs hitting zero returns the id
    to the free list with its contents left in place (stale rows are
    only ever gathered at masked positions until the next owner
    overwrites them, the same dead-cell invariant as the dense cache).

    Single-threaded like the decoder that owns it (pump runs on the
    event engine)."""

    def __init__(self, config: LlamaConfig, block_tokens: int,
                 kv_int8: bool, initial_blocks: int = 64,
                 grow_blocks: int = 64, name: str = "pool",
                 registry=None):
        self.config = config
        self.block_tokens = int(block_tokens)
        if self.block_tokens < 1:
            raise ValueError(
                f"block_tokens must be >= 1, got {block_tokens}")
        self.kv_int8 = bool(kv_int8)
        self.name = str(name)
        self.grow_blocks = max(1, int(grow_blocks))
        self.logger = get_logger(f"serving.pool.{name}")
        n = max(2, int(initial_blocks) + 1)          # +1: null block
        self.num_blocks = n
        # what the model declares a layer keeps of a token: one leaf a
        # pool side.  A model with a single shared row (latent
        # attention) has no V side: v_pools is then the empty list,
        # which every program below takes and hands back as it is
        # A model may declare them layer by layer (layer_leaves): a
        # layer that keeps nothing of a token has None in both lists,
        # and a leaf may hold one row every few tokens.  The names are
        # grouped-query attention's: k_pools is every layer's FIRST
        # leaf and v_pools its SECOND, whatever they hold (a latent row
        # a token and a pooled indexer key every four, ISSUE 33), and
        # after every layer's second every layer's THIRD (an indexer
        # key beside K and V, ISSUE 38): num_layers entries a leaf,
        # which the programs split again (_pool_sides)
        leaves = layer_leaves(config)
        self.k_pools = self._zero_pools(n, leaves, 0)
        self.v_pools = [pool for side in range(
            1, max(len(layer) for layer in leaves))
            for pool in self._zero_pools(n, leaves, side)]
        self._refs = np.zeros((n,), np.int32)
        self._free = list(range(n - 1, 0, -1))       # 0 reserved
        # every leaf a layer keeps (K + V, or one latent row), all
        # layers, one block's tokens — the budget currency
        self.block_nbytes = int(self.block_tokens *
                                token_nbytes(config, self.kv_int8))
        from .observe.metrics import MirroredStats, default_registry
        self._registry = registry or default_registry()
        self.stats = MirroredStats(
            {"allocs": 0, "frees": 0, "grows": 0, "shrinks": 0,
             "cow_copies": 0, "cow_copy_bytes": 0,
             "install_blocks": 0, "install_bytes": 0},
            metric="kv_pool_events_total",
            help="paged KV block-pool events by kind",
            registry=self._registry,
            skip=("cow_copy_bytes", "install_bytes"),
            labels={"pool": self.name})
        self._gauge_total = self._registry.gauge(
            "kv_pool_blocks", "paged KV pool capacity in blocks",
            labels={"pool": self.name})
        self._gauge_used = self._registry.gauge(
            "kv_pool_blocks_used",
            "paged KV pool blocks with at least one owner",
            labels={"pool": self.name})
        self._gauge_occupancy = self._registry.gauge(
            "kv_pool_occupancy",
            "used / capacity fraction of the paged KV pool",
            labels={"pool": self.name})
        self._used = 0
        # KV memory ledger (ISSUE 20): when attached, every PHYSICAL
        # transition (alloc, refs 1->0 release) reports tenant-
        # attributed byte deltas — retains are ownership moves and
        # stay invisible, so ledger totals conserve against
        # used_blocks() * block_nbytes by construction
        self._ledger = None
        # shrink floor: construction capacity, raised by reserve() —
        # maybe_shrink never retraces below what a caller declared as
        # steady state, so drain/refill cycles don't thrash shapes
        self._floor_blocks = n
        self._publish_gauges()

    def attach_ledger(self, ledger) -> None:
        self._ledger = ledger
        if ledger is not None:
            ledger.attach_pool(self)

    # -- device arrays -----------------------------------------------------
    def _zero_pools(self, n: int, leaves: tuple, side: int) -> list:
        """Pool side `side` of every layer: None where the layer keeps
        no such leaf."""
        def zeros(layer):
            if len(layer) <= side:
                return None
            heads, lanes, every = layer[side]
            if self.block_tokens % every:
                raise ValueError(
                    f"a leaf of one row every {every} tokens needs blocks "
                    f"of whole rows, got {self.block_tokens} tokens")
            shape = (n, heads, self.block_tokens // every, lanes)
            if self.kv_int8:
                return {"q": jnp.zeros(shape, jnp.int8),
                        "s": jnp.zeros(shape[:3], jnp.float32)}
            return jnp.zeros(shape, self.config.dtype)
        return [zeros(layer) for layer in leaves]

    def nbytes(self) -> int:
        """Bytes currently allocated to the pool device arrays — what
        ContinuousDecoder.kv_cache_bytes() reports in paged mode."""
        return int(sum(
            leaf.size * jnp.dtype(leaf.dtype).itemsize
            for pools in (self.k_pools, self.v_pools)
            for pool in pools
            for leaf in jax.tree_util.tree_leaves(pool)))

    def _grow(self, need: int) -> None:
        # GEOMETRIC growth (at least doubling): every distinct pool
        # capacity is a fresh shape for every compiled program that
        # touches it, so linear growth would retrace the whole
        # step/admit/extend family once per increment — measured as a
        # 10x cold-TTFT inflation on the conversation rung.  Doubling
        # bounds the retrace count to O(log blocks), the same
        # discipline as _fit_caches' t_block quantization.
        extra = -(-max(need, 1) // self.grow_blocks) * self.grow_blocks
        extra = max(extra, self.num_blocks - 1)
        old_n, new_n = self.num_blocks, self.num_blocks + extra
        grow = _pool_grow_fn(old_n, new_n)
        self.k_pools = grow(self.k_pools)
        self.v_pools = grow(self.v_pools)
        self._free.extend(range(new_n - 1, old_n - 1, -1))
        # geometric growth is amortized O(log blocks) and reserve()
        # pre-warms steady state out of the serving window entirely
        self._refs = np.concatenate(  # graft: disable=lint-hot-alloc
            [self._refs, np.zeros((extra,), np.int32)])
        self.num_blocks = new_n
        self.stats["grows"] += 1
        self._publish_gauges()

    def reserve(self, capacity: int) -> None:
        """Grow the pool to at least `capacity` allocatable blocks NOW
        (no allocation).  Every distinct pool capacity is a fresh
        shape for the compiled programs, so callers that can predict
        steady-state residency (slot coverage + prefix-cache budget)
        reserve it up front and keep growth retraces out of the
        serving window."""
        self._floor_blocks = max(self._floor_blocks,
                                 int(capacity) + 1)
        short = int(capacity) - (self.num_blocks - 1)
        if short > 0:
            self._grow(short)

    def maybe_shrink(self, watermark: float = 0.25) -> int:
        """Idle-watermark release (ISSUE 16 satellite): when occupancy
        has fallen to `watermark` or below — a tenant drain — release
        the pool's FREE TAIL back to the allocator so steady-state HBM
        stays honest after a burst.  Returns blocks released (0 when
        the watermark, floor, or geometric hysteresis says no).

        Only the tail [keep, num_blocks) can go: block ids are array
        positions, so reclaiming interior free blocks would mean
        compacting live contents and rewriting every owner's table.
        The release is geometric (at least halving, mirroring _grow's
        doubling) and never cuts below the reserve()/construction
        floor — a capacity change retraces every compiled program
        that touches the pool, so callers gate this on IDLE (the
        decoder's pump does) and the hysteresis keeps it rare."""
        capacity = self.num_blocks - 1
        if capacity <= 0 or self._used > watermark * capacity:
            return 0
        keep = self.num_blocks
        while keep > self._floor_blocks and self._refs[keep - 1] == 0:
            keep -= 1
        released = self.num_blocks - keep
        if released * 2 < self.num_blocks:
            return 0
        shrink = _pool_shrink_fn(keep)
        self.k_pools = shrink(self.k_pools)
        self.v_pools = shrink(self.v_pools)
        self._free = [i for i in self._free if i < keep]
        self._refs = self._refs[:keep]
        self.num_blocks = keep
        self.stats["shrinks"] += 1
        self._publish_gauges()
        self.logger.info("pool %s shrank by %d blocks to %d",
                         self.name, released, keep - 1)
        return released

    # -- allocator ---------------------------------------------------------
    def alloc_blocks(self, count: int, tenant: str = "") -> list:
        """`count` fresh block ids, each with refs=1 owned by the
        caller.  Grows the device pools when the free list runs dry.
        `tenant` attributes the bytes in the KV ledger (ISSUE 20) —
        accounting only, allocation behavior is tenant-blind."""
        count = int(count)
        if count <= 0:
            return []
        if len(self._free) < count:
            self._grow(count - len(self._free))
        ids = [self._free.pop() for _ in range(count)]
        for block_id in ids:
            self._refs[block_id] = 1
        self._used += count
        self.stats["allocs"] += count
        if self._ledger is not None:
            self._ledger.device_delta(
                tenant, count * self.block_nbytes, "alloc")
        self._publish_gauges()
        return ids

    def retain(self, ids) -> None:
        for block_id in ids:
            if not 0 < block_id < self.num_blocks or \
                    self._refs[block_id] <= 0:
                raise ValueError(
                    f"pool {self.name!r}: retain of dead block "
                    f"{block_id}")
            self._refs[block_id] += 1

    def release_blocks(self, ids, tenant: str = "") -> None:
        """Drop one ref per id; refs hitting zero return the id to the
        free list (contents stay — dead cells until reallocated).
        `tenant` attributes the freed bytes in the KV ledger — only
        the refs 1->0 transitions are physical."""
        freed = 0
        for block_id in ids:
            if not 0 < block_id < self.num_blocks:
                continue
            refs = self._refs[block_id]
            if refs <= 0:
                raise ValueError(
                    f"pool {self.name!r}: release of free block "
                    f"{block_id}")
            self._refs[block_id] = refs - 1
            if refs == 1:
                self._free.append(block_id)
                freed += 1
        if freed:
            self._used -= freed
            self.stats["frees"] += freed
            if self._ledger is not None:
                self._ledger.device_delta(
                    tenant, -freed * self.block_nbytes, "release")
            self._publish_gauges()

    def refs(self, block_id: int) -> int:
        return int(self._refs[block_id])

    def used_blocks(self) -> int:
        """Blocks with at least one live owner (null block excluded).
        The refs scan stays the AUDIT surface (drain/leak tests);
        the hot path publishes the incremental `_used` twin, which
        alloc (every 0->1) and release (every 1->0) keep exact."""
        return int((self._refs[1:] > 0).sum())

    def occupancy(self) -> float:
        capacity = self.num_blocks - 1
        return self.used_blocks() / capacity if capacity else 0.0

    def tail_free_blocks(self) -> int:
        """Length of the pool's free TAIL — the only span
        maybe_shrink can release (ids are array positions).  The
        tiered-KV interplay surface (ISSUE 17): a demotion wave frees
        device blocks via release_blocks, and this reports how much
        of that release the NEXT idle shrink can actually give back
        (interior frees fragment until their tail neighbours drain
        too)."""
        keep = self.num_blocks
        while keep > 1 and self._refs[keep - 1] == 0:
            keep -= 1
        return self.num_blocks - keep

    def _publish_gauges(self) -> None:
        # alloc/release land here once per pump-path transition: an
        # O(num_blocks) used_blocks() scan per one-block allocation
        # would grow per-round host work with pool capacity
        capacity = self.num_blocks - 1
        self._gauge_total.set(capacity)
        self._gauge_used.set(self._used)
        self._gauge_occupancy.set(
            self._used / capacity if capacity else 0.0)

    # -- block content movement --------------------------------------------
    def copy_blocks(self, src_ids, dst_ids) -> int:
        """Device-copy block contents src -> dst (copy-on-extend): one
        batched program per call.  Returns the bytes copied — the
        number the paged A/B is meant to shrink to at most one partial
        block per shared write."""
        if not src_ids:
            return 0
        src = jnp.asarray(list(src_ids), jnp.int32)
        dst = jnp.asarray(list(dst_ids), jnp.int32)
        copy = _copy_blocks_fn(self.config, self.kv_int8)
        self.k_pools = copy(self.k_pools, src, dst)
        self.v_pools = copy(self.v_pools, src, dst)
        copied = len(src_ids) * self.block_nbytes
        self.stats["cow_copies"] += len(src_ids)
        self.stats["cow_copy_bytes"] += copied
        return copied

    def write_blocks(self, ids, k_layers, v_layers) -> None:
        """Install host block rows directly into pool blocks (the
        disaggregated KV landing, ISSUE 15): `k_layers`/`v_layers` are
        per-layer stacks covering len(ids) blocks —
        [M, H, B, D] arrays or {"q" [M, H, B, D], "s" [M, H, B]} dicts
        — written as ONE scatter per layer, so a shipped chain costs
        one device transfer per layer instead of one per leaf."""
        if not ids:
            return
        dst = jnp.asarray(list(ids), jnp.int32)
        write = _write_blocks_fn(self.config, self.kv_int8)
        as_device = _as_device_rows
        self.k_pools = write(self.k_pools, dst,
                             [as_device(rows) for rows in k_layers])
        self.v_pools = write(self.v_pools, dst,
                             [as_device(rows) for rows in v_layers])
        self.stats["install_blocks"] += len(ids)
        self.stats["install_bytes"] += len(ids) * self.block_nbytes

    def block_rows(self, block_id: int) -> tuple:
        """(per-layer K leaves, per-layer V leaves) for one block —
        device-side slice views in the pool's storage layout (the read
        behind shipping a pool-resident cache block over the wire)."""
        return ([L.slice_paged_block(pool, block_id)
                 for pool in self.k_pools],
                [L.slice_paged_block(pool, block_id)
                 for pool in self.v_pools])


def _as_device_rows(rows):
    if isinstance(rows, dict):
        return {"q": jnp.asarray(rows["q"]),
                "s": jnp.asarray(rows["s"])}
    return jnp.asarray(rows)


@functools.lru_cache(maxsize=32)
def _pool_grow_fn(old_n: int, new_n: int):
    pad = new_n - old_n

    def grow_leaf(leaf):
        spec = [(0, 0)] * leaf.ndim
        spec[0] = (0, pad)
        return jnp.pad(leaf, spec)

    def grow(pools):
        return [jax.tree.map(grow_leaf, pool) for pool in pools]

    return jax.jit(grow, donate_argnums=(0,))


@functools.lru_cache(maxsize=32)
def _pool_shrink_fn(new_n: int):
    def shrink(pools):
        return [jax.tree.map(lambda leaf: leaf[:new_n], pool)
                for pool in pools]

    return jax.jit(shrink, donate_argnums=(0,))


@functools.lru_cache(maxsize=8)
def _copy_blocks_fn(config: LlamaConfig, kv_int8: bool):
    def copy(pools, src, dst):
        def copy_leaf(leaf):
            return leaf.at[dst].set(jnp.take(leaf, src, axis=0),
                                    mode="drop")
        return [jax.tree.map(copy_leaf, pool) for pool in pools]

    return jax.jit(copy, donate_argnums=(0,))


@functools.lru_cache(maxsize=8)
def _write_blocks_fn(config: LlamaConfig, kv_int8: bool):
    def write(pools, dst, rows):
        out = []
        for pool, layer_rows in zip(pools, rows):
            if isinstance(pool, dict):
                out.append({
                    "q": pool["q"].at[dst].set(layer_rows["q"],
                                               mode="drop"),
                    "s": pool["s"].at[dst].set(layer_rows["s"],
                                               mode="drop")})
            else:
                out.append(pool.at[dst].set(layer_rows, mode="drop"))
        return out

    return jax.jit(write, donate_argnums=(0,))


# -- compiled paged programs --------------------------------------------------

def _slice_time(cache, t_cap: int):
    """Slice a gathered slot-major view to exactly t_cap positions: a
    width that ends inside a block (a max_seq that is no multiple of
    the block) gathers whole blocks and trims here."""
    if isinstance(cache, dict):
        return {"q": cache["q"][:, :, :t_cap],
                "s": cache["s"][:, :, :t_cap]}
    return cache[:, :, :t_cap]


def _table_cap(tables, block_tokens: int, t_cap: int):
    """Slice a round table to the blocks covering t_cap — an int32
    table slice, not a KV gather.  The gather path cuts its table with
    it BEFORE gathering; the kernel masks positions against
    entry_lengths natively, so this is the only t_cap handling the
    kernel path needs."""
    return tables[:, :-(-t_cap // block_tokens)]


def _gather_views(pools, tables, t_cap: int) -> list:
    """Slot-major views of positions [0, t_cap) of every slot.  The
    table is cut to the blocks that cover t_cap FIRST, so the gather
    itself moves t_cap positions whatever the table's width (a step
    at half the cap builds half the views); the time slice after it
    only trims a t_cap that ends inside a block."""
    block_tokens = jax.tree_util.tree_leaves(pools)[0].shape[2]
    capped = _table_cap(tables, block_tokens, t_cap)
    return [None if pool is None      # a layer that keeps no such leaf
            else _slice_time(L.gather_paged_kv(pool, capped), t_cap)
            for pool in pools]


# -- pallas kernel attention (ISSUE 16, ISSUE 30) -----------------------------
# kernel=True swaps the gather+shared-body attention for
# ops.paged_attention.paged_decode_attention: the pool leaves and the
# round table go to the kernel directly, so the slot-major [S, H, T, D]
# gather never materializes and each slot's attention reads its own
# live blocks.  The plain step takes it where the decoder chose it
# (ContinuousDecoder.step_kernel: asked for by
# AIKO_DECODE_ATTENTION=paged_kernel, or unasked on a TPU where the
# kernel suits the decoder); the speculative step and the extend only
# where it was asked for.  The gather path stays the bit-parity ORACLE
# — tests prove greedy token identity per (int8 × chunked × spec ×
# block size) combination, and the kernel builders key their lru
# caches on the toggle so both variants coexist in one process
# (chip_smoke.py runs one after the other).

def _kernel_grouped_attention(layer, config: LlamaConfig, x, cos, sin,
                              k_pool, v_pool, tables, k_side, v_side,
                              walk_lengths, lengths, write_index,
                              side_valid):
    """Kernel-path sibling of serving._grouped_block_attention: the
    same QKV projection / rope / side-buffer write, then the fused
    paged kernel instead of the gathered-view einsums.  `side_valid`
    is the caller's per-query mask in its compact [S, W, P] form (the
    kernel broadcasts it over heads and groups) — one kernel serves
    the plain scan (W=1) and the widened speculative verify
    (W=1+k).  `walk_lengths` is how far the kernel reads each slot's
    pool rows: the entry length, or 0 for a slot it is to skip."""
    from .ops.paged_attention import paged_decode_attention
    from .serving import _project_qkv
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
        q_grouped = q.reshape(slots_n, num_kv, group * num_q, head_dim)
        out = paged_decode_attention(q_grouped, k_pool, v_pool, tables,
                                     k_side, v_side, side_valid,
                                     walk_lengths, groups=group)
        out = out.reshape(slots_n, num_heads, num_q,
                          head_dim).astype(x.dtype)
    with jax.named_scope(SCOPE_ATTN_PROJ):
        return (L.linear(layer["attn"]["o"], L._merge_heads(out)),
                k_side, v_side)


def _kernel_attention_block(tables, layer, config: LlamaConfig, x,
                            cos, sin, k_pool, v_pool, k_side, v_side,
                            entry_lengths, lengths, step_index,
                            entry_active=None):
    """Kernel sibling of serving._slot_attention_block — the same side
    mask, in [S, 1, P] form.  A slot that was not active at round entry
    (`entry_active`, all active when None) walks nothing of the pool:
    its token is discarded and its stale length may point anywhere."""
    side_positions = jnp.arange(k_side.shape[2])
    side_valid = ((side_positions[None] <= step_index) &
                  (side_positions[None] <
                   (lengths - entry_lengths + 1)[:, None]))[:, None, :]
    walk_lengths = entry_lengths if entry_active is None \
        else jnp.where(entry_active, entry_lengths, 0)
    return _kernel_grouped_attention(layer, config, x, cos, sin,
                                     k_pool, v_pool, tables, k_side,
                                     v_side, walk_lengths, lengths,
                                     step_index, side_valid)


def _kernel_attention_spec(tables, layer, config: LlamaConfig, x, cos,
                           sin, k_pool, v_pool, k_side, v_side,
                           pos_side, entry_lengths, lengths, base):
    """Kernel sibling of serving._slot_attention_spec: the in-kernel
    speculative verify is just the same kernel at W = 1 + k with the
    pos_side <= q_pos causal mask — no second variant.  Signature
    matches _slot_attention_spec after the leading `tables` partial,
    so serving._spec_scan_body takes it via its attention= seam."""
    width = x.shape[1]
    q_pos = lengths[:, None] + jnp.arange(width)[None]       # [S, w]
    side_valid = pos_side[:, None, :] <= q_pos[:, :, None]   # [S,w,P]
    return _kernel_grouped_attention(layer, config, x, cos, sin,
                                     k_pool, v_pool, tables, k_side,
                                     v_side, entry_lengths, lengths,
                                     base, side_valid)


def _pool_sides(k_pools, v_pools) -> list:
    """The pool sides a model keeps, as the programs below walk them,
    each a list by layer: K and V, or K alone where a layer keeps one
    latent row (v_pools is then the empty list), or K, V and a third
    where v_pools holds two leaves a layer, one leaf after the other."""
    n = len(k_pools)
    return [k_pools] + [v_pools[i:i + n] for i in range(0, len(v_pools), n)]


def _join_sides(pools: list) -> tuple:
    """(k_pools, v_pools) of the sides again, as the pool holds them."""
    return pools[0], [leaf for side in pools[1:] for leaf in side]


def _paged_scatter(pools, tables, positions, live, sides, kv_int8,
                   block_tokens: int):
    """Scatter side-buffer rows into pool blocks at absolute
    `positions` ([S, W]; rows where `live` is False drop).  int8 pools
    quantize the side rows ONCE here, mirroring the dense merge."""
    nb = tables.shape[1]
    num_total = jax.tree_util.tree_leaves(pools[0])[0].shape[0]
    blocks = positions // block_tokens
    offsets = positions % block_tokens
    dest = jnp.take_along_axis(tables, jnp.clip(blocks, 0, nb - 1),
                               axis=1)
    dest = jnp.where(live & (blocks >= 0) & (blocks < nb), dest,
                     num_total)
    out = []
    for pool, side in zip(pools, sides):
        rows = L.quantize_kv_cache(side) if kv_int8 else side
        out.append(L.scatter_paged_rows(pool, dest, offsets, rows))
    return out


def _paged_write_runs(pools, tables, starts, live, sides,
                      block_tokens: int):
    """Write each slot's RUN of side-buffer rows, positions [starts[s],
    starts[s] + W) of its table row, into the pool leaves (`live` [S]:
    slots that are not drop whole).  The step's merge and the extend's
    chunk both write runs.  A leaf takes the whole-block form
    (layers.write_paged_runs) wherever that has fewer scatter windows
    than the row form, by its static shape alone, and the row form
    (_paged_scatter, which also serves the speculative step's sparse
    positions) where it has not.  int8 pools quantize the rows ONCE,
    before either."""
    out = []
    for pool, side in zip(pools, sides):
        if pool is None:               # a layer that keeps no such leaf
            out.append(None)
            continue
        heads, width = side.shape[1], side.shape[2]
        kv_int8 = isinstance(pool, dict)
        # a leaf of one row every few tokens: the run starts at the row
        # that holds the first token, in blocks of fewer rows
        block_rows = jax.tree_util.tree_leaves(pool)[0].shape[2]
        first = starts if block_rows == block_tokens \
            else starts // (block_tokens // block_rows)
        if L.writes_runs_by_blocks(heads, width, block_rows):
            rows = L.quantize_kv_cache(side) if kv_int8 else side
            out.append(L.write_paged_runs(pool, tables, first, rows,
                                          live))
        else:
            positions = first[:, None] + jnp.arange(width)[None]
            out.extend(_paged_scatter([pool], tables, positions,
                                      live[:, None], [side], kv_int8,
                                      block_rows))
    return out


def run_write_form(config, width: int, block_tokens: int) -> str:
    """What a decoder logs of the choice above, for a run of `width`
    rows a slot of this model's first pool side."""
    heads = first_leaf(config)[0]
    if L.writes_runs_by_blocks(heads, width, block_tokens):
        return "%d whole blocks a slot" % L.run_blocks(width, block_tokens)
    return "%d rows a slot" % (width * heads)


def _build_paged_step(config, kernel: bool = False):
    """Paged sibling of serving._build_step's block-KV variant: gather
    the slot-major KV views from the pool (once — the main cache is
    read-only through the scan), run the model's pass over the token
    block (for grouped-query attention the IDENTICAL scan body,
    _slot_attention_block owns the numerics), and merge the round's
    side buffers back by (block, offset) scatter.  t_cap is static:
    the width the views are built and attended at, which the decoder
    picks every round from its ladder of widths to cover the longest
    live context (ContinuousDecoder._attend_width).  `tables` comes at
    its full, constant shape and is cut to t_cap in here, so a change
    of width between two rounds uploads nothing; the merge scatters
    through the whole table.  Any t_cap that covers every scanned
    slot's entry length gives the same tokens: the tail is masked to
    exact zeros.

    kernel=True swaps the gather + shared attention body for the
    fused pallas kernel reading pool blocks through the table
    (_kernel_attention_block): no views are built, a slot that was
    inactive at round entry reads nothing of the pool, and t_cap only
    cuts the table (a decoder whose kernel walks live blocks hands the
    cap, always: one program).  The loop, side buffers and merge are
    unchanged, and the gather path remains the parity oracle.

    What lies between the embedding and the head is the model's
    (config.paged_model(), PagedModel): the loop, the side buffers (one
    a pool side the model keeps: K and V, or one latent row) and the
    merge are written once, here.  A model that counts (`counters`)
    hands its counts back behind the pools."""
    model = config.paged_model()
    cos, sin = model.rope(config)
    attention = model.step_attention(kernel)
    leaves = layer_leaves(config)
    stateful = bool(getattr(config, "slot_state", ()))

    def step(params, tokens, lengths, active, budgets, k_pools,
             v_pools, tables, state=(), *, num_steps, eos, t_cap):
        block_tokens = jax.tree_util.tree_leaves(k_pools)[0].shape[2]
        pools = _pool_sides(k_pools, v_pools)
        if kernel or reads_own_pool(config):
            views = None
            cap_tables = _table_cap(tables, block_tokens, t_cap)
        else:
            cap_tables = None
            # once a round, before the scan: the views are read-only in it
            with jax.named_scope(SCOPE_KV_VIEW):
                views = [_gather_views(side, tables, t_cap)
                         for side in pools]
        entry_lengths = lengths
        entry_active = active
        slots_n = tokens.shape[0]
        # one side buffer a leaf: the round's rows (a leaf of one row
        # every few tokens closes at most that share of them)
        sides = [[None if j >= len(layer) else jnp.zeros(
            (slots_n, layer[j][0], -(-num_steps // layer[j][2]),
             layer[j][1]), config.dtype) for layer in leaves]
                 for j in range(len(pools))]
        counts = jnp.zeros((len(model.counters),), jnp.int32) \
            if model.counters else ()

        def body(carry, step_index):
            tokens, lengths, active, budgets, sides, counts, state = carry
            fresh = [[] for _ in sides]
            after, tallies = [], []

            def attend(i, layer, normed):
                attn_out, rewritten, left, tally = attention(
                    cap_tables, layer, config, normed, cos, sin,
                    [side[i] for side in pools],
                    views and [view[i] for view in views],
                    [side[i] for side in sides], entry_lengths, lengths,
                    step_index, entry_active,
                    state[i] if stateful else (), active)
                for column, side in zip(fresh, rewritten):
                    column.append(side)
                if stateful:
                    # the model leaves the state of a slot that decodes
                    # nothing (`active` False) as it was: the slot may be
                    # in the middle of its prompt's chunks
                    after.append(tuple(
                        new.astype(old.dtype)
                        for new, old in zip(left, state[i])))
                if tally is not None:
                    tallies.append(tally)
                return attn_out

            next_tokens, counted = model.token_block_argmax(
                params, config, tokens[:, None], attend, active)
            next_tokens = next_tokens[:, 0]
            if model.counters:
                counts = counts + sum(tallies, counted)
            next_tokens = jnp.where(active, next_tokens, tokens)
            lengths = jnp.where(active, lengths + 1, lengths)
            budgets = jnp.where(active, budgets - 1, budgets)
            still = active & (budgets > 0) & (next_tokens != eos)
            return ((next_tokens, lengths, still, budgets, fresh,
                     counts, after if stateful else state),
                    (next_tokens, active))

        # a loop that ends with the round and not a scan of num_steps:
        # the decoder runs every round through the ONE program of its
        # longest round (steps_per_sync) and cuts a shorter one by its
        # budgets, so a round length is no program of its own (each
        # cost 2 s of set-up, a width and a length 9 programs).  An
        # iteration with no slot active would change nothing but the
        # side rows of slots that the merge writes as dead cells, so
        # leaving it out leaves the same tokens and the same pool.
        def unfinished(loop):
            index, carry, _ = loop
            return (index < num_steps) & carry[2].any()

        def iterate(loop):
            index, carry, (emitted, emitted_active) = loop
            carry, (next_tokens, was_active) = body(carry, index)
            return (index + 1, carry,
                    (emitted.at[index].set(next_tokens),
                     emitted_active.at[index].set(was_active)))

        _, (tokens, lengths, active, budgets, sides, counts, state), \
            (emitted, emitted_active) = jax.lax.while_loop(
                unfinished, iterate,
                (jnp.int32(0),
                 (tokens, lengths, active, budgets, sides, counts,
                  [tuple(layer) for layer in state] if stateful else ()),
                 (jnp.zeros((num_steps, slots_n), tokens.dtype),
                  jnp.zeros((num_steps, slots_n), bool))))

        # merge: each slot's side rows land at their absolute positions
        # [entry_length, entry_length + num_steps) — rows past a slot's
        # actual take are dead cells in blocks it owns, same invariant
        # as the dense merge's garbage rows.  Slots inactive at round
        # entry drop entirely (their stale lengths point into prompt
        # regions their extends are writing).
        with jax.named_scope(SCOPE_KV_MERGE):
            merged = [_paged_write_runs(side, tables, entry_lengths,
                                        entry_active, rows, block_tokens)
                      for side, rows in zip(pools, sides)]
        k_pools, v_pools = _join_sides(merged)
        return (emitted, emitted_active, tokens, lengths,
                k_pools, v_pools) + ((counts,) if model.counters else ()) \
            + ((state,) if stateful else ())

    return jax.jit(step, static_argnames=("num_steps", "eos", "t_cap"),
                   donate_argnames=("k_pools", "v_pools", "state"))


@functools.lru_cache(maxsize=16)
def _paged_step_for(config, kernel: bool = False):
    """Process-wide builder cache, like serving._step_for.  Keyed on
    the kernel toggle so the pallas variant and the gather oracle
    coexist in one process (parity tests, chip_smoke.py)."""
    return _build_paged_step(config, kernel)


def _build_paged_spec_step(config: LlamaConfig, k_spec: int,
                           ngram: int, kernel: bool = False):
    """Paged sibling of serving._build_spec_step: the drafting /
    widened verify / acceptance scan body is the SAME object
    (serving._spec_scan_body — shared like _slot_attention_spec and
    _token_block_argmax so the numerics cannot drift) over gathered
    pool views; the round's consumed side entries scatter-merge to
    (block, offset) pairs, rejected drafts dropping via their
    _POS_INVALID positions exactly as the dense merge drops them.

    kernel=True routes the scan body's attention seam to the fused
    pallas kernel (_kernel_attention_spec over the pool leaves +
    table) — the verify stays widened INSIDE the one kernel, so spec
    mode needs no second pallas variant."""
    from .serving import _POS_INVALID, _spec_scan_body
    cos, sin = L.rope_frequencies(config.head_dim, config.max_seq_len,
                                  config.rope_theta)
    width = k_spec + 1

    def spec_step(params, tokens, lengths, active, budgets, context,
                  k_pools, v_pools, tables, num_steps, eos, t_cap):
        block_tokens = jax.tree_util.tree_leaves(k_pools)[0].shape[2]
        if kernel:
            k_caches, v_caches = k_pools, v_pools
            attention = functools.partial(
                _kernel_attention_spec,
                _table_cap(tables, block_tokens, t_cap))
        else:
            k_caches = _gather_views(k_pools, tables, t_cap)
            v_caches = _gather_views(v_pools, tables, t_cap)
            attention = None
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
                               entry_lengths, attention=attention)

        (tokens, lengths, active, budgets, context, k_sides, v_sides,
         pos_side), (emitted, emit_mask) = jax.lax.scan(
            body, (tokens, lengths, active, budgets, context, k_sides,
                   v_sides, pos_side), jnp.arange(num_steps))

        live = pos_side < _POS_INVALID
        k_pools = _paged_scatter(k_pools, tables, pos_side, live,
                                 k_sides, isinstance(k_pools[0], dict),
                                 block_tokens)
        v_pools = _paged_scatter(v_pools, tables, pos_side, live,
                                 v_sides, isinstance(v_pools[0], dict),
                                 block_tokens)
        return (emitted, emit_mask, tokens, lengths, context,
                k_pools, v_pools)

    return jax.jit(spec_step,
                   static_argnames=("num_steps", "eos", "t_cap"),
                   donate_argnames=("context", "k_pools", "v_pools"))


# -- step programs compiled ahead of time -------------------------------------
# A decoder compiles a step count at EVERY width of its ladder the first
# time it dispatches that count, and dispatches through the executables:
# a context that grows into the next width in the middle of serving
# meets a program that is already there (a jitted call would compile it
# then, with every slot standing still).  The executables are shared
# process-wide like the jitted builders they come from, by everything a
# jitted call's own cache would tell apart.

_step_programs: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


def placements(args) -> tuple:
    """What a jitted call reads of its arguments beside their shapes
    and dtypes, leaf by leaf: the sharding of a committed array (one
    that a program returned onto a mesh, or that was put on a device);
    an uncommitted one goes wherever the program wants it (None)."""
    return tuple(leaf.sharding if leaf.committed else None
                 for leaf in jax.tree_util.tree_leaves(args))


def compiled_step(step, args: tuple, **static):
    """`step` (a jitted paged step) lowered and compiled for `args` as
    they are: their shapes, dtypes and placements, so a pool that grew
    or state that a tensor-parallel program returned sharded gets an
    executable of its own, as it would from the jitted call.  Nothing
    is allocated: lowering reads the live arrays' types only."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    key = (tuple(sorted(static.items())), treedef,
           tuple((leaf.shape, leaf.dtype) for leaf in leaves),
           placements(leaves))
    programs = _step_programs.setdefault(step, {})
    if key not in programs:
        programs[key] = step.lower(*args, **static).compile()
    return programs[key]


@functools.lru_cache(maxsize=16)
def _paged_spec_step_for(config: LlamaConfig, k_spec: int, ngram: int,
                         kernel: bool = False):
    return _build_paged_spec_step(config, k_spec, ngram, kernel)


@functools.lru_cache(maxsize=64)
def _paged_admit_fn_for(config, bucket: int, width: int,
                        kv_int8: bool, speculative: bool):
    """Paged sibling of serving._admit_fn_for: the SAME stacked prefill
    compute (the model's `prefill`), but the rows it leaves for the
    cache scatter into pool blocks named by each row's table slice
    instead of dense slot rows.  Positions past a prompt's bucket pad
    to the block boundary as dead cells in blocks the slot owns;
    invalid (pad) rows carry out-of-range ids and drop."""
    model = config.paged_model()
    leaves = layer_leaves(config)
    stateful = bool(getattr(config, "slot_state", ()))

    def admit(params, k_pools, v_pools, tokens, lengths, context,
              prompts, true_lens, slots, valid, tables_rows, state=()):
        block_tokens = jax.tree_util.tree_leaves(k_pools)[0].shape[2]
        num_total = jax.tree_util.tree_leaves(k_pools)[0].shape[0]
        pools = _pool_sides(k_pools, v_pools)
        hidden, rows, *after = model.prefill(params, config, prompts,
                                             valid, true_lens)
        with jax.named_scope(SCOPE_HEAD):
            idx = jnp.maximum(true_lens - 1, 0)
            last_hidden = jnp.take_along_axis(
                hidden, idx[:, None, None], axis=1)[:, 0]
            last = L.linear_logits(params["lm_head"], last_hidden) \
                if model.head is None \
                else model.head(params, config, last_hidden)
            firsts = jnp.argmax(last, axis=-1).astype(jnp.int32)
        nbb = tables_rows.shape[1]
        padded_t = nbb * block_tokens
        dest = jnp.where(valid[:, None], tables_rows, num_total)
        pad = padded_t - bucket
        with jax.named_scope(SCOPE_KV_MERGE):
            for i, layer_rows in enumerate(rows):
                if pad:
                    layer_rows = [
                        jnp.pad(side_rows, [(0, 0), (0, 0),
                                            (0, pad // every), (0, 0)])
                        for side_rows, (_, _, every)
                        in zip(layer_rows, leaves[i])]
                if kv_int8:
                    layer_rows = [L.quantize_kv_cache(side_rows)
                                  for side_rows in layer_rows]
                for side, side_rows in zip(pools, layer_rows):
                    side[i] = L.write_paged_blocks(side[i], dest,
                                                   side_rows)
        k_pools, v_pools = _join_sides(pools)
        tokens = tokens.at[slots].set(
            jnp.where(valid, firsts, tokens[slots]))
        lengths = lengths.at[slots].set(
            jnp.where(valid, true_lens, lengths[slots]))
        if speculative:
            context = context.at[slots, :bucket].set(
                jnp.where(valid[:, None], prompts,
                          context[slots][:, :bucket]))
        if stateful:
            # an admit starts from zeros: what the slot held goes
            return (firsts, k_pools, v_pools, tokens, lengths, context,
                    _state_store(state, slots, valid, after[0]))
        return firsts, k_pools, v_pools, tokens, lengths, context

    return jax.jit(
        admit, donate_argnames=("k_pools", "v_pools", "tokens",
                                "lengths", "context", "state"))


@functools.lru_cache(maxsize=64)
def _paged_extend_fn_for(config, chunk_len: int,
                         width: int, kv_int8: bool, speculative: bool,
                         kernel: bool = False):
    """Paged sibling of serving._extend_fn_for: the prefix reads come
    from a gathered pool view (sliced to the dense t_cap so the
    attention shapes — and therefore the greedy numerics — match the
    dense program exactly), and only the chunk's positions scatter
    back.  int8 prefixes dequantize for the attention read and the
    chunk stores quantized, exactly like dense — untouched positions
    are never re-rounded because they are never rewritten at all.

    t_cap here is always the decoder's cap (max_seq), never a width
    of the step's ladder: an extend reads one row a chunk, so a
    narrower view wins nothing and every width would be one more
    program for each (chunk, rows) pair.

    kernel=True reads the prefix through the pallas kernel instead of
    gathering: the chunk's own K/V ride as the kernel's side buffer
    with a causal triangle mask (the chunk must NOT round-trip through
    the pool before attention — the oracle attends the exact compute-
    dtype rows, then stores quantized), the prefix mask is t < offset
    (positions the pool actually owns; the chunk covers [offset,
    offset + chunk)), and int8 prefixes dequantize INSIDE the kernel
    (fold_scales=False) to match the oracle's dequantize-then-dot
    numerics bit-for-bit.

    The layers are the model's (PagedModel.extend_layer: the paragraphs
    above are grouped-query attention's, _gqa_extend_layer; a latent
    pool is read the expanded way, piece by piece); the embedding, the
    destinations, the scatter of the chunk's rows, the head and the
    slot state are written once, here."""
    model = config.paged_model()
    cos, sin = model.rope(config)
    extend_layer = model.extend_layer(kernel)
    stateful = bool(getattr(config, "slot_state", ()))

    def extend(params, k_pools, v_pools, tokens, lengths, context,
               chunk_tokens, offsets, slots, valid, finish,
               final_idx, tables_rows, state=(), *, t_cap):
        block_tokens = jax.tree_util.tree_leaves(k_pools)[0].shape[2]
        pools = _pool_sides(k_pools, v_pools)
        x = L.embedding(params["embed"],
                        chunk_tokens).astype(config.dtype)
        if model.residual_in is not None:
            x = model.residual_in(config, x)
        if stateful:
            # a prompt's first chunk starts its slot from zeros
            held, after = _state_rows(state, slots, offsets == 0), []
        q_pos = offsets[:, None] + jnp.arange(chunk_len)[None, :]
        ctx = {"offsets": offsets, "q_pos": q_pos, "valid": valid,
               "finish": finish, "final_idx": final_idx,
               "tables_rows": tables_rows, "t_cap": t_cap,
               "block_tokens": block_tokens, "kv_int8": kv_int8}
        prepared = model.extend_prepare(config, chunk_len, kernel, ctx)
        for i, layer in enumerate(params["layers"]):
            x, stores, *more = extend_layer(
                layer, config, x, cos, sin,
                [side[i] for side in pools], ctx, prepared,
                *((held[i],) if stateful else ()))
            if stateful:
                after.append(more[0])
            with jax.named_scope(SCOPE_KV_MERGE):
                # the chunk is a run: positions [offset, offset + chunk)
                # of each valid row, in blocks _copy_on_write made its own
                written = _paged_write_runs(
                    [side[i] for side in pools], tables_rows, offsets,
                    valid, stores, block_tokens)
                for side, leaf in zip(pools, written):
                    side[i] = leaf
        k_pools, v_pools = _join_sides(pools)
        with jax.named_scope(SCOPE_HEAD):
            x = L.rms_norm(params["ln_out"], x) \
                if model.final_norm is None \
                else model.final_norm(params, config, x)
            last_hidden = jnp.take_along_axis(
                x, final_idx[:, None, None], axis=1)[:, 0]
            last = L.linear_logits(params["lm_head"], last_hidden) \
                if model.head is None \
                else model.head(params, config, last_hidden)
            firsts = jnp.argmax(last, axis=-1).astype(jnp.int32)
        apply = valid & finish
        tokens = tokens.at[slots].set(
            jnp.where(apply, firsts, tokens[slots]))
        lengths = lengths.at[slots].set(
            jnp.where(apply, offsets + final_idx + 1,
                      lengths[slots]))
        if speculative:
            ctx_rows = context[slots]
            written = jax.vmap(
                lambda row, blk, off: jax.lax.dynamic_update_slice(
                    row, blk, (off,)))(ctx_rows, chunk_tokens,
                                       offsets)
            context = context.at[slots].set(
                jnp.where(valid[:, None], written, ctx_rows))
        if stateful:
            return (firsts, k_pools, v_pools, tokens, lengths, context,
                    _state_store(state, slots, valid, after))
        return firsts, k_pools, v_pools, tokens, lengths, context

    return jax.jit(
        extend, static_argnames=("t_cap",),
        donate_argnames=("k_pools", "v_pools", "tokens", "lengths",
                         "context", "state"))


# -- grouped-query attention as a PagedModel ----------------------------------
# What models/llama.py's LlamaConfig.paged_model() hands out: the layer
# functions of the K-and-V pool, which lived inside the builders above
# until a second kind of cache came (ISSUE 31).  The operations and
# their order are what they were: the programs lower as they did.

def _gqa_rope(config):
    return L.rope_frequencies(config.head_dim, config.max_seq_len,
                              config.rope_theta)


def _gqa_token_block_argmax(params, config, token_block, attend, live):
    from .serving import _token_block_argmax
    return _token_block_argmax(params, config, token_block, attend), ()


def _gqa_step_attention(kernel: bool):
    from .serving import _slot_attention_block

    def attend(tables, layer, config, x, cos, sin, leaves, views, sides,
               entry_lengths, lengths, step_index, entry_active, state,
               active):
        if kernel:
            attn_out, k_side, v_side = _kernel_attention_block(
                tables, layer, config, x, cos, sin, leaves[0], leaves[1],
                sides[0], sides[1], entry_lengths, lengths, step_index,
                entry_active)
        else:
            attn_out, k_side, v_side = _slot_attention_block(
                layer, config, x, cos, sin, views[0], views[1],
                sides[0], sides[1], entry_lengths, lengths, step_index)
        return attn_out, (k_side, v_side), (), None

    return attend


def _gqa_prefill(params, config, prompts, valid, true_lens):
    from .models.llama import init_llama_caches, llama_hidden
    caches = init_llama_caches(config, prompts.shape[0], prompts.shape[1])
    hidden, caches = llama_hidden(params, config, prompts, caches)
    return hidden, [(cache["k"], cache["v"]) for cache in caches]


def _gqa_extend_prepare(config, chunk_len: int, kernel: bool, ctx):
    t_cap, q_pos = ctx["t_cap"], ctx["q_pos"]
    mask = (jnp.arange(t_cap)[None, None, :] <=
            q_pos[:, :, None])[:, None, None]
    scale = 1.0 / jnp.sqrt(jnp.asarray(config.head_dim,
                                       jnp.float32))
    prepared = {"mask": mask, "scale": scale}
    if kernel:
        prepared["cap_tables"] = _table_cap(
            ctx["tables_rows"], ctx["block_tokens"], t_cap)
        # per-query chunk causality: side position p is visible to
        # chunk query c iff p <= c (both offset-relative)
        prepared["tri"] = jnp.broadcast_to(
            jnp.tril(jnp.ones((chunk_len, chunk_len), bool))[None],
            (q_pos.shape[0], chunk_len, chunk_len))
    return prepared


def _gqa_extend_layer(kernel: bool):
    def write_rows(rows, chunk_kv, offs):
        return jax.vmap(
            lambda row, kv, off: jax.lax.dynamic_update_slice(
                row, kv, (0, off, 0)))(rows, chunk_kv, offs)

    def extend_layer(layer, config, x, cos, sin, leaves, ctx, prepared):
        num_heads, num_kv = config.num_heads, config.num_kv_heads
        group = num_heads // num_kv
        chunk_len = x.shape[1]
        offsets, tables_rows = ctx["offsets"], ctx["tables_rows"]
        t_cap, kv_int8 = ctx["t_cap"], ctx["kv_int8"]
        k_pool, v_pool = leaves
        with jax.named_scope(SCOPE_ATTN_PROJ):
            normed = L.rms_norm(layer["ln_attn"], x)
            q = L._split_heads(L.linear(layer["attn"]["q"], normed),
                               num_heads)
            k = L._split_heads(L.linear(layer["attn"]["k"], normed),
                               num_kv)
            v = L._split_heads(L.linear(layer["attn"]["v"], normed),
                               num_kv)
            q = L.apply_rope(q, cos, sin, offsets)
            k = L.apply_rope(k, cos, sin, offsets)
        if kernel:
            from .ops.paged_attention import \
                paged_decode_attention
            with jax.named_scope(SCOPE_ATTN_CORE):
                q_grouped = q.reshape(q.shape[0], num_kv,
                                      group * chunk_len,
                                      config.head_dim)
                out = paged_decode_attention(
                    q_grouped, k_pool, v_pool, prepared["cap_tables"],
                    k, v, prepared["tri"], offsets, groups=group,
                    fold_scales=False)
                out = out.reshape(out.shape[0], num_heads,
                                  chunk_len,
                                  config.head_dim).astype(x.dtype)
        else:
            with jax.named_scope(SCOPE_KV_VIEW):
                gathered_k = _slice_time(
                    L.gather_paged_kv(k_pool, tables_rows),
                    t_cap)
                gathered_v = _slice_time(
                    L.gather_paged_kv(v_pool, tables_rows),
                    t_cap)
                if kv_int8:
                    k_rows = write_rows(
                        L.dequantize_kv_cache(gathered_k, x.dtype),
                        k, offsets)
                    v_rows = write_rows(
                        L.dequantize_kv_cache(gathered_v, x.dtype),
                        v, offsets)
                else:
                    k_rows = write_rows(gathered_k, k, offsets)
                    v_rows = write_rows(gathered_v, v, offsets)
            with jax.named_scope(SCOPE_ATTN_CORE):
                q_grouped = q.reshape(q.shape[0], num_kv, group,
                                      chunk_len, config.head_dim)
                scores = jnp.einsum(
                    "akgcd,aktd->akgct", q_grouped, k_rows,
                    preferred_element_type=jnp.float32) * \
                    prepared["scale"]
                scores = jnp.where(prepared["mask"], scores, -1e30)
                weights = jax.nn.softmax(
                    scores, axis=-1).astype(v_rows.dtype)
                out = jnp.einsum(
                    "akgct,aktd->akgcd", weights, v_rows,
                    preferred_element_type=jnp.float32)
                out = out.reshape(out.shape[0], num_heads,
                                  chunk_len,
                                  config.head_dim).astype(x.dtype)
        with jax.named_scope(SCOPE_ATTN_PROJ):
            x = x + L.linear(layer["attn"]["o"],
                             L._merge_heads(out))
        with jax.named_scope(SCOPE_MLP):
            x = x + llama_ffn(layer, config,
                              L.rms_norm(layer["ln_mlp"], x))
        return x, (k, v)

    return extend_layer


def _gqa_walks(config, kv_int8: bool, interpret: bool) -> str | None:
    from .ops.paged_attention import walks_live_blocks
    return "kernel" if walks_live_blocks(config.head_dim, kv_int8,
                                         interpret) else None


GQA_PAGED_MODEL = PagedModel(
    rope=_gqa_rope, token_block_argmax=_gqa_token_block_argmax,
    step_attention=_gqa_step_attention, prefill=_gqa_prefill,
    extend_prepare=_gqa_extend_prepare, extend_layer=_gqa_extend_layer,
    walks=_gqa_walks,
    supports=frozenset({
        "dense_cache", "int8_kv", "speculation", "prefix_cache",
        "weight_quant", "tensor_parallel", "kv_wire", "drain"}))


@functools.lru_cache(maxsize=64)
def _paged_ctx_fn_for(t_write: int):
    """Speculative-context seed for a paged prefix-hit admit: the KV
    aliasing is a pure host-side table edit, but the drafter's history
    buffer still needs the cached prompt tokens — the only device write
    a paged hit pays (and only with speculation on)."""

    def seed(context, slot, ctx_tokens):
        return context.at[slot, :t_write].set(ctx_tokens)

    return jax.jit(seed, donate_argnames=("context",))
