# Paged decode attention: a pallas TPU kernel that reads K/V straight
# out of the serving block pool through per-slot block tables — vLLM
# PagedAttention's indirection (Kwon et al., SOSP 2023), TPU-flavored
# (ISSUE 16, ROADMAP item 2; the walk of ISSUE 30; its mask of ISSUE 39).
#
# The XLA paged path (serving_paged._gather_views) must materialize a
# slot-major [S, H, T, D] copy of every slot's blocks once per round
# before the attention einsums can run, as wide as the round's LONGEST
# live context for every slot.  Here nothing slot-major ever exists.
# Two bodies share one set of numerics (_unit_scores, _side_softmax,
# _unit_pv); which one a call takes follows from its pool
# (walks_live_blocks):
#
# THE WALK (_walk_kernel: a native pool whose head is whole lanes, 128
# or a multiple; what the benchmark's cells run).  The pool leaves stay
# in HBM (memory_space=pl.ANY) and the kernel copies a slot's LIVE
# blocks into VMEM itself, so what moves is what each slot holds, not
# what the longest one forces on all.  Grid (S, R): one grid step a
# slot and query-row tile.  The slot's length comes from entry_lengths
# (scalar prefetch): a slot walks ceil(length / chunk) CHUNKS of `c`
# blocks (_CHUNK positions: 16 blocks at kv_block=32) and a slot of
# length 0 walks nothing.  A pool block [Hkv, B, D] is one contiguous
# piece of the leaf's [N, Hkv, B, D] layout, all KV heads at once, so
# a chunk is up to `c` async copies pool.at[tables[s, j*c + i]] ->
# buffer[:, i*B:(i+1)*B], and only the blocks that hold a live
# position are copied at all (walk_positions: the length rounded up to
# whole blocks).  A grid step is ONE sequence of 2n chunk items
# through a two-deep VMEM ring:
#   K_0 .. K_{n-1}   masked scores of chunk j into scores[j] of a VMEM
#                    scratch, and a running row max; the copy of the
#                    next item starts before the current one is awaited
#   (side softmax)   the side-buffer scores join, the whole (main ++
#                    side) row becomes exp(x - max) in place, is summed,
#                    and the accumulator is seeded with the side PV
#   V_0 .. V_{n-1}   (scores[j] / sum) @ V_j into the f32 accumulator;
#                    the last item starts K_0 of the NEXT grid step that
#                    walks anything, so only the kernel's first copy is
#                    exposed (2n items: every step starts on ring slot 0)
# Positions past the slot's length are masked to -1e30 and the V rows
# past it are zeroed in the buffer (the rest of a last live block, and
# what an earlier chunk left behind it): no dead cell reaches a result,
# whatever it holds.
#
# A SPARSE SELECTION (ISSUE 39: a model whose indexer chooses, token by
# token, which positions a query attends) rides the walk as a MASK:
# `chosen` [S, positions], the same for every head and query row of a
# slot, an int32 VMEM operand [S, units, unit] of which the K item of
# chunk j reads row j and masks `pos < length` AND chosen to -1e30.  The
# slot still walks ALL its live blocks, and the V pass needs nothing (a
# masked score's weight is an exact zero and its V row is a live, finite
# one).  That reads twelve times what a 2,048-of-24k selection attends
# and is still the faster form up to some 50k live positions a slot:
# a position is 1 KB a leaf at 4 heads of 128 and streams at the
# memory's speed, where a gather of the chosen single rows pays a row's
# latency each (PERF.md §6, PR 39).  Without `chosen` the operand does
# not exist and the module is the one it was.  The walk only: the table
# body refuses a mask by name.
#
# A LATENT pool (ISSUE 31: multi-head latent attention, absorbed) has
# ONE leaf, [N, 1, B, lanes]: one shared row a token, and V is the
# leading lanes of the row that K is.  The walk takes it as it is
# (v_pool=None): the V items copy the same blocks again and the dots
# read the buffer's leading v_side.shape[-1] lanes, so the ring, the
# chunking and the two-pass softmax are the ones above, at one "KV
# head" and 64 query rows a slot.  The row has to be whole lanes (a
# 576-lane row is refused like a head of 64): the pool pads it to 640.
#
# THE TABLE BODY (_table_kernel: a head of 64, every int8 pool).
# Mosaic slices a block out of an HBM operand only where the operand's
# minor axis is whole lanes: it pads the memref of a [.., B, 64] pool
# or of an int8 pool's [N, Hkv, B] scales to 128 and then refuses the
# slice ("Slice shape along dimension 3 must be aligned to tiling
# (128)"), so those pools cannot be walked by hand.  They keep PR 21's
# body: the block table rides the grid as a scalar-prefetch operand
# and the pallas pipeline fetches one pool block a grid step.  Grid
# (S, R, 2, nb): phase 0 walks K blocks tables[s, j] into scores[j],
# its last step is the side softmax, phase 1 walks V blocks.  The
# inactive operand's index map parks on an unchanged block index (K on
# tables[s, nb-1] through phase 1, V on tables[s, 0] through phase 0),
# so the pipeline skips those re-fetches.  It walks ALL nb entries
# whatever is live, one 32-token block a step: 21.0 ms for the 16
# calls of a 7B decode step where the walk takes 1.2 (v5e, PERF.md §6,
# PR 30), which is why a decoder that was told nothing takes the
# kernel only where the walk serves it (ContinuousDecoder's
# constructor).
#
# The unit index (a chunk of the walk, a block of the table body) sits
# on the scores scratch's LEADING axis because mosaic only takes a
# dynamic index on the lane axis when it is provably a multiple of
# 128.  R tiles the G*W query rows (_row_tile) so the scratch fits
# VMEM when a chunked-prefill extend brings G*chunk of them; a decode
# round has R = 1, and a row tile walks its slot again (K and V stream
# once per row tile).
#
# Numerics discipline (the parity contract with the XLA oracle):
# every elementwise op matches serving._grouped_block_attention /
# serving_paged's extend body — f32 QK dots * scale, int8 scale
# treatment, -1e30 masking, jax.nn.softmax's exp(x - max) / sum over
# the full row (exact two-pass: the whole row of scores stays in VMEM,
# 512 KB at 8 heads x 2,048 positions), weight casts before the PV
# dots.  Only the ASSOCIATION of the sums differs (unit by unit vs one
# full-T contraction), which is why the acceptance criterion is greedy
# TOKEN identity, proven per combination in tests/test_paged_kv.py
# (interpret mode on CPU, float32: the walk for native pools, the
# table body for int8).
#
# int8 pools ({"q" i8, "s" f32}) fuse their dequant into the dots two
# ways, each matching its oracle:
#   fold_scales=True   (decode/spec steps) — int8 values stay the dot
#       operand, per-position scales fold into scores (K) and weights
#       (V), the serving._kv_planes discipline
#   fold_scales=False  (chunked-prefill extend) — blocks dequantize in
#       VMEM exactly like layers.dequantize_kv_cache before the dots,
#       because the extend oracle attends dequantized rows
#
# Validated where: tests/test_chip_compile.py compiles both bodies for
# a described v5e at the serving geometries (Llama-1B and Mistral-7B
# heads, kv_block 32, bf16 and int8, fold on and off, decode /
# speculative / extend widths) and the whole 7B step around the walk;
# chip_smoke.py runs both on a v5e chip against the gather path,
# standalone and inside ContinuousDecoder; four of the benchmark's cells
# run the walk in every round (PERF.md §5 has its times), one of them
# with a mask (models/sparse_gqa.py: tests/test_sparse_gqa.py holds it
# to a plain softmax over the chosen rows, tests/test_paged_kv.py the
# mask alone).  In bf16 on
# the chip kernel and gather path differ by rounding, so greedy tokens
# flip at near-ties there (CHANGES.md, PR 21).

from __future__ import annotations

import functools

__all__ = ["paged_decode_attention", "walk_positions",
           "walks_live_blocks"]

# positions a chunk of the walk covers (a whole number of pool blocks,
# at least one): sixteen 64 KB copies a chunk at kv_block=32, head 128.
# Measured alone on a v5e at the 7B cell's geometry, 16 calls a step
# (PERF.md §6, PR 30): 24 ragged slots 1.62 / 1.31 / 1.19 / 1.40 ms at
# 128 / 256 / 512 / 1,024; every slot at the cap 6.88 / 5.18 / 4.54 /
# 4.34.  Short chunks pay their loop more often, a long one computes
# over more masked rows in a slot's last chunk.
_CHUNK = 512


def _chunk_blocks(table_blocks: int, block_tokens: int) -> int:
    return max(1, min(table_blocks, _CHUNK // block_tokens))


def walks_live_blocks(head_dim: int, int8: bool,
                      interpret: bool = False) -> bool:
    """Whether a call at this pool geometry takes the body that copies
    a slot's live blocks itself.  Mosaic slices a block out of an HBM
    operand only where the operand's minor axis is whole lanes (128):
    a head of 64 and an int8 pool's [N, Hkv, B] scales are refused
    ("Slice shape along dimension 3 must be aligned to tiling (128)"),
    so those pools keep the body that follows the table through the
    pipeline.  The interpreter has no lanes."""
    return not int8 and (interpret or head_dim % 128 == 0)


def walk_positions(lengths, block_tokens: int):
    """Positions of the pool the kernel reads for slots of `lengths`
    (numpy or jax, any shape): the blocks that hold a live position,
    whole.  What ContinuousDecoder records as a kernel round's
    attend_width."""
    return -(-lengths // block_tokens) * block_tokens


def _unit_scores(q, k, k_scale, first, length, *, fold: bool,
                 scale: float, chosen=None):
    """Masked f32 scores [Hkv, R, U] of the queries against one unit
    of K rows [Hkv, U, D] that starts at absolute position `first`;
    `chosen` [1, U] int32, where given, is which of the unit's
    positions the slot attends at all."""
    import jax
    import jax.numpy as jnp
    if k_scale is not None and not fold:
        # extend-path numerics: cast both factors then multiply in the
        # compute dtype, layers.dequantize_kv_cache verbatim
        k = k.astype(q.dtype) * k_scale.astype(q.dtype)
    else:
        k = k.astype(q.dtype)
    sc = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale       # [Hkv,R,U]
    if k_scale is not None and fold:
        sc = sc * k_scale
    # absolute position mask — positions past the slot's read-only
    # extent (entry_lengths) are dead cells / null-block zeros / rows
    # of a buffer that no copy wrote
    pos = first + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 2)
    live = pos < length
    if chosen is not None:
        live = live & (chosen[None] != 0)
    return jnp.where(live, sc, -1e30)


def _side_softmax(q, k_side, v_side, valid, row_max, scores, count, *,
                  scale: float):
    """jax.nn.softmax over the whole (main ++ side) row, spelled out
    unit by unit: exp(x - rowmax) / sum(exp(x - rowmax)).  Turns the
    `count` leading units of `scores` into exp(x - max) in place (the
    PV pass reads them back); returns the row sum and the accumulator
    seeded with the side buffer's PV."""
    import jax
    import jax.numpy as jnp
    sc = jax.lax.dot_general(
        q, k_side, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale       # [Hkv,R,P]
    sc = jnp.where(valid[None] != 0, sc, -1e30)
    m = jnp.maximum(row_max, jnp.max(sc, axis=-1, keepdims=True))
    side = jnp.exp(sc - m)

    def unit_exp(i, total):
        e = jnp.exp(scores[i] - m)
        scores[i] = e
        return total + jnp.sum(e, axis=-1, keepdims=True)

    total = jax.lax.fori_loop(
        0, count, unit_exp, jnp.sum(side, axis=-1, keepdims=True))
    seed = jax.lax.dot_general(
        (side / total).astype(v_side.dtype), v_side,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return total, seed


def _unit_pv(e, row_sum, v, v_scale, first, length, dtype, *,
             fold: bool):
    """One unit's share of the output: (exp / sum) @ V, [Hkv, R, D]
    f32.  A dead cell's int8 value is finite; its scale need not be,
    and its weight is an exact zero."""
    import jax
    import jax.numpy as jnp
    w = e / row_sum
    if v_scale is not None:
        pos = first + jax.lax.broadcasted_iota(
            jnp.int32, v_scale.shape, 2 if fold else 1)
        v_scale = jnp.where(pos < length, v_scale, 0.0)
    if v_scale is not None and not fold:
        v = v.astype(dtype) * v_scale.astype(dtype)
    else:
        if v_scale is not None:
            w = w * v_scale
        v = v.astype(dtype)
    return jax.lax.dot_general(
        w.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _leading_lanes(rows, lanes: int):
    """The V rows of a chunk held in the ring: the rows themselves, or
    for a latent pool the leading `lanes` of the rows that K is."""
    return rows if rows.shape[-1] == lanes else rows[:, :, :lanes]


def _walk_kernel(tables_ref, entry_ref, q_ref, k_hbm, v_hbm, k_side_ref,
                 v_side_ref, valid_ref, *refs, scale: float,
                 chunk_blocks: int):
    """The body that walks a slot's live blocks by hand (header).  A
    call with a `chosen` mask has one operand more, before the result."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    *masks, o_ref, ring, sems, scores, acc, chained = refs
    chosen_ref = masks[0] if masks else None
    s, r = pl.program_id(0), pl.program_id(1)
    slots_n, row_tiles = pl.num_programs(0), pl.num_programs(1)
    block_tokens = k_hbm.shape[2]
    c = chunk_blocks
    chunk = c * block_tokens
    length = entry_ref[s]
    n = pl.cdiv(length, chunk)

    def each_live_block(pool_hbm, slot_index, j, at, act):
        """`act` (start, or wait) on the async copy of every block of
        chunk j of slot `slot_index` that holds a live position, into
        ring slot `at`.  Start and wait build the same descriptors
        from the same scalars."""
        live = jnp.minimum(c, pl.cdiv(
            entry_ref[slot_index] - j * chunk, block_tokens))

        def one(i, _):
            rows = pl.ds(pl.multiple_of(i * block_tokens, block_tokens),
                         block_tokens)
            act(pltpu.make_async_copy(
                pool_hbm.at[tables_ref[slot_index, j * c + i]],
                ring.at[at, :, rows, :], sems.at[at]))
            return 0

        jax.lax.fori_loop(0, live, one, 0)

    def start(pool_hbm, slot_index, j, at):
        each_live_block(pool_hbm, slot_index, j, at,
                        lambda copy: copy.start())

    def wait(pool_hbm, j, at):
        each_live_block(pool_hbm, s, j, at, lambda copy: copy.wait())

    @pl.when((s == 0) & (r == 0))
    def _():
        chained[0] = 0

    # the first grid step that walks anything starts its own K_0; every
    # later one finds it started by the step before (on ring slot 0: a
    # step is an even number of items)
    @pl.when((n > 0) & (chained[0] == 0))
    def _():
        start(k_hbm, s, 0, 0)

    q = q_ref[0]                                      # [Hkv, R, D]

    def k_item(j, row_max):
        at = j % 2

        @pl.when(j + 1 < n)
        def _():
            start(k_hbm, s, j + 1, 1 - at)

        @pl.when(j + 1 == n)
        def _():
            start(v_hbm, s, 0, 1 - at)

        wait(k_hbm, j, at)
        sc = _unit_scores(
            q, ring[at], None, j * chunk, length, fold=True, scale=scale,
            chosen=None if chosen_ref is None else chosen_ref[0, pl.ds(j, 1)])
        scores[j] = sc               # leading-axis index: see the header
        return jnp.maximum(row_max, jnp.max(sc, axis=-1, keepdims=True))

    row_max = jax.lax.fori_loop(
        0, n, k_item, jnp.full(q.shape[:2] + (1,), -1e30, jnp.float32))
    row_sum, seed = _side_softmax(
        q, k_side_ref[0], v_side_ref[0], valid_ref[0], row_max, scores,
        n, scale=scale)
    acc[...] = seed

    def next_walk():
        """The slot of the next grid step that walks anything
        (slots_n: none does)."""
        def empty(t):
            return (t < slots_n) & \
                (entry_ref[jnp.minimum(t, slots_n - 1)] == 0)
        after = jax.lax.while_loop(empty, lambda t: t + 1, s + 1)
        return jnp.where(r + 1 < row_tiles, s, after)

    def v_item(j, _):
        at = (n + j) % 2

        @pl.when(j + 1 < n)
        def _():
            start(v_hbm, s, j + 1, 1 - at)

        @pl.when(j + 1 == n)
        def _():
            following = next_walk()
            chained[0] = (following < slots_n).astype(jnp.int32)

            @pl.when(following < slots_n)
            def _():
                start(k_hbm, following, 0, 1 - at)

        wait(v_hbm, j, at)

        # the rows past the slot's length hold a last block's dead
        # cells and whatever an earlier chunk left in the buffer: their
        # weights are exact zeros, and 0 * NaN is not.  (Zeroing only
        # the dead rows, a tile a time, measured no faster on the chip.)
        @pl.when((j + 1) * chunk > length)
        def _():
            held = ring[at]
            pos = j * chunk + jax.lax.broadcasted_iota(
                jnp.int32, held.shape, 1)
            ring[at] = jnp.where(pos < length, held,
                                 jnp.zeros_like(held))

        acc[...] += _unit_pv(scores[j], row_sum,
                             _leading_lanes(ring[at], acc.shape[-1]),
                             None, j * chunk, length, q.dtype, fold=True)
        return 0

    jax.lax.fori_loop(0, n, v_item, 0)
    o_ref[0] = acc[...]


def _table_kernel(*refs, int8: bool, fold: bool, block_tokens: int,
                  scale: float):
    """The body that lets the pallas pipeline follow the table, one
    block a grid step over every entry (header)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if int8:
        (tables_ref, entry_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
         k_side_ref, v_side_ref, valid_ref, o_ref, scores, row_max,
         row_sum, acc) = refs
    else:
        (tables_ref, entry_ref, q_ref, kq_ref, vq_ref,
         k_side_ref, v_side_ref, valid_ref, o_ref, scores, row_max,
         row_sum, acc) = refs
        ks_ref = vs_ref = None
    del tables_ref                     # consumed by the index maps
    s = pl.program_id(0)
    phase = pl.program_id(2)
    j = pl.program_id(3)
    nb = pl.num_programs(3)
    length = entry_ref[s]

    @pl.when(phase == 0)
    def _block_scores():
        sc = _unit_scores(q_ref[0], kq_ref[0],
                          ks_ref[0] if int8 else None, j * block_tokens,
                          length, fold=fold, scale=scale)
        scores[j] = sc         # leading-axis index: see the header
        block_max = jnp.max(sc, axis=-1, keepdims=True)
        # at j == 0 the scratch still holds the previous tile's max
        row_max[...] = jnp.where(
            j == 0, block_max, jnp.maximum(row_max[...], block_max))

    @pl.when((phase == 0) & (j == nb - 1))
    def _side_softmax_step():
        row_sum[...], acc[...] = _side_softmax(
            q_ref[0], k_side_ref[0], v_side_ref[0], valid_ref[0],
            row_max[...], scores, nb, scale=scale)

    @pl.when(phase == 1)
    def _block_pv():
        acc[...] += _unit_pv(scores[j], row_sum[...], vq_ref[0],
                             vs_ref[0] if int8 else None,
                             j * block_tokens, length, q_ref.dtype,
                             fold=fold)

    @pl.when((phase == 1) & (j == nb - 1))
    def _finish():
        o_ref[0] = acc[...]


# The scores scratch holds one query-row tile's whole row in VMEM, a
# unit (a chunk of the walk, or a block of the table body) on its
# leading axis.  Lanes pad to 128 and sublanes to 8; rows are
# independent, so tile them to stay well inside the 16 MiB a v5e
# kernel may scope by default.
_SCORES_VMEM_BUDGET = 8 << 20
# the walk's ring, an extend's side blocks and the scores together pass
# that default by a little (16.25 MiB at a 512-token chunk, head 128):
# it asks for its own limit, a quarter of a v5e core's 128 MiB
_WALK_VMEM_LIMIT = 32 << 20


def _row_tile(gw: int, units: int, num_kv: int, unit_tokens: int) -> int:
    per_row = units * num_kv * (-(-unit_tokens // 128) * 128) * 4
    for rows in range(gw, 0, -1):
        if gw % rows or (rows != gw and rows % 8):
            continue
        if (-(-rows // 8) * 8) * per_row <= _SCORES_VMEM_BUDGET:
            return rows
    raise ValueError(
        f"paged_decode_attention: {units} score units of {unit_tokens} "
        f"tokens x {num_kv} kv heads need more than "
        f"{_SCORES_VMEM_BUDGET >> 20} MiB of VMEM for one 8-row score "
        f"tile; use a larger kv_block or a shorter max_seq")


def paged_decode_attention(q, k_pool, v_pool, tables, k_side, v_side,
                           side_valid, entry_lengths, *, groups: int,
                           scale: float | None = None,
                           fold_scales: bool = True,
                           interpret: bool | None = None, chosen=None):
    """Block-table-native decode attention over a paged KV pool.

    q:            [S, Hkv, G*W, D] grouped queries (G-major: the
                  (group, width) axes flattened)
    k/v_pool:     per-layer pool leaf [N, Hkv, B, D], or the int8
                  serving dict {"q" i8 [N, Hkv, B, D], "s" f32
                  [N, Hkv, B]}.  v_pool=None is a LATENT pool: V is
                  the leading v_side.shape[-1] lanes of the K rows
                  (the walk only: a native leaf of whole lanes)
    tables:       [S, nb] int32 block ids (nb * B >= the slot's
                  readable extent; the walk follows only the entries
                  of blocks that hold a live position, the table body
                  all of them, so unfilled ones point at the null
                  block)
    k/v_side:     [S, Hkv, P, D] this round's side buffers in the
                  compute dtype
    side_valid:   [S, W, P] bool — per-query side visibility, computed
                  by the caller (this is what widens the speculative
                  verify into the same kernel: W = 1 + k and the
                  pos_side <= q_pos mask arrive here unchanged)
    entry_lengths: [S] int32 read-only main extent per slot; 0 reads
                  nothing of the pool into the result
    chosen:       [S, positions] bool or int32, or None: which of the
                  pool's positions a slot attends, the same for every
                  head and query row of it (a sparse selection).  The
                  slot still walks every live block; a position that
                  was not chosen is masked as one past the length is.
                  The walk only: the table body refuses it

    Returns [S, Hkv, G*W, D] f32.  interpret=None auto-selects:
    compiled pallas on TPU, interpreter mode elsewhere (CPU tests run
    the same kernel code path)."""
    import jax
    import numpy as np

    from ..models.layers import paged_pool_planes

    head_dim = q.shape[3]
    block_tokens = paged_pool_planes(k_pool)[0].shape[2]
    if scale is None:
        # f32(1)/sqrt(f32(d)) — the exact value the oracle's traced
        # 1/jnp.sqrt computes, so the score scaling cannot drift a ulp
        scale = float(np.float32(1.0) / np.sqrt(np.float32(head_dim)))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # ONE jitted function for every call of a program: a decode step
    # calls this once a layer at one set of shapes, and a jitted callee
    # is traced and lowered to its mosaic module once, not sixteen
    # times (13.7 s of a warm set-up for the 7B step, against 4.9 s
    # for the three gather programs it replaces: PERF.md §6, PR 30).
    # Everything that shapes the kernel is a static argument, so a
    # chunk patched by a test is another entry of jit's cache
    return _attend_jit()(
        q, k_pool, v_pool, tables, k_side, v_side, side_valid,
        entry_lengths, chosen, groups=groups, scale=scale,
        fold_scales=fold_scales, interpret=interpret,
        chunk_blocks=_chunk_blocks(tables.shape[1], block_tokens))


@functools.cache
def _attend_jit():
    import jax
    return jax.jit(_attend, static_argnames=(
        "groups", "scale", "fold_scales", "interpret", "chunk_blocks"))


def _attend(q, k_pool, v_pool, tables, k_side, v_side, side_valid,
            entry_lengths, chosen, *, groups: int, scale: float,
            fold_scales: bool, interpret: bool, chunk_blocks: int):
    """paged_decode_attention with every default resolved."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..models.layers import paged_pool_planes

    kq, k_scales = paged_pool_planes(k_pool)
    vq, v_scales = paged_pool_planes(k_pool if v_pool is None else v_pool)
    int8 = k_scales is not None
    slots_n, num_kv, gw, head_dim = q.shape
    v_dim = v_side.shape[3]
    nb = tables.shape[1]
    block_tokens = kq.shape[2]
    side_len = k_side.shape[2]
    walk = walks_live_blocks(head_dim, int8, interpret)
    if v_dim != head_dim and not (walk and v_pool is None):
        raise ValueError(
            "paged_decode_attention: values narrower than keys are a "
            "latent pool's (v_pool=None), which only the walk reads: a "
            "native leaf whose rows are whole lanes")
    if chosen is not None and not walk:
        raise ValueError(
            "paged_decode_attention: `chosen` is a mask over the blocks "
            "that the walk copies itself; the table body (a head of 64, "
            "an int8 pool) takes none")
    c = chunk_blocks if walk else 1
    unit = c * block_tokens
    units = -(-nb // c)
    rows = _row_tile(gw, units, num_kv, unit)
    # the [S, W, P] visibility mask repeats per query group (rows are
    # G-major) and travels as int32: mosaic takes neither a bool VMEM
    # operand nor the in-kernel broadcast+reshape that built this
    valid_rows = jnp.tile(side_valid.astype(jnp.int32), (1, groups, 1))
    # a length past the table reads what the table covers (the oracle
    # masks against the view it gathered through the same table)
    entries = jnp.minimum(entry_lengths.astype(jnp.int32),
                          nb * block_tokens)
    side_block = (1, num_kv, side_len, head_dim)
    row_block = (1, num_kv, rows, head_dim)
    v_side_block = (1, num_kv, side_len, v_dim)
    out_block = (1, num_kv, rows, v_dim)
    out_shape = jax.ShapeDtypeStruct((slots_n, num_kv, gw, v_dim),
                                     jnp.float32)
    state = [pltpu.VMEM((units, num_kv, rows, unit), jnp.float32)]

    if walk:
        def q_map(s, r, tables, entries):
            return (s, 0, r, 0)

        def side_map(s, r, tables, entries):
            return (s, 0, 0, 0)

        def valid_map(s, r, tables, entries):
            return (s, r, 0)

        def mask_map(s, r, tables, entries):
            return (s, 0, 0)

        in_pool = pl.BlockSpec(memory_space=pl.ANY)
        masks, mask_specs = [], []
        if chosen is not None:
            if chosen.shape[1] > units * unit:
                raise ValueError(
                    f"paged_decode_attention: `chosen` names "
                    f"{chosen.shape[1]} positions a slot, the table "
                    f"reaches {nb * block_tokens}")
            # a unit's mask is a row of the operand: int32 as the side
            # mask is, indexed by the unit as the scores scratch is
            masks = [jnp.pad(chosen.astype(jnp.int32), (
                (0, 0), (0, units * unit - chosen.shape[1]))).reshape(
                    slots_n, units, unit)]
            mask_specs = [pl.BlockSpec((1, units, unit), mask_map)]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots_n, gw // rows),
            in_specs=[pl.BlockSpec(row_block, q_map), in_pool, in_pool,
                      pl.BlockSpec(side_block, side_map),
                      pl.BlockSpec(v_side_block, side_map),
                      pl.BlockSpec((1, rows, side_len), valid_map),
                      *mask_specs],
            out_specs=pl.BlockSpec(out_block, q_map),
            scratch_shapes=[
                # the two-deep ring that K and V chunks pass through
                pltpu.VMEM((2, num_kv, unit, head_dim), kq.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                *state,
                pltpu.VMEM((num_kv, rows, v_dim), jnp.float32),
                pltpu.SMEM((1,), jnp.int32)])
        return pl.pallas_call(
            functools.partial(_walk_kernel, scale=scale, chunk_blocks=c),
            grid_spec=grid_spec, out_shape=out_shape,
            # a step hands the next one a copy in flight: the grid runs
            # in order on one core
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_WALK_VMEM_LIMIT),
            interpret=interpret,
        )(tables.astype(jnp.int32), entries, q, kq, vq, k_side, v_side,
          valid_rows, *masks)

    def q_map(s, r, p, j, tables, entries):
        return (s, 0, r, 0)

    def side_map(s, r, p, j, tables, entries):
        return (s, 0, 0, 0)

    def valid_map(s, r, p, j, tables, entries):
        return (s, r, 0)

    def k_map(s, r, p, j, tables, entries):
        # phase 0 walks the K blocks; phase 1 parks on the last one so
        # consecutive grid steps keep an unchanged block index and the
        # pipeline skips the re-fetch
        return (jax.lax.select(p == 0, tables[s, j],
                               tables[s, nb - 1]), 0, 0, 0)

    def v_map(s, r, p, j, tables, entries):
        # mirror image: V parks on block 0 through phase 0
        return (jax.lax.select(p == 0, tables[s, 0],
                               tables[s, j]), 0, 0, 0)

    # per-position scales ride in the layout their product broadcasts
    # from — [.., 1, B] against scores/weights (fold), [.., B, 1]
    # against the K/V rows (dequantize) — a free reshape out here, a
    # lane<->sublane relayout if done in the kernel
    scale_shape = (1, num_kv, 1, block_tokens) if fold_scales \
        else (1, num_kv, block_tokens, 1)
    block_kv = (1, num_kv, block_tokens, head_dim)
    in_specs = [pl.BlockSpec(row_block, q_map),
                pl.BlockSpec(block_kv, k_map)]
    operands = [q, kq]
    if int8:
        in_specs.append(pl.BlockSpec(scale_shape, k_map))
        operands.append(k_scales.reshape((-1,) + scale_shape[1:]))
    in_specs.append(pl.BlockSpec(block_kv, v_map))
    operands.append(vq)
    if int8:
        in_specs.append(pl.BlockSpec(scale_shape, v_map))
        operands.append(v_scales.reshape((-1,) + scale_shape[1:]))
    in_specs += [pl.BlockSpec(side_block, side_map),
                 pl.BlockSpec(side_block, side_map),
                 pl.BlockSpec((1, rows, side_len), valid_map)]
    operands += [k_side, v_side, valid_rows]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots_n, gw // rows, 2, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(row_block, q_map),
        scratch_shapes=[
            *state,
            pltpu.VMEM((num_kv, rows, 1), jnp.float32),
            pltpu.VMEM((num_kv, rows, 1), jnp.float32),
            pltpu.VMEM((num_kv, rows, head_dim), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_table_kernel, int8=int8, fold=fold_scales,
                          block_tokens=block_tokens, scale=scale),
        grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
    )(tables.astype(jnp.int32), entries, *operands)
