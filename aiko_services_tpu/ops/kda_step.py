# One token of the gated delta rule (Kimi Delta Attention, arXiv:2510.26692;
# Gated DeltaNet, arXiv:2412.06464) over the state of the slots that DECODE,
# as a pallas TPU kernel (ISSUE 34; a head of unequal sides and one gate a
# head, ISSUE 40: further down).
#
# A KDA layer keeps, for every slot of the decoder, a state S [H, D, D] in
# float32 (4 MB a slot at 64 heads of 128; 134 MB a layer at 32 slots).  A
# decode step changes the state of the slots that decode and of no other:
#
#     S' = diag(exp g) S;   write = beta (v - k^T S');   S <- S' + k write^T
#     o  = q^T S' + (q . k) write                                  (= q^T S)
#
# models/delta_rule.recurrent (hybrid_sparse.kda_recurrent) says this in
# four lines and stays the oracle.  The program XLA makes of it for the chip passes over EVERY
# slot's state three times (two fused reads and a write, a multiply by
# exp(0) and a zero write for a slot that decodes nothing): 2.5 ms of a
# 9.6 ms step where a quarter of the slots decode (PERF.md §6, PR 34).
#
# Here the state stays in HBM (memory_space=pl.ANY), aliased to the result,
# and the kernel moves only what changes.  The ids of the live slots are
# compacted on the device and arrive with their count as scalars (SMEM);
# one loop, bounded by count x tiles, passes a slot's state a TILE of heads
# at a time (`_head_tile`: 2 MB) through a three-deep ring in VMEM:
#
#     start in(i + 1)  ->  wait in(i)  ->  update ring[i % 3] in place  ->
#     start out(i)                     (in(i + 1) first awaits out(i - 2),
#                                       which left the ring slot it fills)
#
# so that every live state is read ONCE and written ONCE and the copies run
# under the arithmetic of the tile before.  A slot that does not decode is
# never addressed: its state keeps its bits and its output row is zeros
# (the output is a VMEM block that the kernel clears first).  With no slot
# live the loop runs zero times.
#
# Inside a tile a head is [D (key, sublanes), D (value, lanes)].  The decay
# and the rank-one update scale ROWS, the two products sum over rows, so
# exp(g), k and q are wanted as COLUMNS [D, 1], broadcast over the lanes,
# and they arrive as rows [H, D].  A [.., D, 1] operand would pad each
# column to a tile as large as the state, and columns made by XLA for every
# slot cost three transposes and a 4 MB pass a layer (0.05 ms by the
# compiler's own count, a third of what the kernel saves).  So the copies
# bring the tile's rows of g, k and q one under the other into ONE
# [128, D] block of VMEM (3 x ht rows: that bound is `_head_tile`'s), and
# the kernel passes the tile's heads a GROUP of eight at a time: the
# group's rows of the three laid into a second block (exp of the g rows),
# that block transposed once (the XLU, 16 vregs), a head's column read at
# a static lane: lane j exp g of the group's head j, 8 + j k, 16 + j q.
# Only a group's eight heads are written out in the body: a loop passes
# the tile's groups through it (`_GROUP`).  v stays rows; beta and q . k
# are scalars in SMEM.
#
# The arithmetic is float32 on the VPU throughout (multiplies and a sum
# over the key axis; no matrix unit, so no bfloat16 pass): what differs
# from the oracle is the order of the 128-term sums.
#
# A head need not be square: S is [Dk (key), Dv (value)], the vectors g, k
# and q have Dk lanes, v and o have Dv (ISSUE 40).  On the chip both sides
# are whole lanes and a tile's heads whole sublanes (`moves_live_states`).
#
# ONE GATE A HEAD (Gated DeltaNet, arXiv:2412.06464; ISSUE 40: 30 heads of
# [96, 192]) takes a SIBLING body, `_head_kernel`, and not the one above,
# for two reasons that are the geometry's.  A value side of 192 is no whole
# number of lanes, so a head [96, 192] cannot be a tile of its own: the
# state lies as [S, Dk, H x Dv], the heads SIDE BY SIDE on the lanes (30 x
# 192 = 5,760 = 45 x 128: nothing padded, where [H, 96, 256] would pad a
# third of every byte the step moves), and a head's boundary falls inside a
# vector of 128 lanes.  And the decay is one number a head: there are no
# [H, Dk] rows of g to lay beside k and q, exp(g), beta and q . k are
# scalars in SMEM, spread over their head's lanes by a select.  What is
# shared is everything around the arithmetic: the compaction of the live
# slots' ids, the three-deep ring, the order of the copies, the aliasing.
# A slot's whole state is ONE item (2.2 MB at the published widths:
# `_SLOT_BYTES` bounds it), passed a GROUP of heads at a time, the fewest
# whose lanes are whole vectors (two heads of 192: 384 lanes); k and q
# arrive as COLUMNS [Dk, 128] a slot (k at lanes [0, 64), q at [64, 128): a
# transpose XLA makes of [S, H, Dk], 49 KB a slot beside 2.2 MB), a head's
# column read at a static lane and spread over the head's lanes.
#
# THE PLAIN DECAYED RULE (Mamba-2, arXiv:2405.21060; ISSUE 45: 64 heads of
# [128 (state), 64 (head)], B the key and C the query ONE vector for every
# head of a slot) is a static argument of that body, `plain`, and no third
# kernel:
#
#     S <- exp(g) S + k v^T;   o = S^T q
#
# with no `beta (v - S'^T k)` term: the write is v itself, no beta arrives,
# and k, q and q . k are a slot's (one column each, lanes 0 and 64 of the
# same [Dk, 128] block, spread over every lane once an item and not a head
# at a time).
#
# Validated where: tests/test_kda_step.py (interpreter, CPU: mixed, none
# and all slots live, bits of the untouched states, four donated steps in
# a while_loop, both bodies, unequal head sides, the plain rule);
# tests/test_chip_compile.py (each cell's whole step compiled for a described
# v5e: one custom call a recurrent layer, no other operation on a state
# leaf); chip_smoke.py's hybrid, gated_delta and ssm_hybrid phases and the
# cells long_doc_open_loop, gdn_decode_saturated and ssm_chat_open_loop on the
# chip.

from __future__ import annotations

import functools

__all__ = ["kda_live_step", "moves_live_states"]

_LANES = 128
_RING = 3
# the state of a tile of heads: a copy long enough to run at the speed of
# HBM, short enough that the first one in and the last one out, which
# nothing hides, stay a few microseconds
_TILE_BYTES = 2 << 20
# heads whose arithmetic is written out one after the other: the body the
# compiler sees holds this many, and a loop passes a tile's groups through
# it (a sublane tile of rows a vector; every head of a tile written out
# cost the step's first dispatch 2 s of tracing and lowering, PERF.md §6)
_GROUP = 8


# one gate a head: the state of a whole slot is one item of the ring, and
# the ring, v and o of every slot (whole in VMEM: 1.5 MB each at 64 slots
# of 5,760 lanes) have to fit what the call asks of VMEM (128 MiB a core)
_SLOT_BYTES = 4 << 20
_HEAD_VMEM_LIMIT = 48 << 20


def moves_live_states(heads: int, head_dim: int, interpret: bool = False,
                      value_dim: int | None = None,
                      by_head: bool = False) -> bool:
    """Whether a state of `heads` heads [head_dim (key), value_dim (value;
    head_dim where None)] can take the kernel.  A gate a CHANNEL: mosaic
    slices a tile out of an HBM operand only where its minor axis is whole
    lanes (both sides of a head), and lays a tile's rows into VMEM at whole
    sublanes (a tile of a multiple of 8 heads).  A gate a HEAD (`by_head`):
    the heads lie side by side on the lanes, so a slot's `heads x
    value_dim` lanes are whole vectors and so are a group's
    (`_head_group`), the key side whole sublanes, k and q of every head
    fit a vector's halves, and a slot's state is one item of the ring.
    The interpreter has no tiles."""
    value_dim = value_dim or head_dim
    if interpret:
        return True
    if by_head:
        return (heads * value_dim) % _LANES == 0 and head_dim % 8 == 0 \
            and heads <= _LANES // 2 \
            and heads % _head_group(heads, value_dim) == 0 \
            and 4 * heads * head_dim * value_dim <= _SLOT_BYTES
    return head_dim % _LANES == 0 and value_dim % _LANES == 0 and \
        _head_tile(heads, head_dim, value_dim) % 8 == 0


def _head_tile(heads: int, head_dim: int, value_dim: int | None = None) -> int:
    """Heads a tile (a gate a channel): as many as divide `heads`, lay the
    tile's rows of g, k and q one under the other in a block of 128 rows,
    and keep the tile's state at _TILE_BYTES."""
    most = max(1, min(_LANES // 3, _TILE_BYTES // (
        4 * head_dim * (value_dim or head_dim))))
    return max(t for t in range(1, min(heads, most) + 1) if heads % t == 0)


def _head_group(heads: int, value_dim: int) -> int:
    """Heads a group (a gate a head): the fewest whose value lanes, side by
    side, are whole vectors (two heads of 192: 384 lanes), or every head
    where no such few divide `heads` (the interpreter's sizes)."""
    import math
    few = math.lcm(value_dim, _LANES) // value_dim
    return few if heads % few == 0 else heads


def _kernel(count_ref, ids_ref, beta_ref, qk_ref, g_hbm, k_hbm, q_hbm, v_hbm,
            state_hbm, o_ref, state_out, ring, rows, stage, cols, values,
            arrived, left, *, head_tile: int):
    """The body (header).  `state_out` is `state_hbm`'s own buffer."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads = state_hbm.shape[1]
    ht = head_tile
    hg = _GROUP if ht % _GROUP == 0 else ht
    tiles = heads // ht
    items = count_ref[0] * tiles

    def where(item):
        slot = ids_ref[item // tiles]
        return slot, pl.multiple_of((item % tiles) * ht, ht)

    def arriving(item, at):
        slot, first = where(item)
        tile = pl.ds(first, ht)
        return [pltpu.make_async_copy(state_hbm.at[slot, tile], ring.at[at],
                                      arrived.at[at]),
                pltpu.make_async_copy(v_hbm.at[slot, tile], values.at[at],
                                      arrived.at[at])] + [
            pltpu.make_async_copy(vector.at[slot, tile],
                                  rows.at[at, pl.ds(n * ht, ht)],
                                  arrived.at[at])
            for n, vector in enumerate((g_hbm, k_hbm, q_hbm))]

    def leaving(item, at):
        slot, first = where(item)
        return pltpu.make_async_copy(
            ring.at[at], state_out.at[slot, pl.ds(first, ht)], left.at[at])

    # a slot that decodes nothing reads zeros, whatever VMEM held
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def update(item, at):
        slot, first = where(item)

        def group(n, _):
            # the rows of g, k, q of the group's heads one under the other
            # (what VMEM held below them is never read), exp of the g
            # rows, one transpose: a head's columns at static lanes
            base = pl.multiple_of(n * hg, hg)
            stage[pl.ds(0, hg), :] = jnp.exp(rows[at, pl.ds(base, hg), :])
            for m in (1, 2):
                stage[pl.ds(m * hg, hg), :] = \
                    rows[at, pl.ds(m * ht + base, hg), :]
            cols[...] = stage[...].T
            for j in range(hg):
                head = base + j
                scalar = slot * heads + first + head
                decay = cols[:, j:j + 1]                             # [D, 1]
                k = cols[:, hg + j:hg + j + 1]
                q = cols[:, 2 * hg + j:2 * hg + j + 1]
                decayed = ring[at, head] * decay
                seen = jnp.sum(decayed * k, axis=0, keepdims=True)   # [1, D]
                asked = jnp.sum(decayed * q, axis=0, keepdims=True)
                write = beta_ref[scalar] * (
                    values[at, pl.ds(head, 1), :] - seen)
                ring[at, head] = decayed + k * write
                o_ref[slot, pl.ds(first + head, 1), :] = \
                    asked + qk_ref[scalar] * write
            return 0

        jax.lax.fori_loop(0, ht // hg, group, 0)

    _through_the_ring(items, arriving, leaving, update)


def _through_the_ring(items, arriving, leaving, update) -> None:
    """`items` items through the three-deep ring, one after the other:
    start in(i + 1) (which first awaits out(i - 2), the ring slot it
    fills), wait in(i), `update(i, ring slot)` in place, start out(i);
    then the last outs are awaited.  `arriving(item, at)` are the copies
    that bring item `item` into ring slot `at`, `leaving(item, at)` the one
    that takes it back."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(items > 0)
    def _():
        for copy in arriving(0, 0):
            copy.start()

    def one(item, _):
        at = jax.lax.rem(item, _RING)
        ahead = jax.lax.rem(item + 1, _RING)

        @pl.when(item + 1 < items)
        def _():
            @pl.when(item + 1 >= _RING)
            def _():
                leaving(item + 1 - _RING, ahead).wait()

            for copy in arriving(item + 1, ahead):
                copy.start()

        for copy in arriving(item, at):
            copy.wait()
        update(item, at)
        leaving(item, at).start()
        return 0

    jax.lax.fori_loop(0, items, one, 0)

    def drain(item, _):
        leaving(item, jax.lax.rem(item, _RING)).wait()
        return 0

    jax.lax.fori_loop(jnp.maximum(items - _RING, 0), items, drain, 0)


def kda_live_step(q, k, v, g, beta, state, active, *,
                  interpret: bool | None = None):
    """One token of the gated delta rule for the slots where `active`:
    q, k [S, H, Dk], v [S, H, Dv] float32, beta [S, H], active [S] bool,
    and by the gate's grain
        g [S, H, Dk] (a channel), state [S, H, Dk, Dv] float32, or
        g [S, H] (a head), state [S, Dk, H x Dv] float32: the heads side
        by side on the lanes (`heads_side_by_side` lays it so)
    -> (o [S, H, Dv], the new state, laid as it came).  `beta` None (a
    gate a head only) is the PLAIN decayed rule, S <- exp(g) S + k v^T,
    o = S^T q, with q and k [S, Dk], one vector for every head of a slot.  Where `active` is
    False the state comes back bit for bit (it is never touched: the
    result IS the argument's buffer, `input_output_aliases`) and o is
    zeros; g and beta of such a slot are not read.  Equals
    models/delta_rule.recurrent on the live slots up to the order of
    float32 sums.  interpret=None: compiled on a TPU, the interpreter
    elsewhere."""
    import jax
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if g.ndim == 2:
        return _head_step_jit()(q, k, v, g, beta, state, active,
                                interpret=interpret)
    # ONE jitted function for every KDA layer of a program: traced and
    # lowered to its mosaic module once (ops.paged_attention._attend_jit).
    # The tile is a static argument (a test that patches _TILE_BYTES gets
    # another entry of jit's cache); _RING and _GROUP are read when the
    # body is traced, so a sweep over them needs a new function each time
    return _step_jit()(q, k, v, g, beta, state, active,
                       interpret=interpret,
                       head_tile=_head_tile(q.shape[1], q.shape[2],
                                            v.shape[2]))


@functools.cache
def _step_jit():
    import jax
    return jax.jit(_step, static_argnames=("interpret", "head_tile"))


def _live_ids(active):
    """(the live slots' ids, ascending, then zeros; their count [1]): a
    handful of compares (no sort, no scatter)."""
    import jax.numpy as jnp
    live = active.astype(jnp.int32)
    order = jnp.arange(active.shape[0], dtype=jnp.int32)
    rank = jnp.cumsum(live) - 1
    ids = jnp.sum(jnp.where(
        (rank[None, :] == order[:, None]) & active[None, :],
        order[None, :], 0), axis=1)
    return ids, live.sum()[None]


def _step(q, k, v, g, beta, state, active, *, interpret: bool,
          head_tile: int):
    """kda_live_step with every default resolved, a gate a channel."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, d = q.shape
    dv = v.shape[2]
    ht = head_tile
    ids, count = _live_ids(active)

    scalars = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out, state = pl.pallas_call(
        functools.partial(_kernel, head_tile=ht),
        out_shape=(jax.ShapeDtypeStruct((slots, heads, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        in_specs=[scalars] * 4 + [in_hbm] * 5,
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM), in_hbm),
        scratch_shapes=[pltpu.VMEM((_RING, ht, d, dv), jnp.float32),
                        pltpu.VMEM((_RING, _LANES, d), jnp.float32),
                        pltpu.VMEM((_LANES, d), jnp.float32),
                        pltpu.VMEM((d, _LANES), jnp.float32),
                        pltpu.VMEM((_RING, ht, dv), jnp.float32),
                        pltpu.SemaphoreType.DMA((_RING,)),
                        pltpu.SemaphoreType.DMA((_RING,))],
        input_output_aliases={8: 1},
        name="kda_live_step",
        interpret=interpret,
    )(count, ids, beta.reshape(-1), (q * k).sum(axis=-1).reshape(-1),
      g, k, q, v, state)
    return out, state


# -- one gate a head: the heads side by side on the lanes ---------------------

def heads_side_by_side(state):
    """[A, H, Dk, Dv] -> [A, Dk, H x Dv]: a state as the kernel of a gate a
    head holds it (`heads_apart` is the way back)."""
    a, heads, dk, dv = state.shape
    return state.transpose(0, 2, 1, 3).reshape(a, dk, heads * dv)


def heads_apart(state, heads: int):
    """[A, Dk, H x Dv] -> [A, H, Dk, Dv]."""
    a, dk, lanes = state.shape
    return state.reshape(a, dk, heads, lanes // heads).transpose(0, 2, 1, 3)


def _head_kernel(count_ref, ids_ref, decay_ref, *refs, heads: int, group: int,
                 plain: bool):
    """The body of a gate a head (header).  `state_out` is `state_hbm`'s
    own buffer; an item is a slot's whole state.  `plain`: the decayed rule
    with no correction, k, q and q . k a slot's (no beta arrives)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    beta_ref = None if plain else refs[0]
    (qk_ref, cols_hbm, v_ref, state_hbm, o_ref, state_out, ring, cols,
     arrived, left) = refs[0 if plain else 1:]
    dk, lanes = state_hbm.shape[1:]
    dv = lanes // heads
    wide = group * dv
    items = count_ref[0]

    def arriving(item, at):
        slot = ids_ref[item]
        return [pltpu.make_async_copy(state_hbm.at[slot], ring.at[at],
                                      arrived.at[at]),
                pltpu.make_async_copy(cols_hbm.at[slot], cols.at[at],
                                      arrived.at[at])]

    def leaving(item, at):
        return pltpu.make_async_copy(ring.at[at], state_out.at[ids_ref[item]],
                                     left.at[at])

    # a slot that decodes nothing reads zeros, whatever VMEM held
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, wide), 1)

    def update(item, at):
        slot = ids_ref[item]

        def spread(of):
            """of(j) of the group's head j over that head's lanes."""
            out = of(0)
            for j in range(1, group):
                out = jnp.where(lane >= j * dv, of(j), out)
            return out

        if plain:
            # the slot's one k and one q, for every head of every group
            k, q = (jnp.broadcast_to(cols[at, :, base:base + 1], (dk, wide))
                    for base in (0, _LANES // 2))

        # every group written out: a head's column sits at a STATIC lane
        for n in range(heads // group):
            first = n * group
            here = pl.ds(n * wide, wide)

            def scalar(ref):
                return spread(lambda j: jnp.full(
                    (1, wide), ref[slot * heads + first + j], jnp.float32))

            def column(base):
                return spread(lambda j: jnp.broadcast_to(
                    cols[at, :, base + first + j:base + first + j + 1],
                    (dk, wide)))

            if not plain:
                k, q = column(0), column(_LANES // 2)
            decayed = ring[at, :, here] * scalar(decay_ref)          # [Dk, w]
            if not plain:
                seen = jnp.sum(decayed * k, axis=0, keepdims=True)   # [1, w]
            asked = jnp.sum(decayed * q, axis=0, keepdims=True)
            # (the plain rule's v and o are a [1, lanes] slab a slot: a row
            # at a dynamic sublane read straight into a broadcast, or
            # written from none, is refused, "dynamic load / store with
            # unaligned indices")
            if plain:
                write = v_ref[slot, :, here]
                ring[at, :, here] = decayed + k * write
                o_ref[slot, :, here] = asked + qk_ref[slot] * write
                continue
            write = scalar(beta_ref) * (v_ref[pl.ds(slot, 1), here] - seen)
            ring[at, :, here] = decayed + k * write
            o_ref[pl.ds(slot, 1), here] = asked + scalar(qk_ref) * write

    _through_the_ring(items, arriving, leaving, update)


@functools.cache
def _head_step_jit():
    import jax
    return jax.jit(_head_step, static_argnames=("interpret",))


def _head_step(q, k, v, g, beta, state, active, *, interpret: bool):
    """kda_live_step with every default resolved, a gate a head."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, dv = v.shape
    dk = q.shape[-1]
    half = _LANES // 2
    plain = beta is None
    ids, count = _live_ids(active)
    # k and q as columns, a slot a [Dk, 128] block: k's heads at lanes
    # [0, 64), q's at [64, 128) (the plain rule's one k and one q: lanes 0
    # and 64)
    columns = jnp.concatenate(
        [jnp.pad(z[:, :, None] if plain else z.transpose(0, 2, 1),
                 ((0, 0), (0, 0), (0, half - (1 if plain else heads))))
         for z in (k, q)], axis=-1)
    # what the rule reads as scalars: the decay, the delta rule's beta, q . k
    rule = (jnp.exp(g).reshape(-1),) + (
        () if plain else (beta.reshape(-1),)) + (
        (q * k).sum(axis=-1).reshape(-1),)
    # v and o, whole in VMEM, a row a slot (the plain rule's: a slab)
    rows = (slots,) + (1,) * plain + (heads * dv,)
    scalars = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out, state = pl.pallas_call(
        functools.partial(_head_kernel, heads=heads,
                          group=_head_group(heads, dv), plain=plain),
        out_shape=(jax.ShapeDtypeStruct(rows, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        in_specs=[scalars] * (2 + len(rule)) + [in_hbm, in_vmem, in_hbm],
        out_specs=(in_vmem, in_hbm),
        scratch_shapes=[pltpu.VMEM((_RING, dk, heads * dv), jnp.float32),
                        pltpu.VMEM((_RING, dk, _LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((_RING,)),
                        pltpu.SemaphoreType.DMA((_RING,))],
        input_output_aliases={4 + len(rule): 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_HEAD_VMEM_LIMIT),
        name="ssm_live_step" if plain else "gdn_live_step",
        interpret=interpret,
    )(count, ids, *rule, columns,
      v.reshape(rows), state)
    return out.reshape(slots, heads, dv), state
