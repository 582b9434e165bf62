# One token of the gated delta rule (Kimi Delta Attention, arXiv:2510.26692)
# over the state of the slots that DECODE, as a pallas TPU kernel (ISSUE 34).
#
# A KDA layer keeps, for every slot of the decoder, a state S [H, D, D] in
# float32 (4 MB a slot at 64 heads of 128; 134 MB a layer at 32 slots).  A
# decode step changes the state of the slots that decode and of no other:
#
#     S' = diag(exp g) S;   write = beta (v - k^T S');   S <- S' + k write^T
#     o  = q^T S' + (q . k) write                                  (= q^T S)
#
# models/hybrid_sparse.kda_recurrent says this in four lines and stays the
# oracle.  The program XLA makes of it for the chip passes over EVERY
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
# Validated where: tests/test_kda_step.py (interpreter, CPU: mixed, none
# and all slots live, bits of the untouched states, four donated steps in
# a while_loop); tests/test_chip_compile.py (the cell's whole step compiled
# for a described v5e: four custom calls, no other operation on a state
# leaf); chip_smoke.py's hybrid phase and the cell long_doc_open_loop on
# the chip.

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


def moves_live_states(heads: int, head_dim: int,
                      interpret: bool = False) -> bool:
    """Whether a state of `heads` heads [head_dim, head_dim] can take the
    kernel: mosaic slices a tile out of an HBM operand only where its
    minor axis is whole lanes, and lays a tile's rows into VMEM at whole
    sublanes.  The interpreter has neither."""
    return interpret or (head_dim % _LANES == 0 and
                         _head_tile(heads, head_dim) % 8 == 0)


def _head_tile(heads: int, head_dim: int) -> int:
    """Heads a tile: as many as divide `heads`, lay the tile's rows of
    g, k and q one under the other in a block of 128 rows, and keep the
    tile's state at _TILE_BYTES."""
    most = max(1, min(_LANES // 3, _TILE_BYTES // (4 * head_dim * head_dim)))
    return max(t for t in range(1, min(heads, most) + 1) if heads % t == 0)


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
    q, k, v, g [S, H, D] float32, beta [S, H], state [S, H, D, D] float32,
    active [S] bool -> (o [S, H, D], the new state).  Where `active` is
    False the state comes back bit for bit (it is never touched: the
    result IS the argument's buffer, `input_output_aliases`) and o is
    zeros; g and beta of such a slot are not read.  Equals
    hybrid_sparse.kda_recurrent on the live slots up to the order of
    float32 sums.  interpret=None: compiled on a TPU, the interpreter
    elsewhere."""
    import jax
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # ONE jitted function for every KDA layer of a program: traced and
    # lowered to its mosaic module once (ops.paged_attention._attend_jit).
    # The tile is a static argument (a test that patches _TILE_BYTES gets
    # another entry of jit's cache); _RING and _GROUP are read when the
    # body is traced, so a sweep over them needs a new function each time
    return _step_jit()(q, k, v, g, beta, state, active,
                       interpret=interpret,
                       head_tile=_head_tile(q.shape[1], q.shape[2]))


@functools.cache
def _step_jit():
    import jax
    return jax.jit(_step, static_argnames=("interpret", "head_tile"))


def _step(q, k, v, g, beta, state, active, *, interpret: bool,
          head_tile: int):
    """kda_live_step with every default resolved."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, d = q.shape
    ht = head_tile
    # the live slots' ids, ascending, then zeros; a handful of compares
    # (no sort, no scatter)
    live = active.astype(jnp.int32)
    order = jnp.arange(slots, dtype=jnp.int32)
    rank = jnp.cumsum(live) - 1
    ids = jnp.sum(jnp.where(
        (rank[None, :] == order[:, None]) & active[None, :],
        order[None, :], 0), axis=1)
    count = live.sum()[None]

    scalars = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out, state = pl.pallas_call(
        functools.partial(_kernel, head_tile=ht),
        out_shape=(jax.ShapeDtypeStruct((slots, heads, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        in_specs=[scalars] * 4 + [in_hbm] * 5,
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM), in_hbm),
        scratch_shapes=[pltpu.VMEM((_RING, ht, d, d), jnp.float32),
                        pltpu.VMEM((_RING, _LANES, d), jnp.float32),
                        pltpu.VMEM((_LANES, d), jnp.float32),
                        pltpu.VMEM((d, _LANES), jnp.float32),
                        pltpu.VMEM((_RING, ht, d), jnp.float32),
                        pltpu.SemaphoreType.DMA((_RING,)),
                        pltpu.SemaphoreType.DMA((_RING,))],
        input_output_aliases={8: 1},
        name="kda_live_step",
        interpret=interpret,
    )(count, ids, beta.reshape(-1), (q * k).sum(axis=-1).reshape(-1),
      g, k, q, v, state)
    return out, state
