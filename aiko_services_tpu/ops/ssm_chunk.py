# The Mamba-2 recurrence (arXiv:2405.21060) over a prompt's piece, in its
# chunked form, as ONE pallas TPU kernel a layer (ISSUE 46).
# models/ssm_hybrid.ssm_chunked says the same in thirty lines of jax.numpy
# and stays the form of the CPU, of widths the tiles refuse and of the
# tests, and this kernel's oracle beside `ssm_plain`.
#
# The program XLA makes of `ssm_chunked` for the chip is a `while` over the
# piece's chunks that writes, for every chunk, the table exp(c_t - c_s)
# [Q, Q, H] (4 MB at 64 heads), the pairs times that table (4 MB more) and
# `write`, `keeps`, `before`, `inside` at [Q, H, P] each, all through HBM:
# 0.37 ms a layer of a 512-token piece where the rule's own rows and state
# are 21 MB (PERF.md §6, PR 46).  Here a slot's state is a VMEM block from
# the piece's first chunk to its last, read from HBM once and written once;
# a chunk's rows of x, dt, B and C come in once through the pipeline and its
# y rows go out once; the cumulative dt A, C B^T, the tables, `write` and
# `keeps` live in VMEM for one chunk and never exist in HBM.
#
# This is NOT ops/delta_chunk's body with the WY half left out.  That body
# is a loop over heads whose q and k are a head's own.  Mamba-2 with one
# group has ONE B (the key) and ONE C (the query) for every head, so two of
# the three large products are one product over all the lanes, the state
# left side by side as the pool keeps it, [N, H x P]:
#
#   total       c_t = sum_{s<=t} dt_s A, every head at once [Q, G]: a
#               product with a triangle of ones
#   pairs       C B^T [Q, Q], ONCE a chunk for all the heads
#   seen        C S [Q, G x P]: ONE product, S stationary
#   a head h    table_h = exp(c_t - c_s) where t >= s (every exponent <= 0:
#               no cap, no factorised exp(c_t) exp(-c_s));  write_h = dt x;
#               y_h = exp(c_t) seen_h + (pairs * table_h) write_h;
#               keeps_h = exp(c_Q - c_s) write_h
#   S <- exp(c_Q) S + B^T keeps: ONE product over the group's lanes
#
# Only the own-chunk term needs a head's own [Q, Q] table.  The heads are
# passed a VECTOR of lanes at a time (two heads of 64: `_per_vector`), so x
# and y keep their layout [A, T, H x P], no transpose around the call, and
# every load, store and multiply is of whole vectors: a head's numbers
# (columns of [Q, G]) are spread over its lanes by a select, and the
# vector's heads share one product [Q, 2 Q] x [2 Q, 128] whose right side
# holds each head's rows under its own lanes and zeros elsewhere.
#
# The grid is (rows, groups of heads, chunks), the chunks innermost and in
# order; the state's output block is the accumulator (its index does not
# move along the chunks).  All float32, every product at HIGHEST precision
# (the state is carried over thousands of tokens): the same mathematics at
# the same precision as `ssm_chunked`.  A position with dt = 0 neither
# decays nor writes, to the bit: its rate is 0 and its rows of `write` and
# `keeps` are zeros.  T is padded to whole chunks with such positions.
#
# Validated where: tests/test_ssm_chunk.py (interpreter, CPU: against
# `ssm_plain` token by token and against `ssm_chunked`, a tail that is not
# live, a state that arrives non-zero, a head that forgets within a chunk);
# tests/test_chip_compile.py and tests/test_0_chip_ssm_hybrid.py (compiled
# for a described v5e at the cell's shapes, and the cell's whole admit and
# extend); chip_smoke.py's ssm_hybrid phase and the cell ssm_chat_open_loop
# on the chip.

from __future__ import annotations

import functools

from .delta_chunk import _LANES, _VMEM_LIMIT, _dot

__all__ = ["ssm_chunk_scan", "scans_ssm_chunks"]

# tokens a chunk: the result does not depend on it; on the chip 128 reads
# 92 us a 512-token call where 64 reads 116 and 256 reads 133 (PERF.md §6)
_CHUNK = 128
# what a grid step's blocks (twice: the pipeline), the state and the
# chunk's tables over the group's lanes may take of VMEM
_GROUP_BYTES = 12 << 20


def scans_ssm_chunks(heads: int, width: int, state: int,
                     interpret: bool = False) -> bool:
    """Whether the chunked form over `heads` heads of `width` with a state
    of `state` rows can take the kernel: B and C rows of whole sublanes, a
    head that lies inside a vector of lanes or is whole vectors, and some
    group of heads that is whole vectors and fits (`_group`).  The
    interpreter has no tiles."""
    if interpret:
        return True
    return state % 8 == 0 and (_LANES % width == 0 or width % _LANES == 0) \
        and _group(heads, width, state, False) > 0


def _group(heads: int, width: int, state: int, interpret: bool) -> int:
    """Heads a grid step: the most that divide `heads`, are whole vectors
    of lanes and keep the step's blocks under _GROUP_BYTES (0: none
    does).  On the chip a 512-token call reads the same at 16, 32 or all 64
    heads a step (94.9, 92.3, 92.4 us) and slower below (8: 103, 4: 122:
    C B^T and the running sums are made once a step; PERF.md §6, PR
    46)."""
    # over the group's lanes, float32: x and y a chunk's rows, twice; the
    # state's block in and its block out, twice each; `seen`, `keeps` and
    # the update, a chunk's rows or the state's each
    a_lane = 4 * (6 * _CHUNK + 5 * state)
    fits = [n for n in range(1, heads + 1)
            if heads % n == 0 and n * width * a_lane <= _GROUP_BYTES and (
                interpret or (n * width) % _LANES == 0)]
    return max(fits, default=0)


def _per_vector(group: int, width: int) -> int:
    """Heads that share a vector of lanes: the most that divide the group
    and lie inside one (1: a head is a vector or more)."""
    return max(n for n in range(1, group + 1)
               if group % n == 0 and (n == 1 or n * width <= _LANES))


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, state_ref, y_ref, state_out,
            keeps, *, width: int):
    """The body (header): one chunk of a group of heads."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    q, lanes = x_ref.shape[1:]
    group, n = lanes // width, b_ref.shape[2]
    per = _per_vector(group, width)
    tile = per * width

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_out[...] = state_ref[...]

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    keep = iota((q, q), 0) >= iota((q, q), 1)
    # which of the vector's heads a lane belongs to
    owner = {rows: iota((rows, tile), 1) // width for rows in {q, n}}

    def spread(columns, first):
        """[R, G] a number a row and head -> [R, tile]: the vector's heads
        from `first`, each over its own lanes."""
        out = columns[:, first + per - 1:first + per]
        for i in range(per - 2, -1, -1):
            out = jnp.where(owner[columns.shape[0]] == i,
                            columns[:, first + i:first + i + 1], out)
        return jnp.broadcast_to(out, (columns.shape[0], tile))

    b, c, dt = b_ref[0], c_ref[0], dt_ref[0, 0]
    # c_t, inclusive, every head at once; as rows for the tables' c_s
    total = _dot(keep.astype(jnp.float32), dt * a_ref[0])           # [Q, G]
    total_rows = total.T                                            # [G, Q]
    last = total[q - 1:q]                                           # [1, G]
    pairs = jnp.where(keep, _dot(c, b, ((1,), (1,))), 0.0)          # [Q, Q]
    grown = jnp.exp(total)
    fading = dt * jnp.exp(last - total)
    # exp(c_Q) as COLUMNS [N, G] of one number, by a selection (exact):
    # mosaic spreads no [1, 1] over sublanes and lanes at once
    decay = jnp.exp(_dot((iota((n, q), 1) == q - 1).astype(jnp.float32),
                         total))
    seen = _dot(c, state_out[0])                                    # C S

    for j in range(group // per):
        first, at = j * per, slice(j * tile, (j + 1) * tile)
        x = x_ref[0, :, at]
        write = x * spread(dt, first)
        keeps[:, at] = x * spread(fading, first)
        tables = [pairs * jnp.exp(jnp.where(
            keep, total[:, h:h + 1] - total_rows[h:h + 1], 0.0))
            for h in range(first, first + per)]
        if per == 1:
            inside = _dot(tables[0], write)
        else:
            inside = _dot(
                jnp.concatenate(tables, axis=1),
                jnp.concatenate([jnp.where(owner[q] == i, write, 0.0)
                                 for i in range(per)], axis=0))
        y_ref[0, :, at] = seen[:, at] * spread(grown, first) + inside
        state_out[0, :, at] = state_out[0, :, at] * spread(decay, first)

    state_out[0] = state_out[0] + _dot(b.T, keeps[...])


def ssm_chunk_scan(x, dt, b, c, a, state, *, interpret: bool | None = None):
    """The Mamba-2 recurrence over T tokens, chunk by chunk in one kernel:
    x [A, T, H, P], dt [A, T, H] (0 at a position that is not live), b, c
    [A, T, N] the one key and query of every head, a [H] = -exp(A_log),
    all float32, state [A, N, H x P] float32 as the pool keeps it -> (y
    [A, T, H, P], the state after the live positions).  Equals
    models/ssm_hybrid.ssm_chunked (and `ssm_plain`) up to the order of
    float32 sums.  interpret=None: compiled on a TPU, the interpreter
    elsewhere."""
    import jax
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    heads, width = x.shape[2:]
    # ONE jitted function for every Mamba layer of a program: traced and
    # lowered to its mosaic module once a shape (ops.delta_chunk._scan_jit)
    return _scan_jit()(x, dt, b, c, a, state, interpret=interpret,
                       group=_group(heads, width, b.shape[2], interpret))


@functools.cache
def _scan_jit():
    import jax
    return jax.jit(_scan, static_argnames=("interpret", "group"))


def _scan(x, dt, b, c, a, state, *, interpret: bool, group: int):
    """ssm_chunk_scan with every default resolved."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, t, heads, width = x.shape
    n = b.shape[2]
    # a piece shorter than a chunk is one chunk of whole sublanes
    q = min(_CHUNK, -(-t // 8) * 8)
    chunks = -(-t // q)
    groups, lanes = heads // group, group * width

    def padded(z):                      # [A, T, ..] -> [A, chunks x Q, ..]
        return jnp.pad(z, ((0, 0), (0, chunks * q - t)) +
                       ((0, 0),) * (z.ndim - 2))

    # dt a group of heads at a time: [A, H / G, T, G] (as it came where the
    # group is every head)
    rates = padded(dt).reshape(rows, chunks * q, groups, group).transpose(
        0, 2, 1, 3)
    a_row = pl.BlockSpec((1, 1, group), lambda i, g, m: (g, 0, 0))
    a_chunk = pl.BlockSpec((1, q, lanes), lambda i, g, m: (i, m, g))
    shared = pl.BlockSpec((1, q, n), lambda i, g, m: (i, m, 0))
    a_state = pl.BlockSpec((1, n, lanes), lambda i, g, m: (i, 0, g))
    # what the call moves and multiplies, for XLA's scheduling around it
    macs = rows * chunks * (groups * q * q * (n + group) +
                            heads * width * q * (2 * n + q))
    moved = 2 * x.size + dt.size + groups * (b.size + c.size) + \
        2 * state.size
    out, state = pl.pallas_call(
        functools.partial(_kernel, width=width),
        grid=(rows, groups, chunks),
        out_shape=(jax.ShapeDtypeStruct((rows, chunks * q, heads * width),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        in_specs=[a_chunk,
                  pl.BlockSpec((1, 1, q, group),
                               lambda i, g, m: (i, g, m, 0)),
                  shared, shared, a_row, a_state],
        out_specs=(a_chunk, a_state),
        scratch_shapes=[pltpu.VMEM((q, lanes), jnp.float32)],
        input_output_aliases={5: 1},
        cost_estimate=pl.CostEstimate(
            flops=2 * macs, bytes_accessed=4 * moved,
            transcendentals=rows * chunks * heads * q * (q + 2)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="ssm_chunk_scan",
        interpret=interpret,
    )(padded(x.reshape(rows, t, heads * width)), rates, padded(b), padded(c),
      a.reshape(groups, 1, group), state)
    return out[:, :t].reshape(rows, t, heads, width), state
