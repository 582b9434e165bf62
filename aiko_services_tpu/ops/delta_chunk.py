# The gated delta rule over a prompt's piece, in its chunked (WY) form, as
# ONE pallas TPU kernel a layer (ISSUE 41).  models/delta_rule.chunked says
# the same in forty lines of jax.numpy and stays the form of the CPU, of
# widths the tiles refuse and of the tests, and this kernel's oracle beside
# `recurrent`.
#
# The program XLA makes of `chunked` for the chip is some 75 small
# operations a layer, each writing what the next reads (535 MB a layer at 30
# heads of [96, 192] where the rule's own rows and state are 40 MB), and ONE
# of them is three quarters of its time: `solve_triangular` lowers to the
# custom call `InvertDiagBlocksLowerTriangular`, 1.2 ms a layer for 240
# systems of [64, 64] (PERF.md §6, PR 41).  Here a head's state [Dk, Dv] is
# a VMEM scratch from the piece's first chunk to its last, read from HBM
# once and written once; a chunk's rows of q, k, v, gate and beta come in
# once through the pipeline and its output rows go out once; the cumulative
# gate, the pair tables, the inverse, `reads`, `writes`, `asks`, `keeps`
# and `u` live in VMEM for one head and chunk and never exist in HBM.
#
# The grid is (rows, groups of heads, chunks), the chunks innermost and in
# order.  A grid step is a chunk of 64 tokens of a GROUP of heads (`_group`:
# as many as a budget of VMEM holds); a loop passes the group's heads
# through ONE body.  For a head and chunk, all float32, every product on
# the matrix unit at HIGHEST precision (the state is carried over thousands
# of tokens):
#
#   G           the gate summed from the chunk's start, a product with a
#               triangle of ones
#   kk, qk      k_t . k_s and q_t . k_s decayed from s to t, ONE product
#               [k; q] k^T.  A gate a HEAD: times a table exp(G_t - G_s).  A
#               gate a CHANNEL: a sub-block of 16 queries at a time, both
#               sides rescaled to the sub-block's start (delta_rule.
#               _pair_products says why, and why the cap)
#   T           (I + beta kk)^-1, unit lower triangular [64, 64]: the four
#               16 x 16 diagonal blocks by forward substitution (fifteen
#               rank-one steps on the vector unit, the four blocks at once),
#               the rest by products, [[A, 0], [C, B]]^-1 = [[A^-1, 0],
#               [-B^-1 C A^-1, B^-1]], 16 -> 32 -> 64.  Not the Neumann
#               product: with beta up to 2 its powers grow and cancel
#   writes, reads = T (beta v), T (beta k exp G)
#   [reads; asks] S  one product, S stationary;  u = writes - reads S
#   [qk; keeps^T] u  one product, u stationary: the output's own-chunk part
#               and the state's update;  o = asks S + qk u,
#               S <- exp(G_C) S + keeps^T u
#
# A position with g = 0 and beta = 0 changes nothing, to the bit: its row
# of T is the unit row, its u is zero.  T is padded with such positions.
#
# The two grains of gate are two builders of (kk, qk, the scalings) around
# one body, chosen by the gate's shape, and lay the state as ops/kda_step
# does: a gate a channel [A, H, Dk, Dv]; a gate a head [A, Dk, H x Dv], the
# heads side by side on the lanes as the pool keeps them, split into the
# scratch at the first chunk and laid back at the last.  q, k, v (and a
# channel gate) arrive head-major [A, H, T, D] (XLA transposes them: a head
# of 96 or 192 lanes cannot be cut out of [T, H x D] by whole vectors), the
# gate a head and beta as ROWS [.., Hg, 64]; a row becomes the column that
# scales rows by a select against the identity and a sum over lanes.
#
# Validated where: tests/test_delta_chunk.py (interpreter, CPU: against
# `recurrent` token by token and against `chunked`, both grains, a tail
# that is not live, beta to 2, a state that arrives non-zero);
# tests/test_chip_compile.py (compiled for a described v5e at both cells'
# shapes, and the cell's whole admit); chip_smoke.py's hybrid and
# gated_delta phases and the cells gdn_decode_saturated and
# long_doc_open_loop on the chip.

from __future__ import annotations

import functools

__all__ = ["delta_chunk_scan", "scans_chunks"]

_LANES = 128
_CHUNK = 64        # tokens a WY block: models/delta_rule._CHUNK
_SUB = 16          # a diagonal block of the inverse; a sub-block of queries
_EXP_CAP = 80.0    # models/delta_rule._EXP_CAP
# what a grid step's blocks (twice: the pipeline) and the group's state may
# take of VMEM, and what the call asks the compiler for
_GROUP_BYTES = 12 << 20
_VMEM_LIMIT = 64 << 20


def scans_chunks(heads: int, key_dim: int, value_dim: int, by_head: bool,
                 interpret: bool = False) -> bool:
    """Whether the chunked form over `heads` heads [key_dim, value_dim]
    can take the kernel.  A chunk's rows and a head's state are blocks
    whose minor sides are the array's own, so any side that is whole
    sublanes passes; a gate a HEAD also lays the state's heads side by
    side, so some group of them has to be whole vectors of lanes
    (`_group`).  The interpreter has no tiles."""
    if interpret:
        return True
    return key_dim % 8 == 0 and value_dim % 8 == 0 and \
        _group(heads, key_dim, value_dim, by_head, False) > 0


def _group(heads: int, key_dim: int, value_dim: int, by_head: bool,
           interpret: bool) -> int:
    """Heads a grid step: the most that divide `heads` and keep the step's
    blocks and state under _GROUP_BYTES (0: none does).  On the chip it
    reads the same at 2, 6, 10 or all 30 heads a step (PERF.md §6, PR
    41)."""
    def lanes(n):
        return -(-n // _LANES) * _LANES
    rows = -(-key_dim // 8) * 8
    # q, k (and a channel gate), v, o: a chunk's rows, twice; the state:
    # its scratch, its block in and its block out, twice each
    vectors = (2 if by_head else 3) * lanes(key_dim) + 2 * lanes(value_dim)
    a_head = 4 * (2 * _CHUNK * vectors + 5 * rows * lanes(value_dim))
    fits = [n for n in range(1, heads + 1)
            if heads % n == 0 and n * a_head <= _GROUP_BYTES and (
                interpret or not by_head or (n * value_dim) % _LANES == 0)]
    return max(fits, default=0)


def _inverse(lower, consts):
    """(I + lower)^-1 for `lower` [C, C] strictly lower triangular: the
    diagonal blocks of _SUB by forward substitution, all at once, PACKED
    [C, _SUB] (row r holds its own block's columns); then 16 -> 32 -> 64 by
    products."""
    import jax.numpy as jnp
    same, pack, unpack = (consts[name] for name in ("same", "pack", "unpack"))
    c = lower.shape[0]
    blocks = c // _SUB
    # l[r, j] = lower[r, r's block x _SUB + j]: a selection, exact
    l = _dot(jnp.where(same[_SUB], lower, 0.0), pack)
    y = pack                            # the identity, packed the same way
    # (I + L) = E_0 E_1 ..., E_j = I + l_j e_j^T: the inverse applies
    # E_j^-1 = I - l_j e_j^T from the left, j ascending; row j is final
    # when its turn comes
    for j in range(_SUB - 1):
        row = jnp.concatenate(
            [jnp.broadcast_to(y[b * _SUB + j:b * _SUB + j + 1, :],
                              (_SUB, _SUB)) for b in range(blocks)], axis=0)
        y = y - l[:, j:j + 1] * row
    inv = jnp.where(same[_SUB], _dot(y, unpack), 0.0)
    size = _SUB
    while size < c:
        # the blocks under the diagonal of each pair of `size` blocks
        under = jnp.where(same[2 * size] & ~same[size], lower, 0.0)
        inv = inv - _dot(_dot(inv, under), inv)
        size *= 2
    return inv


def _dot(a, b, dims=((1,), (0,))):
    """a @ b (or the contraction `dims` names) in float32 at HIGHEST."""
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _constants(c: int, key_dim: int, by_head: bool):
    """The masks and selections every head of a grid step shares, made
    from iotas once a step."""
    import jax
    import jax.numpy as jnp

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    row, col = iota((c, c), 0), iota((c, c), 1)
    consts = {"keep": row >= col, "strict": row > col, "eye": row == col,
              "same": {}}
    size = _SUB
    while size <= c:
        consts["same"][size] = (row // size) == (col // size)
        size *= 2
    consts["pack"] = (iota((c, _SUB), 0) % _SUB ==
                      iota((c, _SUB), 1)).astype(jnp.float32)
    consts["unpack"] = (iota((_SUB, c), 1) % _SUB ==
                        iota((_SUB, c), 0)).astype(jnp.float32)
    consts["ones_below"] = consts["keep"].astype(jnp.float32)  # cumsum
    if by_head:
        consts["last"] = iota((key_dim, c), 1) == c - 1
    else:
        consts["key_eye"] = iota((key_dim, key_dim), 0) == \
            iota((key_dim, key_dim), 1)
    return consts


def _column(row, eye):
    """A row [1, N] as the column [N, 1]: a select against the identity
    and a sum over lanes (exact: one term a row is not zero)."""
    import jax.numpy as jnp
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _head_pairs(q, k, g_row, consts):
    """A gate a HEAD, g_row [1, C] the chunk's cumulative gates: (kk, qk,
    `grown` = exp G, which scales q's rows to asks and k's to reads;
    `fading` = exp(G_C - G), k's to keeps; the state's decay exp G_C)."""
    import jax.numpy as jnp
    c = q.shape[0]
    g_col = _column(g_row, consts["eye"])
    table = jnp.exp(jnp.where(consts["keep"], g_col - g_row, 0.0))
    pairs = _dot(jnp.concatenate([k, q], axis=0), k, ((1,), (1,))) * \
        jnp.concatenate([table, table], axis=0)
    last = g_row[:, c - 1:c]                                        # [1, 1]
    # the decay as a column [Dk, 1] of one number: mosaic spreads no [1, 1]
    # over sublanes and lanes at once
    decay = jnp.exp(jnp.sum(jnp.where(consts["last"], g_row, 0.0), axis=1,
                            keepdims=True))
    return (jnp.where(consts["strict"], pairs[:c], 0.0),
            jnp.where(consts["keep"], pairs[c:], 0.0),
            jnp.exp(g_col), jnp.exp(last - g_col), decay)


def _channel_pairs(q, k, g, consts):
    """A gate a CHANNEL, g [C, Dk] the chunk's own gates: the same five,
    the pair products a sub-block of _SUB queries at a time, both sides
    rescaled to the sub-block's start."""
    import jax.numpy as jnp
    c = q.shape[0]
    total = _dot(consts["ones_below"], g)                           # [C, Dk]
    kk, qk = [], []
    for i in range(c // _SUB):
        rows = slice(i * _SUB, (i + 1) * _SUB)
        before = jnp.zeros_like(total[:1]) if i == 0 \
            else total[i * _SUB - 1:i * _SUB]                       # [1, Dk]
        scale = jnp.exp(total[rows] - before)
        keys = k * jnp.exp(jnp.minimum(before - total, _EXP_CAP))
        pairs = _dot(jnp.concatenate([k[rows] * scale, q[rows] * scale],
                                     axis=0), keys, ((1,), (1,)))
        kk.append(pairs[:_SUB])
        qk.append(pairs[_SUB:])
    last = total[c - 1:c]                                           # [1, Dk]
    return (jnp.where(consts["strict"], jnp.concatenate(kk, axis=0), 0.0),
            jnp.where(consts["keep"], jnp.concatenate(qk, axis=0), 0.0),
            jnp.exp(total), jnp.exp(last - total),
            _column(jnp.exp(last), consts["key_eye"]))


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, state_ref, o_ref,
            state_out, memory, total, *, by_head: bool):
    """The body (header): one chunk of a group of heads."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    group, c, dk = q_ref.shape[1:]
    dv = v_ref.shape[3]
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        if by_head:
            for j in range(group):
                memory[j] = state_ref[0, :, j * dv:(j + 1) * dv]
        else:
            memory[...] = state_ref[0]

    consts = _constants(c, dk, by_head)
    if by_head:
        # every head's cumulative gates at once, a row a head
        total[...] = _dot(g_ref[0, 0, 0], consts["ones_below"],
                          ((1,), (1,)))

    def one(j, _):
        q, k, v = q_ref[0, j], k_ref[0, j], v_ref[0, j]
        beta = _column(beta_ref[0, 0, 0, pl.ds(j, 1), :], consts["eye"])
        if by_head:
            kk, qk, grown, fading, decay = _head_pairs(
                q, k, total[pl.ds(j, 1), :], consts)
        else:
            kk, qk, grown, fading, decay = _channel_pairs(
                q, k, g_ref[0, j], consts)
        solve = _inverse(beta * kk, consts)
        writes = _dot(solve, beta * v)
        reads = _dot(solve, beta * grown * k)
        state = memory[j]
        seen = _dot(jnp.concatenate([reads, grown * q], axis=0), state)
        u = writes - seen[:c]
        made = _dot(jnp.concatenate([qk, (fading * k).T], axis=0), u)
        o_ref[0, j] = seen[c:] + made[:c]
        memory[j] = state * decay + made[c:]
        return 0

    # ONE head's body for the compiler: two written out read 1% faster on
    # the chip (their chains of products do not interleave), 30 would cost
    # every set-up their tracing
    jax.lax.fori_loop(0, group, one, 0)

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _():
        if by_head:
            for j in range(group):
                state_out[0, :, j * dv:(j + 1) * dv] = memory[j]
        else:
            state_out[0] = memory[...]


def delta_chunk_scan(q, k, v, g, beta, state, *,
                     interpret: bool | None = None):
    """The gated delta rule over T tokens, chunk by chunk in one kernel:
    q, k [A, T, H, Dk], v [A, T, H, Dv] float32, beta [A, T, H], and by
    the gate's grain
        g [A, T, H, Dk] (a channel), state [A, H, Dk, Dv] float32, or
        g [A, T, H] (a head), state [A, Dk, H x Dv] float32: the heads
        side by side on the lanes, as the pool keeps them
    -> (o [A, T, H, Dv], the state after the last token, laid as it came).
    Equals models/delta_rule.chunked (and T calls of `recurrent`) up to the
    order of float32 sums; a position with g = 0 and beta = 0 changes
    nothing.  interpret=None: compiled on a TPU, the interpreter
    elsewhere."""
    import jax
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    heads, dk = k.shape[2:]
    dv = v.shape[3]
    by_head = g.ndim == 3
    # ONE jitted function for every recurrent layer of a program: traced
    # and lowered to its mosaic module once a shape (ops.kda_step._step_jit)
    return _scan_jit()(q, k, v, g, beta, state, interpret=interpret,
                       group=_group(heads, dk, dv, by_head, interpret))


@functools.cache
def _scan_jit():
    import jax
    return jax.jit(_scan, static_argnames=("interpret", "group"))


def _scan(q, k, v, g, beta, state, *, interpret: bool, group: int):
    """delta_chunk_scan with every default resolved."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    a, t, heads, dk = k.shape
    dv = v.shape[3]
    by_head = g.ndim == 3
    c = _CHUNK
    n = -(-t // c)
    groups = heads // group

    def head_major(z):                  # [A, T, H, D] -> [A, H, N x C, D]
        z = jnp.pad(z, ((0, 0), (0, n * c - t), (0, 0), (0, 0)))
        return z.transpose(0, 2, 1, 3)

    def rows(z):                        # [A, T, H] -> [A, H/Hg, N, Hg, C]
        z = jnp.pad(z, ((0, 0), (0, n * c - t), (0, 0)))
        return z.reshape(a, n, c, groups, group).transpose(0, 3, 1, 4, 2)

    def vectors(d):
        return pl.BlockSpec((1, group, c, d), lambda i, h, m: (i, h, m, 0))

    a_row = pl.BlockSpec((1, 1, 1, group, c),
                         lambda i, h, m: (i, h, m, 0, 0))
    if by_head:
        a_state = pl.BlockSpec((1, dk, group * dv), lambda i, h, m: (i, 0, h))
        gate, gate_spec = rows(g), a_row
    else:
        a_state = pl.BlockSpec((1, group, dk, dv),
                               lambda i, h, m: (i, h, 0, 0))
        gate, gate_spec = head_major(g), vectors(dk)
    # what the call moves and multiplies, for XLA's scheduling around it
    macs = c * (2 * c * dk + 5 * c * c + c * (dk + dv) + 2 * dk * dv +
                (c + dk) * dv)
    moved = q.size + k.size + v.size + g.size + beta.size + v.size + \
        2 * state.size
    out, state = pl.pallas_call(
        functools.partial(_kernel, by_head=by_head),
        grid=(a, groups, n),
        out_shape=(jax.ShapeDtypeStruct((a, heads, n * c, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        in_specs=[vectors(dk), vectors(dk), vectors(dv), gate_spec, a_row,
                  a_state],
        out_specs=(vectors(dv), a_state),
        scratch_shapes=[pltpu.VMEM((group, dk, dv), jnp.float32),
                        pltpu.VMEM((group, c), jnp.float32)],
        input_output_aliases={5: 1},
        cost_estimate=pl.CostEstimate(
            flops=2 * macs * a * heads * n, bytes_accessed=4 * moved,
            transcendentals=a * heads * n * c * (c + 2 * dk)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="gdn_chunk_scan" if by_head else "kda_chunk_scan",
        interpret=interpret,
    )(head_major(q), head_major(k), head_major(v), gate, rows(beta), state)
    return out.transpose(0, 2, 1, 3)[:, :t], state
