# Paged decode attention: a pallas TPU kernel that reads K/V straight
# out of the serving block pool through per-slot block tables — vLLM
# PagedAttention's indirection (Kwon et al., SOSP 2023), TPU-flavored
# via scalar-prefetch index maps (ISSUE 16, ROADMAP item 2).
#
# The XLA paged path (serving_paged._gather_views) must materialize a
# slot-major [S, H, T, D] copy of every slot's blocks once per round
# before the attention einsums can run — the one cost plain XLA cannot
# delete (its share of the decode step is not measured on the current
# chip).  Here the block table rides the grid as a scalar-prefetch
# operand, so each grid step DMAs one pool block [H, B, D] directly
# into VMEM: K and V stream through HBM once per query-row tile, and
# nothing slot-major ever exists.
#
# Grid (S, R, 2, nb): per slot and query-row tile, two phases:
#   phase 0  walks K blocks tables[s, j], storing each block's masked
#            scores at scores[j] of a VMEM scratch [nb, Hkv, rows, B]
#            and keeping a running row max; the last step adds the
#            side-buffer scores, turns the whole (main ++ side) row
#            into exp(x - max) in place, sums it, and seeds the
#            accumulator with the side PV
#   phase 1  walks V blocks, accumulating (scores[j] / sum) @ V into
#            the f32 accumulator, and writes the output on the last step
# The block index sits on the scratch's LEADING axis because mosaic
# only takes a dynamic index on the lane axis when it is provably a
# multiple of 128: a flat [Hkv, rows, nb*B + P] row indexed at j*B was
# refused at every serving geometry (kv_block=32).  R tiles the G*W
# query rows (_row_tile) so the scratch fits VMEM when a chunked-
# prefill extend brings G*chunk of them; a decode round has R = 1.
# The inactive operand's index map parks on an unchanged block index
# (K on tables[s, nb-1] through phase 1, V on tables[s, 0] through
# phase 0), so the pallas pipeline skips those re-fetches — net HBM
# traffic stays one K pass + one V pass per row tile.
#
# Numerics discipline (the parity contract with the XLA oracle):
# every elementwise op matches serving._grouped_block_attention /
# serving_paged's extend body — f32 QK dots * scale, int8 scale
# treatment, -1e30 masking, jax.nn.softmax's exp(x - max) / sum over
# the full row, weight casts before the PV dots.  The kernel's extra
# [t_cap, nb*B) columns are masked to -1e30 and contribute exact zeros
# to the softmax sum, so no t_cap re-slice is needed.  Only the
# ASSOCIATION of the sums differs (blockwise vs one full-T
# contraction), which is why the acceptance criterion is greedy TOKEN
# identity, proven per combination in tests/test_paged_kv.py
# (interpret mode on CPU, float32).
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
# Validated where (PR 21): tests/test_chip_compile.py compiles it for
# a described v5e at the serving geometries (Llama-1B heads, kv_block
# 32, bf16 and int8, fold on and off, decode / speculative / extend
# widths); chip_smoke.py runs it on a v5e chip against the gather
# path, standalone and inside ContinuousDecoder.  In bf16 on the chip
# the two paths differ by rounding, so greedy tokens flip at near-ties
# there (CHANGES.md, PR 21); its speed is not measured.

from __future__ import annotations

import functools

__all__ = ["paged_decode_attention"]


def _paged_attn_kernel(*refs, int8: bool, fold: bool,
                       block_tokens: int, scale: float):
    import jax
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

    @pl.when(phase == 0)
    def _block_scores():
        q = q_ref[0]                                  # [Hkv, R, D]
        k = kq_ref[0]                                 # [Hkv, B, D]
        if int8 and not fold:
            # extend-path numerics: cast both factors then multiply in
            # the compute dtype, layers.dequantize_kv_cache verbatim
            k = k.astype(q.dtype) * ks_ref[0].astype(q.dtype)
        else:
            k = k.astype(q.dtype)
        sc = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [Hkv,R,B]
        if int8 and fold:
            sc = sc * ks_ref[0]
        # absolute position mask — positions past the slot's read-only
        # extent (entry_lengths) are dead cells / null-block zeros
        pos = j * block_tokens + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 2)
        sc = jnp.where(pos < entry_ref[s], sc, -1e30)
        scores[j] = sc         # leading-axis index: see the header
        block_max = jnp.max(sc, axis=-1, keepdims=True)
        # at j == 0 the scratch still holds the previous tile's max
        row_max[...] = jnp.where(
            j == 0, block_max, jnp.maximum(row_max[...], block_max))

    @pl.when((phase == 0) & (j == nb - 1))
    def _side_softmax():
        q = q_ref[0]
        k_s = k_side_ref[0]                           # [Hkv, P, D]
        sc = jax.lax.dot_general(
            q, k_s, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [Hkv,R,P]
        sc = jnp.where(valid_ref[0][None] != 0, sc, -1e30)
        # jax.nn.softmax over the whole (main ++ side) row, spelled out
        # blockwise: exp(x - rowmax) / sum(exp(x - rowmax))
        m = jnp.maximum(row_max[...],
                        jnp.max(sc, axis=-1, keepdims=True))
        side = jnp.exp(sc - m)

        def block_exp(i, total):
            e = jnp.exp(scores[i] - m)
            scores[i] = e                    # phase 1 reads them back
            return total + jnp.sum(e, axis=-1, keepdims=True)

        total = jax.lax.fori_loop(
            0, nb, block_exp, jnp.sum(side, axis=-1, keepdims=True))
        row_sum[...] = total
        v_s = v_side_ref[0]
        acc[...] = jax.lax.dot_general(
            (side / total).astype(v_s.dtype), v_s,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(phase == 1)
    def _block_pv():
        w = scores[j] / row_sum[...]
        v = vq_ref[0]
        if int8 and not fold:
            v = v.astype(q_ref.dtype) * vs_ref[0].astype(q_ref.dtype)
        else:
            if int8:
                w = w * vs_ref[0]
            v = v.astype(q_ref.dtype)
        acc[...] += jax.lax.dot_general(
            w.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when((phase == 1) & (j == nb - 1))
    def _finish():
        o_ref[0] = acc[...]


# The scores scratch holds one query-row tile's whole (blockwise) row
# in VMEM.  Lanes pad to 128 and sublanes to 8, so at kv_block=32 a
# chunked-prefill extend (G*chunk rows) would need 4x its nominal
# bytes; rows are independent, so tile them to stay well inside the
# 16 MiB a v5e kernel may scope by default.
_SCORES_VMEM_BUDGET = 8 << 20


def _row_tile(gw: int, nb: int, num_kv: int, block_tokens: int) -> int:
    per_row = nb * num_kv * (-(-block_tokens // 128) * 128) * 4
    for rows in range(gw, 0, -1):
        if gw % rows or (rows != gw and rows % 8):
            continue
        if (-(-rows // 8) * 8) * per_row <= _SCORES_VMEM_BUDGET:
            return rows
    raise ValueError(
        f"paged_decode_attention: {nb} blocks of {block_tokens} tokens "
        f"x {num_kv} kv heads need more than "
        f"{_SCORES_VMEM_BUDGET >> 20} MiB of VMEM for one 8-row score "
        f"tile; use a larger kv_block or a shorter max_seq")


def paged_decode_attention(q, k_pool, v_pool, tables, k_side, v_side,
                           side_valid, entry_lengths, *, groups: int,
                           scale: float | None = None,
                           fold_scales: bool = True,
                           interpret: bool | None = None):
    """Block-table-native decode attention over a paged KV pool.

    q:            [S, Hkv, G*W, D] grouped queries (G-major: the
                  (group, width) axes flattened)
    k/v_pool:     per-layer pool leaf [N, Hkv, B, D], or the int8
                  serving dict {"q" i8 [N, Hkv, B, D], "s" f32
                  [N, Hkv, B]}
    tables:       [S, nb] int32 block ids (nb * B >= the slot's
                  readable extent; unfilled entries point at the null
                  block and are masked)
    k/v_side:     [S, Hkv, P, D] this round's side buffers in the
                  compute dtype
    side_valid:   [S, W, P] bool — per-query side visibility, computed
                  by the caller (this is what widens the speculative
                  verify into the same kernel: W = 1 + k and the
                  pos_side <= q_pos mask arrive here unchanged)
    entry_lengths: [S] int32 read-only main extent per slot

    Returns [S, Hkv, G*W, D] f32.  interpret=None auto-selects:
    compiled pallas on TPU, interpreter mode elsewhere (CPU tests run
    the same kernel code path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..models.layers import paged_pool_planes

    kq, k_scales = paged_pool_planes(k_pool)
    vq, v_scales = paged_pool_planes(v_pool)
    int8 = k_scales is not None
    slots_n, num_kv, gw, head_dim = q.shape
    nb = tables.shape[1]
    block_tokens = kq.shape[2]
    side_len = k_side.shape[2]
    if scale is None:
        # f32(1)/sqrt(f32(d)) — the exact value the oracle's traced
        # 1/jnp.sqrt computes, so the score scaling cannot drift a ulp
        scale = float(np.float32(1.0) / np.sqrt(np.float32(head_dim)))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows = _row_tile(gw, nb, num_kv, block_tokens)
    # the [S, W, P] visibility mask repeats per query group (rows are
    # G-major) and travels as int32: mosaic takes neither a bool VMEM
    # operand nor the in-kernel broadcast+reshape that built this
    valid_rows = jnp.tile(side_valid.astype(jnp.int32), (1, groups, 1))

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
    in_specs = [pl.BlockSpec((1, num_kv, rows, head_dim), q_map),
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
    in_specs += [pl.BlockSpec((1, num_kv, side_len, head_dim), side_map),
                 pl.BlockSpec((1, num_kv, side_len, head_dim), side_map),
                 pl.BlockSpec((1, rows, side_len), valid_map)]
    operands += [k_side, v_side, valid_rows]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots_n, gw // rows, 2, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, num_kv, rows, head_dim), q_map),
        scratch_shapes=[
            pltpu.VMEM((nb, num_kv, rows, block_tokens), jnp.float32),
            pltpu.VMEM((num_kv, rows, 1), jnp.float32),
            pltpu.VMEM((num_kv, rows, 1), jnp.float32),
            pltpu.VMEM((num_kv, rows, head_dim), jnp.float32),
        ])
    kernel = functools.partial(
        _paged_attn_kernel, int8=int8, fold=fold_scales,
        block_tokens=block_tokens, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (slots_n, num_kv, gw, head_dim), jnp.float32),
        interpret=interpret,
    )(tables.astype(jnp.int32), entry_lengths.astype(jnp.int32),
      *operands)
