# Ring attention: exact attention over sequences sharded across devices.
#
# Long-context / sequence-parallel support the reference entirely lacks
# (SURVEY.md §5.7: no attention code at all).  Design follows blockwise ring
# attention (Liu et al.): Q stays resident, K/V blocks rotate around the
# sequence-axis ring via ppermute (one ICI hop per step), and softmax is
# accumulated online (running max / normalizer), so the full S×S score
# matrix never materializes and memory is O(S_local²) per device.
#
# XLA overlaps the ppermute with the local block's compute, so on a TPU
# ring the collective cost hides behind the matmuls for realistic shapes.

from __future__ import annotations

import functools
import math

from .mesh import AXIS_SEQUENCE

__all__ = ["ring_attention", "ring_attention_sharded", "attention_reference"]


def _block_update(q, k, v, o, m, l, q_offset, k_offset, causal, scale):
    """One online-softmax accumulation step against a K/V block.

    q: [B,H,Sq,D]  k,v: [B,H,Sk,D]  o: [B,H,Sq,D]  m,l: [B,H,Sq]
    offsets are the blocks' global sequence positions (for causal masks)."""
    import jax.numpy as jnp
    from jax import lax

    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        q_pos = q_offset + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = k_offset + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        scores = jnp.where(k_pos <= q_pos, scores, -jnp.inf)

    block_max = jnp.max(scores, axis=-1)                    # [B,H,Sq]
    m_new = jnp.maximum(m, block_max)
    # fully-masked block: keep accumulators untouched (exp(-inf)=0 paths)
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(jnp.isneginf(scores), 0.0, p)
    correction = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = l * correction + jnp.sum(p, axis=-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v, preferred_element_type=jnp.float32)
    return o_new, m_new, l_new


def ring_attention_sharded(q, k, v, axis_name: str = AXIS_SEQUENCE,
                           causal: bool = False, scale: float | None = None):
    """The per-device body — call inside shard_map with the sequence axis
    sharded over `axis_name`.  q,k,v: [B, H, S_local, D].

    GQA-aware: k/v may carry fewer heads than q (H_q % H_kv == 0).  The
    ring rotates the SMALL K/V blocks — expansion to H_q happens
    transiently per block, so ICI bytes and resident K/V stay at
    H_kv size."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    group = q.shape[1] // k.shape[1]
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    s_local = q.shape[2]
    q_offset = my_idx * s_local

    # derive accumulators from q so they carry q's device-varying axes
    # (shard_map type system: the fori_loop carry must match its output,
    # which varies over every mesh axis q is sharded on)
    zeros = (q * 0).astype(jnp.float32)
    o = zeros                                       # [B,H,Sq,D]
    l = jnp.sum(zeros, axis=-1)                     # [B,H,Sq] zeros
    m = l - jnp.inf                                 # [B,H,Sq] -inf
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        o, m, l, k_blk, v_blk = carry
        kv_idx = (my_idx - i) % n         # whose block we hold at step i
        if group > 1:                     # GQA: expand per block only
            k_full = jnp.repeat(k_blk, group, axis=1)
            v_full = jnp.repeat(v_blk, group, axis=1)
        else:
            k_full, v_full = k_blk, v_blk
        o, m, l = _block_update(q, k_full, v_full, o, m, l,
                                q_offset, kv_idx * s_local, causal, scale)
        # rotate K/V one hop; XLA overlaps this with the next iteration's
        # compute on TPU (skipped after the last block)
        k_blk, v_blk = jax.tree.map(
            lambda x: lax.ppermute(x, axis_name, perm), (k_blk, v_blk))
        return o, m, l, k_blk, v_blk

    o, m, l, _, _ = lax.fori_loop(0, n, step, (o, m, l, k, v))
    l = jnp.where(l == 0.0, 1.0, l)       # fully-masked rows → zeros
    return (o / l[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh, axis_name: str = AXIS_SEQUENCE,
                   batch_axis: str | None = "data", causal: bool = False,
                   scale: float | None = None):
    """Sequence-parallel exact attention.

    q, k, v: [B, H, S, D] with S sharded over `axis_name` (and optionally B
    over `batch_axis`) on `mesh`.  Returns [B, H, S, D] with the same
    sharding."""
    import jax
    from jax.sharding import PartitionSpec as P

    batch = batch_axis if (batch_axis in mesh.axis_names) else None
    spec = P(batch, None, axis_name, None)
    body = functools.partial(ring_attention_sharded, axis_name=axis_name,
                             causal=causal, scale=scale)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)


def attention_reference(q, k, v, causal: bool = False,
                        scale: float | None = None):
    """Plain full attention — correctness oracle for the ring version."""
    import jax.numpy as jnp
    from jax import lax, nn

    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        q_pos = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        scores = jnp.where(k_pos <= q_pos, scores, -jnp.inf)
    weights = nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
