# The gated delta rule, token by token and in its chunked (WY) form, for
# both grains of gate (ISSUE 40; the forms are ISSUE 33's, which lived in
# models/hybrid_sparse.py until a second model wanted them):
#
#     S' = decay(g) S;   write = beta (v - k^T S');   S <- S' + k write^T
#     o  = q^T S
#
# A head's state S is [Dk (key), Dv (value)], the two sides free of each
# other; `beta` is whatever the model makes it ((0, 1) for Kimi Delta
# Attention, (0, 2) where I - beta k k^T may reach eigenvalue -1).  The
# gate's grain is read off its shape:
#
#   a CHANNEL  g [.., H, Dk], decay = diag(exp g): Kimi Delta Attention
#              (arXiv:2510.26692).  Inside a chunk the decay cannot leave
#              the product q_t . k_s, so the pair products are taken a
#              sub-block of queries at a time, both sides rescaled to the
#              sub-block's start (`_pair_products`, `_EXP_CAP`).
#   a HEAD     g [.., H], decay = exp g, ONE number: Gated DeltaNet
#              (arXiv:2412.06464).  The chunk is ONE q k^T and ONE k k^T a
#              head times a [C, C] table exp(G_t - G_s), whose exponent is
#              never positive where it is kept: no cap, no sub-blocks.
#
# float32 throughout, products at HIGHEST precision: the state is carried
# over thousands of tokens.

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["recurrent", "chunked"]

_HIGHEST = jax.lax.Precision.HIGHEST
_CHUNK = 64        # tokens a WY block
_SUB = 16          # tokens whose channel decays are taken pair by pair
# the largest exponent a key's scaling may take: a sub-block's own
# cumulative decay, _SUB tokens at a lower bound of -5 a token
_EXP_CAP = 80.0


def recurrent(q, k, v, g, beta, state):
    """ONE token of the gated delta rule: q, k [A, H, Dk], v [A, H, Dv],
    g [A, H, Dk] (a channel) or [A, H] (a head), beta [A, H], state
    [A, H, Dk, Dv] f32 -> (o [A, H, Dv], the new state).  A row with
    g = 0 and beta = 0 keeps its state.  As written S is read once for
    both products and written once; the program XLA makes of it for the
    chip passes over EVERY row's state, live or not, some three times (two
    fused reads and a write).  So the decode step on a TPU takes
    ops.kda_step.kda_live_step where the geometry lets it
    (`moves_live_states`), which moves the live slots' state once in and
    once out; this stays the form of every other backend and width (the
    CPU's tests, a `tiny` preset), an admit's or a chunk's lone token, and
    the kernel's oracle."""
    fade = jnp.exp(g)[..., None] if g.ndim == k.ndim \
        else jnp.exp(g)[..., None, None]
    decayed = state * fade
    seen = jnp.einsum("ahd,ahdv->ahv", k, decayed, precision=_HIGHEST)
    asked = jnp.einsum("ahd,ahdv->ahv", q, decayed, precision=_HIGHEST)
    write = beta[..., None] * (v - seen)
    out = asked + (q * k).sum(axis=-1, keepdims=True) * write
    return out, decayed + k[..., None] * write[..., None, :]


def _pair_products(x, k, cumulative, sub: int, strict: bool):
    """P[t, s] = sum_c x_t[c] k_s[c] exp(G_t[c] - G_s[c]) for s <= t (s < t
    where `strict`), 0 elsewhere, over chunks [..., C, D], as ONE product
    a sub-block of `sub` queries: both sides are scaled to the cumulative
    decay B just before the queries' sub-block, the queries by exp(G_t -
    B) <= 1 and a key by exp(B - G_s), which is <= 1 for every earlier
    sub-block and at most exp(5 x sub) inside the queries' own (float32
    holds e^80; later keys are capped and masked)."""
    c, d = x.shape[-2:]
    blocks = c // sub
    lead = x.shape[:-2]
    xs = x.reshape(lead + (blocks, sub, d))
    gs = cumulative.reshape(lead + (blocks, sub, d))
    before = jnp.concatenate(
        [jnp.zeros(lead + (1, d), cumulative.dtype),
         gs[..., :-1, -1, :]], axis=-2)                      # [.., blocks, D]
    queries = xs * jnp.exp(gs - before[..., None, :])
    keys = k[..., None, :, :] * jnp.exp(jnp.minimum(
        before[..., :, None, :] - cumulative[..., None, :, :],
        _EXP_CAP))                                           # [.., blocks, C, D]
    pairs = jnp.einsum("...imd,...isd->...ims", queries, keys,
                       precision=_HIGHEST).reshape(lead + (c, c))
    return jnp.where(_kept(c, strict), pairs, 0.0)


def _kept(c: int, strict: bool):
    order = jnp.arange(c)
    return order[:, None] > order[None, :] if strict \
        else order[:, None] >= order[None, :]


def _pair_table(x, k, cumulative, strict: bool):
    """The same P for ONE decay a head, cumulative [..., C, 1]: the plain
    product times exp(G_t - G_s), the exponent held at 0 where the pair
    is dropped (it is positive there, and only there)."""
    c = x.shape[-2]
    keep = _kept(c, strict)
    table = jnp.exp(jnp.where(
        keep, cumulative - jnp.swapaxes(cumulative, -1, -2), 0.0))
    pairs = jnp.einsum("...td,...sd->...ts", x, k, precision=_HIGHEST)
    return jnp.where(keep, pairs * table, 0.0)


def chunked(q, k, v, g, beta, state, chunk: int = _CHUNK, sub: int = _SUB):
    """The gated delta rule over T tokens in its chunked (WY) form: q, k
    [A, T, H, Dk], v [A, T, H, Dv], g [A, T, H, Dk] (a channel) or
    [A, T, H] (a head), all f32, beta [A, T, H], state [A, H, Dk, Dv] f32
    -> (o [A, T, H, Dv], the state after the last token).  Equals T calls
    of `recurrent`.  A position with beta = 0 and g = 0 changes nothing.
    T is padded to whole chunks with such positions."""
    a, t, heads, _ = k.shape
    dv = v.shape[-1]
    by_head = g.ndim < k.ndim
    if by_head:
        g = g[..., None]
    c = min(chunk, -(-t // sub) * sub) if t > sub else t
    m = min(sub, c)
    pad = -t % c
    if pad:
        q, k, v, g = (jnp.pad(z, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for z in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (t + pad) // c

    def split(z):                       # [A, T, H, *] -> [N, A, H, C, *]
        return z.reshape(a, n, c, heads, -1).transpose(1, 0, 3, 2, 4)

    q, k, v, g = split(q), split(k), split(v), split(g)
    beta = split(beta[..., None])                            # [N, A, H, C, 1]
    total = jnp.cumsum(g, axis=-2)                           # G_t, inclusive
    if by_head:
        kk = _pair_table(k, k, total, strict=True)
        qk = _pair_table(q, k, total, strict=False)
    else:
        kk = _pair_products(k, k, total, m, strict=True)
        qk = _pair_products(q, k, total, m, strict=False)
    system = jnp.eye(c, dtype=jnp.float32) + beta * kk
    solved = jax.scipy.linalg.solve_triangular(
        system, beta * jnp.concatenate([v, k * jnp.exp(total)], axis=-1),
        lower=True, unit_diagonal=True)
    writes, reads = solved[..., :dv], solved[..., dv:]
    asks = q * jnp.exp(total)
    last = total[..., -1:, :]                                # G_C
    keeps = k * jnp.exp(last - total)

    def one(state, xs):
        writes, reads, asks, qk, keeps, last = xs
        u = writes - jnp.einsum("ahcd,ahdv->ahcv", reads, state,
                                precision=_HIGHEST)
        out = jnp.einsum("ahcd,ahdv->ahcv", asks, state,
                         precision=_HIGHEST) + \
            jnp.einsum("ahcs,ahsv->ahcv", qk, u, precision=_HIGHEST)
        state = state * jnp.exp(last)[..., 0, :, None] + \
            jnp.einsum("ahcd,ahcv->ahdv", keeps, u, precision=_HIGHEST)
        return state, out

    state, out = jax.lax.scan(one, state,
                              (writes, reads, asks, qk, keeps, last))
    out = out.transpose(1, 0, 3, 2, 4).reshape(a, n * c, heads, dv)
    return out[:, :t], state
