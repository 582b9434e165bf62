# Hybrid decoder of Mamba-2 state-space layers (arXiv:2405.21060) and
# grouped-query attention, nine recurrent layers in ten (ISSUE 45: granite-
# 4.0-h-micro, `model_type` granitemoehybrid with no expert anywhere).
#
#   mamba  u the block's input [dim].  [z | xBC | dt~] = W_in u (widths
#          H x P | H x P + 2 N | H);  xBC through a causal depthwise
#          convolution of `conv` taps WITH bias, then SiLU; split x (H heads
#          of P) | B (N) | C (N): ONE B and ONE C for every head (a single
#          group);  dt = softplus(dt~ + dt_bias), A = -exp(A_log), a head;
#          S [P, N] float32 a head:
#              S <- exp(dt A) S + dt x B^T;   y = S C + D x
#          out = W_out (w * rms_{H x P}(y * silu(z))): the gate FIRST, then
#          ONE norm over all H x P channels.  The layer keeps NO row a
#          token: a slot holds S and the convolution's last conv - 1 inputs.
#          In ops/kda_step's terms k = B, q = C, v = dt x, g = dt A, and S
#          lies as [N, H x P] a slot, the heads side by side on the lanes.
#   attn   q, k, v = W u (Hq / Hkv / Hkv heads of D), no bias, NO rotary,
#          causal softmax of `attention_multiplier` q . k, W_o.
#   block  x_0 = embedding_multiplier E[token];  h = x + r mix(rms(x)),
#          out = h + r mlp(rms(h)), r = residual_multiplier, mlp a SwiGLU;
#          logits = E rms(x) / logits_scaling (the head IS the embedding).
#
# Through the paged decoder the Mamba layers' S and convolution tail are SLOT
# STATE (serving_paged.SlotState) and the attention layers keep ONE pool leaf
# a layer whose row is a K/V head's V in lanes [0, D) and its K in lanes
# [D, 2 D): at D = 64 a row of whole lanes, which the SHARED paged kernel
# walks as it walks a latent pool (`walks` "kernel", ops/paged_attention:
# the query padded with zeros over V's lanes scores 0 . V + q . K, the
# weighted rows' leading D lanes are the result), where a pool of 64-wide K
# and V leaves would fall to that kernel's table body (ISSUE 45, route 1).
# The decode step's recurrence is ops/kda_step's kernel in its plain rule on
# the chip (`step_kernel`) and `ssm_step` everywhere else; an admit's and a
# chunk's is ops/ssm_chunk's kernel, one call a layer over the state as the
# pool keeps it, on the chip where the heads tile (`_scan_kernel`, ISSUE 46)
# and `ssm_chunked`, in XLA, elsewhere.

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..ops.kda_step import kda_live_step, moves_live_states
from ..ops.paged_attention import paged_decode_attention, walks_live_blocks
from ..ops.ssm_chunk import scans_ssm_chunks, ssm_chunk_scan
from . import layers as L
# what any model with slot state beside a pool without positions shares
from .gated_delta import _extend_prepare, _rope, _zero_state
from .llama import (SCOPE_ATTN_CORE, SCOPE_ATTN_PROJ, SCOPE_HEAD,
                    SCOPE_KV_VIEW, SCOPE_MLP, _swiglu)

__all__ = ["SsmHybridConfig", "SSM_HYBRID_PRESETS", "ssm_hybrid_init",
           "ssm_hybrid_forward", "SSM_HYBRID_COUNTERS", "SCOPE_SSM_PROJ",
           "SCOPE_SSM_CONV", "SCOPE_SSM_STATE", "SCOPE_SSM_SCAN", "ssm_step",
           "ssm_chunked", "ssm_plain"]

SCOPE_SSM_PROJ = "aiko.ssm_proj"     # W_in and W_out
SCOPE_SSM_CONV = "aiko.ssm_conv"     # convolution, SiLU, dt, gate, norm
SCOPE_SSM_STATE = "aiko.ssm_state"   # the recurrence over S in the step
SCOPE_SSM_SCAN = "aiko.ssm_scan"     # the chunked form in admit and extend

# what a decode step counts, over the Mamba layers: the slot states S the
# token changed (the slots that decoded: what the kernel moves, once in and
# once out) and those the layers hold (every slot)
SSM_HYBRID_COUNTERS = ("ssm_states_moved", "ssm_states_held")

_HIGHEST = jax.lax.Precision.HIGHEST
# tokens a chunk of the chunked form: the result does not depend on it; the
# tables are [H, C, C] a row (4 MB at 128 and the published 64 heads)
_CHUNK = 128


@dataclass(frozen=True)
class SsmHybridConfig:
    vocab: int = 100352
    dim: int = 2048
    layer_types: tuple = (("mamba",) * 5 + ("attention",) +
                          ("mamba",) * 4) * 4
    ffn_dim: int = 8192
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    ssm_heads: int = 64              # mamba_n_heads
    ssm_head_dim: int = 64           # mamba_d_head
    ssm_state: int = 128             # mamba_d_state
    conv_width: int = 4              # mamba_d_conv
    norm_eps: float = 1e-5           # rms_norm_eps
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    max_seq_len: int = 131072
    dtype: object = jnp.float32

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.ssm_inner + 2 * self.ssm_state

    @property
    def layer_cache_leaves(self) -> tuple:
        """Layer by layer, (heads, lanes, tokens a row) of each leaf: an
        attention layer ONE leaf whose row is a head's V then its K, a
        Mamba layer none."""
        row = ((self.num_kv_heads, 2 * self.head_dim, 1),)
        return tuple(row if kind == "attention" else ()
                     for kind in self.layer_types)

    @property
    def slot_state(self) -> tuple:
        """Layer by layer, (shape, dtype) of what a SLOT holds: a Mamba
        layer its state S [N, H x P], the heads side by side, and the
        convolution's tail, its conv-1 positions side by side on the lanes
        (layers.conv_tail); an attention layer nothing."""
        mamba = (((self.ssm_state, self.ssm_inner), jnp.float32),
                 (((self.conv_width - 1) * self.conv_channels,),
                  self.dtype))
        return tuple(mamba if kind == "mamba" else ()
                     for kind in self.layer_types)

    def paged_model(self):
        return _paged_model()


SSM_HYBRID_PRESETS = {
    # every mechanism at a size a CPU test holds: a pattern that is not
    # periodic, 6 heads (no multiple of 8) of 8 that share B and C of 16, a
    # K/V group of 4
    "tiny": SsmHybridConfig(
        vocab=256, dim=64,
        layer_types=("mamba", "attention", "mamba", "mamba", "mamba",
                     "mamba", "attention", "mamba"),
        ffn_dim=128, num_heads=8, num_kv_heads=2, head_dim=8, ssm_heads=6,
        ssm_head_dim=8, ssm_state=16, attention_multiplier=0.125,
        max_seq_len=128),
}


# -- parameters ------------------------------------------------------------------

def _lin(key, fan_in: int, fan_out: int, dtype):
    return L.linear_init(key, fan_in, fan_out, bias=False, dtype=dtype)


def _mamba_init(key, config: SsmHybridConfig):
    keys = jax.random.split(key, 6)
    dim, dtype, heads = config.dim, config.dtype, config.ssm_heads
    inner, channels = config.ssm_inner, config.conv_channels
    return {"in": _lin(keys[0], dim, inner + channels + heads, dtype),
            "conv": {"w": (jax.random.normal(
                keys[1], (config.conv_width, channels)) *
                config.conv_width ** -0.5).astype(dtype),
                "b": jnp.zeros((channels,), dtype)},
            "dt_bias": jax.random.normal(keys[2], (heads,)) - 2.0,
            "a_log": jax.random.uniform(keys[3], (heads,), jnp.float32,
                                        -1.0, 1.0),
            "d": jnp.ones((heads,), jnp.float32),
            "norm": L.rms_norm_init(inner, dtype),
            "out": _lin(keys[4], inner, dim, dtype)}


def _attn_init(key, config: SsmHybridConfig):
    keys = jax.random.split(key, 4)
    dim, dtype, d = config.dim, config.dtype, config.head_dim
    return {"q": _lin(keys[0], dim, config.num_heads * d, dtype),
            "k": _lin(keys[1], dim, config.num_kv_heads * d, dtype),
            "v": _lin(keys[2], dim, config.num_kv_heads * d, dtype),
            "o": _lin(keys[3], config.num_heads * d, dim, dtype)}


def _layer_init(key, config: SsmHybridConfig, index: int):
    keys = jax.random.split(key, 4)
    dim, dtype = config.dim, config.dtype
    layer = {"ln_attn": L.rms_norm_init(dim, dtype),
             "ln_mlp": L.rms_norm_init(dim, dtype),
             "gate": _lin(keys[1], dim, config.ffn_dim, dtype),
             "up": _lin(keys[2], dim, config.ffn_dim, dtype),
             "down": _lin(keys[3], config.ffn_dim, dim, dtype)}
    if config.layer_types[index] == "mamba":
        return layer | {"mamba": _mamba_init(keys[0], config)}
    return layer | {"attn": _attn_init(keys[0], config)}


def ssm_hybrid_init(key, config: SsmHybridConfig):
    keys = jax.random.split(key, config.num_layers + 1)
    return {"embed": L.embedding_init(keys[0], config.vocab, config.dim,
                                      config.dtype),
            "layers": [_layer_init(keys[i + 1], config, i)
                       for i in range(config.num_layers)],
            "ln_out": L.rms_norm_init(config.dim, config.dtype)}


# -- the recurrence, three forms ---------------------------------------------------
# x [.., H, P] f32 the heads' inputs, dt [.., H] f32 (0 at a position that
# is not live: it then neither decays nor writes), b, c [.., N] f32 the one
# key and query of every head, a [H] = -exp(A_log), state [A, N, H x P] f32
# as the pool keeps it.  The skip D x is the caller's.

def _lanes(per_head, width: int):
    """[.., H] -> [.., H x P]: a head's number over its lanes."""
    return jnp.repeat(per_head, width, axis=-1)


def ssm_step(x, dt, b, c, a, state):
    """ONE token: x [A, H, P], dt [A, H], b, c [A, N] -> (y [A, H, P], the
    new state).  XLA's program passes over EVERY row's state; on a TPU the
    decode step takes ops.kda_step.kda_live_step's plain rule where the
    geometry lets it, which moves the live slots' states alone."""
    rows, heads, width = x.shape
    state = state * _lanes(jnp.exp(dt * a), width)[:, None, :] + \
        b[:, :, None] * (dt[..., None] * x).reshape(rows, 1, heads * width)
    out = jnp.einsum("an,anl->al", c, state, precision=_HIGHEST)
    return out.reshape(rows, heads, width), state


def ssm_plain(x, dt, b, c, a, state):
    """T tokens as T calls of `ssm_step` under `lax.scan`: the oracle of the
    chunked form.  x [A, T, H, P] -> (y [A, T, H, P], the state after)."""
    def token(state, xs):
        out, state = ssm_step(*xs, a, state)
        return state, out

    state, out = jax.lax.scan(token, state, tuple(
        jnp.moveaxis(z, 1, 0) for z in (x, dt, b, c)))
    return jnp.moveaxis(out, 0, 1), state


def ssm_chunked(x, dt, b, c, a, state, chunk: int = _CHUNK):
    """T tokens in chunks of `chunk`: with a_t = dt_t A and c_t its running
    sum inside a chunk (every exponent a difference c_t - c_s <= 0: no cap)

        y_t = exp(c_t) S_in C_t + sum_{s<=t} exp(c_t - c_s) (C_t . B_s) dt_s x_s
        S_out = exp(c_Q) S_in + sum_s exp(c_Q - c_s) dt_s x_s B_s^T

    C B^T is ONE [Q, Q] product a chunk for all the heads.  Equals
    `ssm_plain`.  T is padded to whole chunks with positions of dt = 0."""
    rows, t, heads, width = x.shape
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, b, c = (jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) *
                               (z.ndim - 2)) for z in (x, dt, b, c))
    n = (t + pad) // q

    def split(z):                            # [A, T, ..] -> [N, A, Q, ..]
        return jnp.moveaxis(z.reshape((rows, n, q) + z.shape[2:]), 1, 0)

    order = jnp.arange(q)
    kept = order[:, None] >= order[None, :]

    def one(state, xs):
        x, dt, b, c = xs                     # [A, Q, H, P], [A, Q, H], [A, Q, N]
        total = jnp.cumsum(dt * a, axis=1)   # c_t, inclusive      [A, Q, H]
        write = dt[..., None] * x            # dt_s x_s            [A, Q, H, P]
        pairs = jnp.einsum("atn,asn->ats", c, b, precision=_HIGHEST)
        table = jnp.exp(jnp.where(
            kept[None, :, :, None],
            total[:, :, None, :] - total[:, None, :, :], -jnp.inf))
        inside = jnp.einsum("atsh,ashp->athp", pairs[..., None] * table,
                            write, precision=_HIGHEST)
        held = state.reshape(rows, -1, heads, width)         # [A, N, H, P]
        before = jnp.einsum("atn,anhp->athp", c, held, precision=_HIGHEST) \
            * jnp.exp(total)[..., None]
        last = total[:, -1]                                   # c_Q [A, H]
        keeps = write * jnp.exp(last[:, None] - total)[..., None]
        held = held * jnp.exp(last)[:, None, :, None] + jnp.einsum(
            "asn,ashp->anhp", b, keeps, precision=_HIGHEST)
        return held.reshape(state.shape), inside + before

    state, out = jax.lax.scan(one, state, tuple(map(split, (x, dt, b, c))))
    out = jnp.moveaxis(out, 0, 1).reshape(rows, n * q, heads, width)
    return out[:, :t], state


# -- the Mamba layer ---------------------------------------------------------------

def _mamba_inputs(mamba, config: SsmHybridConfig, x, tail, live):
    """x [A, T, dim], tail [A, (conv-1) x channels] the convolution's
    inputs before position 0 of x, live [A, T] -> the heads' inputs [A, T,
    H, P], dt [A, T, H] (0 where not live), B, C [A, T, N], all f32, the
    gate z [A, T, H x P] f32, and the new tail: the inputs of the last
    conv-1 LIVE positions (live positions lead each row)."""
    heads, width, n = config.ssm_heads, config.ssm_head_dim, config.ssm_state
    inner = config.ssm_inner
    rows, t, _ = x.shape
    with jax.named_scope(SCOPE_SSM_PROJ):
        z, pre, rate = jnp.split(L.linear(mamba["in"], x),
                                 [inner, inner + config.conv_channels],
                                 axis=-1)
    with jax.named_scope(SCOPE_SSM_CONV):
        mixed, tail = L.conv_tail(pre, tail, mamba["conv"]["w"],
                                  mamba["conv"]["b"], live)
        inputs, b, c = jnp.split(mixed, [inner, inner + n], axis=-1)
        dt = jax.nn.softplus(rate.astype(jnp.float32) +
                             mamba["dt_bias"].astype(jnp.float32)) * live[
            :, :, None]
    return (inputs.reshape(rows, t, heads, width), dt, b, c,
            z.astype(jnp.float32), tail)


def _mamba_output(mamba, config: SsmHybridConfig, out, inputs, gate, dtype):
    """out, inputs [A, T, H, P] f32 -> [A, T, dim]: the skip D x, the gate,
    then ONE norm over all the channels, W_out."""
    rows, t = out.shape[:2]
    with jax.named_scope(SCOPE_SSM_CONV):
        out = out + mamba["d"].astype(jnp.float32)[:, None] * inputs
        gated = out.reshape(rows, t, -1) * jax.nn.silu(gate)
        normed = gated * jax.lax.rsqrt(
            jnp.mean(gated * gated, axis=-1, keepdims=True)
            + config.norm_eps) * mamba["norm"]["scale"].astype(jnp.float32)
    with jax.named_scope(SCOPE_SSM_PROJ):
        return L.linear(mamba["out"], normed.astype(dtype))


def _mamba_block(layer, config: SsmHybridConfig, x, state, live,
                 live_only: bool = False):
    """A Mamba layer's token mixing over a block x [A, T, dim] from the slot
    state (S [A, N, H x P], tail): -> (out [A, T, dim], the state after the
    block's live positions).  `live_only` (a block of one token): the kernel
    that moves the state of the live rows and no other."""
    mamba = layer["mamba"]
    memory, tail = state
    inputs, dt, b, c, gate, tail = _mamba_inputs(mamba, config, x, tail, live)
    a = -jnp.exp(mamba["a_log"].astype(jnp.float32))
    if x.shape[1] == 1:
        with jax.named_scope(SCOPE_SSM_STATE):
            if live_only:
                out, memory = kda_live_step(
                    c[:, 0], b[:, 0], dt[:, 0, :, None] * inputs[:, 0],
                    dt[:, 0] * a, None, memory, live[:, 0])
            else:
                out, memory = ssm_step(inputs[:, 0], dt[:, 0], b[:, 0],
                                       c[:, 0], a, memory)
        out = out[:, None]
    else:
        with jax.named_scope(SCOPE_SSM_SCAN):
            scan = ssm_chunk_scan if _scan_kernel(
                config, jax.default_backend() != "tpu") else ssm_chunked
            out, memory = scan(inputs, dt, b, c, a, memory)
    return (_mamba_output(mamba, config, out, inputs, gate, x.dtype),
            (memory, tail))


# -- the attention layer -----------------------------------------------------------

def _attn_project(layer, config: SsmHybridConfig, x):
    """x [A, T, dim] -> q [A, Hq, T, D], k, v [A, Hkv, T, D]: no rotary."""
    attn = layer["attn"]
    return (L._split_heads(L.linear(attn["q"], x), config.num_heads),
            L._split_heads(L.linear(attn["k"], x), config.num_kv_heads),
            L._split_heads(L.linear(attn["v"], x), config.num_kv_heads))


def _pool_rows(k, v):
    """A K/V head's row of the pool: V's lanes, then K's."""
    return jnp.concatenate([v, k], axis=-1)


def _softmax_attention(config: SsmHybridConfig, q, rows, mask):
    """q [A, Hq, C, D] over pool rows [A, Hkv, T, 2 D] where mask [A, 1, 1
    or C, T]; a K/V head serves a group of Hq / Hkv query heads."""
    a, _, c, d = q.shape
    num_kv = config.num_kv_heads
    grouped = q.reshape(a, num_kv, -1, c, d)
    scores = jnp.einsum("akgcd,aktd->akgct", grouped, rows[..., d:],
                        preferred_element_type=jnp.float32) * \
        config.attention_multiplier
    weights = jax.nn.softmax(jnp.where(mask[:, :, None], scores, -1e30),
                             axis=-1)
    out = jnp.einsum("akgct,aktd->akgcd", weights.astype(rows.dtype),
                     rows[..., :d], preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


def _attn_block(layer, config: SsmHybridConfig, x, prefix=None):
    """The attention layer over a block x [A, C, dim]: causal among its own
    positions and, where `prefix` = (pool rows [A, Hkv, P, 2 D], mask [A,
    P]) is given (an extend), after the pool's rows.  -> (out [A, C, dim],
    the block's pool rows [A, Hkv, C, 2 D])."""
    with jax.named_scope(SCOPE_ATTN_PROJ):
        q, k, v = _attn_project(layer, config, x)
        own = _pool_rows(k, v)
    c = x.shape[1]
    with jax.named_scope(SCOPE_ATTN_CORE):
        rows = own
        mask = jnp.tril(jnp.ones((c, c), bool))[None, None]
        if prefix is not None:
            rows = jnp.concatenate([prefix[0], own], axis=2)
            mask = jnp.concatenate(
                [jnp.broadcast_to(prefix[1][:, None, None, :],
                                  (x.shape[0], 1, c, prefix[1].shape[1])),
                 jnp.broadcast_to(mask, (x.shape[0], 1, c, c))], axis=-1)
        out = _softmax_attention(config, q, rows, mask)
    with jax.named_scope(SCOPE_ATTN_PROJ):
        return L.linear(layer["attn"]["o"], L._merge_heads(out)), (own,)


def _attn_step(layer, config: SsmHybridConfig, kernel: bool, x, tables,
               leaf, view, side, entry_lengths, lengths, step_index,
               entry_active):
    """The attention layer in a decode step, x [S, 1, dim]: the slot's pool
    rows before the round (`kernel`: read by ops/paged_attention, which
    walks each slot's live blocks of the ONE leaf as a latent pool's; else
    the gathered view) and the round's own in the side buffer, as
    serving._slot_attention_block masks them."""
    d, num_kv = config.head_dim, config.num_kv_heads
    with jax.named_scope(SCOPE_ATTN_PROJ):
        q, k, v = _attn_project(layer, config, x)
        side = jax.lax.dynamic_update_slice_in_dim(
            side, _pool_rows(k, v), step_index, axis=2)
    at = jnp.arange(side.shape[2])
    side_valid = (at[None] <= step_index) & (
        at[None] < (lengths - entry_lengths + 1)[:, None])       # [S, P]
    with jax.named_scope(SCOPE_ATTN_CORE):
        if kernel:
            # the query scores 0 . V + q . K over the whole row; a slot
            # that was not live at round entry walks nothing: its stale
            # length may point anywhere
            grouped = q.reshape(q.shape[0], num_kv, -1, d)
            out = paged_decode_attention(
                jnp.concatenate([jnp.zeros_like(grouped), grouped], axis=-1),
                leaf, None, tables, side, side[..., :d], side_valid[:, None],
                jnp.where(entry_active, entry_lengths, 0),
                groups=config.num_heads // num_kv,
                scale=config.attention_multiplier)
            out = out.reshape(q.shape).astype(x.dtype)
        else:
            held = jnp.arange(view.shape[2])[None] < entry_lengths[:, None]
            out = _softmax_attention(
                config, q, jnp.concatenate([view, side], axis=2),
                jnp.concatenate([held, side_valid], axis=1)[:, None, None])
    with jax.named_scope(SCOPE_ATTN_PROJ):
        return L.linear(layer["attn"]["o"], L._merge_heads(out)), side


# -- whole passes ----------------------------------------------------------------

def _mix_scope(layer) -> str:
    return SCOPE_SSM_CONV if "mamba" in layer else SCOPE_ATTN_PROJ


def _before(layer, config: SsmHybridConfig, x):
    with jax.named_scope(_mix_scope(layer)):
        return L.rms_norm(layer["ln_attn"], x, config.norm_eps)


def _after(layer, config: SsmHybridConfig, x, mixed):
    """The block around its token mixing: h = x + r mixed, out = h + r
    mlp(rms(h))."""
    r = config.residual_multiplier
    with jax.named_scope(_mix_scope(layer)):
        x = x + (mixed * r).astype(x.dtype)
    with jax.named_scope(SCOPE_MLP):
        fed = _swiglu(layer, L.rms_norm(layer["ln_mlp"], x, config.norm_eps))
        return x + (fed * r).astype(x.dtype)


def _embed(params, config: SsmHybridConfig, tokens):
    return _residual_in(config, L.embedding(params["embed"], tokens))


def _logits(params, config: SsmHybridConfig, hidden):
    """The head is the embedding: f32 logits, as layers.linear_logits."""
    return jnp.einsum("...d,vd->...v", hidden, params["embed"]["table"],
                      preferred_element_type=jnp.float32) / \
        config.logits_scaling


def _block_layer(layer, config: SsmHybridConfig, x, live, state,
                 prefix=None):
    """One layer over a block of tokens x [A, C, dim]: -> (x, the rows of
    its pool leaf or (), the slot state after or ())."""
    normed = _before(layer, config, x)
    if "mamba" in layer:
        mixed, state = _mamba_block(layer, config, normed, state, live)
        rows = ()
    else:
        mixed, rows = _attn_block(layer, config, normed, prefix)
    return _after(layer, config, x, mixed), rows, state


def ssm_hybrid_hidden(params, config: SsmHybridConfig, tokens, live=None):
    """tokens [A, T] from position 0 -> (hidden after the last norm [A, T,
    dim], per layer the rows of its pool leaf, per layer the slot state
    after each row's live positions)."""
    if live is None:
        live = jnp.ones(tokens.shape, bool)
    x = _embed(params, config, tokens)
    rows, states = [], []
    for layer, state in zip(params["layers"],
                            _zero_state(config, tokens.shape[0])):
        x, own, state = _block_layer(layer, config, x, live, state)
        rows.append(own)
        states.append(state)
    with jax.named_scope(SCOPE_HEAD):
        return L.rms_norm(params["ln_out"], x, config.norm_eps), rows, states


def ssm_hybrid_forward(params, config: SsmHybridConfig, tokens):
    """Teacher-forced full-sequence forward: tokens [A, T] -> f32 logits
    [A, T, vocab]."""
    hidden, _, _ = ssm_hybrid_hidden(params, config, tokens)
    return _logits(params, config, hidden)


# -- as a PagedModel (what serving_paged's builders call) ------------------------

def _step_argmax(params, config: SsmHybridConfig, token_block, attend,
                 live):
    """The decode step's pass over its [S, 1] block: `attend(i, layer,
    normed)` is every layer's token mixing (the builder hands it the layer's
    leaf, side rows and slot state)."""
    x = _embed(params, config, token_block)
    for i, layer in enumerate(params["layers"]):
        x = _after(layer, config, x,
                   attend(i, layer, _before(layer, config, x)))
    with jax.named_scope(SCOPE_HEAD):
        logits = _logits(params, config, L.rms_norm(params["ln_out"], x,
                                                    config.norm_eps))
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tokens, jnp.zeros((len(SSM_HYBRID_COUNTERS),), jnp.int32)


def _state_kernel(config: SsmHybridConfig, interpret: bool) -> bool:
    return moves_live_states(config.ssm_heads, config.ssm_state, interpret,
                             value_dim=config.ssm_head_dim, by_head=True)


def _scan_kernel(config: SsmHybridConfig, interpret: bool) -> bool:
    """Whether a prompt's piece runs the chunked form as ops/ssm_chunk's
    kernel: on the chip, where the heads tile.  Read off the geometry at
    trace time; the interpreter is never taken unasked."""
    return not interpret and scans_ssm_chunks(
        config.ssm_heads, config.ssm_head_dim, config.ssm_state)


def _walks(config: SsmHybridConfig, kv_int8: bool,
           interpret: bool) -> str | None:
    """The pool's row is a head's V and K side by side: whole lanes where
    the head is 64 wide."""
    return "kernel" if walks_live_blocks(2 * config.head_dim, kv_int8,
                                         interpret) else None


def _step_attention(kernel: bool):
    """A layer's token mixing in the decode step.  `kernel` (the decoder's
    `step_kernel`: on a TPU, weights and state on one device, nothing else
    asked for) is true for BOTH of this model's reasons at once, as
    models/gated_delta's is: an attention layer then reads its slots'
    blocks through ops/paged_attention's walk (else it attends the gathered
    view), a Mamba layer takes ops/kda_step's kernel over the slots that
    decode where its geometry lets it (else the recurrence over every
    slot)."""

    def attend(tables, layer, config, x, cos, sin, leaves, views, sides,
               entry_lengths, lengths, step_index, entry_active, state,
               active):
        interpret = jax.default_backend() != "tpu"
        if "mamba" in layer:
            # a slot that is not live neither decays nor writes, and its
            # convolution tail stays: no pass of its own over the state
            out, state = _mamba_block(
                layer, config, x, state, active[:, None],
                live_only=kernel and _state_kernel(config, interpret))
            return out, sides, state, jnp.stack(
                [active.sum(), active.size]).astype(jnp.int32)
        out, side = _attn_step(
            layer, config, kernel, x, tables, leaves[0],
            views and views[0], sides[0], entry_lengths, lengths,
            step_index, entry_active)
        return out, [side], (), None

    return attend


def _prefill(params, config: SsmHybridConfig, prompts, valid, true_lens):
    live = valid[:, None] & (jnp.arange(prompts.shape[1])[None] <
                             true_lens[:, None])
    return ssm_hybrid_hidden(params, config, prompts, live)


def _extend_layer(kernel: bool):
    """A layer over a prompt's chunk.  An attention layer reads its prefix
    as a gathered view of the rows' tables, as a dense model's extend does;
    the kernel, where it was asked for, is the decode step's only."""

    def extend_layer(layer, config, x, cos, sin, leaves, ctx, prepared,
                     state):
        prefix = None
        if "mamba" not in layer:
            from ..serving_paged import _slice_time
            with jax.named_scope(SCOPE_KV_VIEW):
                prefix = (_slice_time(
                    L.gather_paged_kv(leaves[0], ctx["tables_rows"]),
                    ctx["t_cap"]), prepared["before"])
        return _block_layer(layer, config, x, prepared["live"], state,
                            prefix)

    return extend_layer


def _residual_in(config: SsmHybridConfig, x):
    """What an extend does to the embedding's rows: the multiplier."""
    return (x * config.embedding_multiplier).astype(config.dtype)


def _final_norm(params, config: SsmHybridConfig, x):
    return L.rms_norm(params["ln_out"], x, config.norm_eps)


@functools.cache
def _paged_model():
    from ..serving_paged import PagedModel
    # the paths slot state is carried through: none beyond the paged
    # decoder itself (no snapshot of S to alias, ship or roll back); no
    # rotary anywhere (`position_embedding_type` "nope": gated_delta's table
    # of one position that nothing reads)
    return PagedModel(
        rope=_rope, token_block_argmax=_step_argmax,
        step_attention=_step_attention, prefill=_prefill,
        extend_prepare=_extend_prepare, extend_layer=_extend_layer,
        walks=_walks, step_kernel=_state_kernel, scan_kernel=_scan_kernel,
        counters=SSM_HYBRID_COUNTERS, supports=frozenset(),
        residual_in=_residual_in, final_norm=_final_norm, head=_logits)
