# Hybrid decoder of Gated DeltaNet layers (arXiv:2412.06464) and full
# attention, three recurrent layers to one attending (ISSUE 40: the language
# model of Olmo-Hybrid-7B, `model_type` olmo_hybrid).
#
#   gdn    u the block's input [dim].  q~, k~, v~ = W_q u, W_k u, W_v u (H
#          heads of Dk, Dk, Dv); a causal depthwise convolution of `conv`
#          taps over every channel of the three, no bias, then SiLU;
#          q = q~ / |q~| x Dk^-0.5, k = k~ / |k~| a head;  beta = 2 sigmoid
#          (W_b u) a head (the 2 is `neg_eigval`: I - beta k k^T reaches
#          eigenvalue -1; 1 without it);  g = -exp(A_log) softplus(W_a u +
#          dt_bias), ONE number a head;  S [Dk, Dv] float32 a head:
#          S' = exp(g) S,  S = S' + k (beta (v - S'^T k))^T,  o = S^T q;
#          y = W_o (rms_Dv(o) * silu(W_g u)), the norm's scale learned.
#          The layer keeps NO row a token: a slot holds S and the
#          convolution's last conv - 1 inputs.
#   full   q, k, v = W_q u, W_k u, W_v u (H heads of D, no grouping), a
#          learned RMSNorm over the WHOLE width of q and of k before the
#          split, NO rotary, causal softmax at D^-0.5, W_o.  K and V rows a
#          token, as a dense model's.
#   block  the reordered norm: h = x + rms(mix(x)), out = h + rms(mlp(h)),
#          mlp a SwiGLU; a final RMSNorm and an untied head.
#
# Through the paged decoder the recurrent layers' S and convolution tail are
# SLOT STATE (serving_paged.SlotState) and the full layers' K and V a pool
# that the SHARED paged kernel walks (`walks` "kernel", ops/paged_attention,
# a group of 1), as a dense model's: the first model with both.  S lies as
# [Dk, H x Dv] a slot, the heads side by side on the lanes (ops/kda_step.py
# says why); the decode step's recurrence is that module's kernel on the
# chip (`step_kernel`) and models/delta_rule.recurrent everywhere else, an
# admit's and a chunk's the chunked form with one decay a head: on the chip
# ops/delta_chunk's kernel, ONE call a layer over the state as the pool
# keeps it (`_scan_kernel`, ISSUE 41), models/delta_rule.chunked elsewhere.

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..ops.delta_chunk import delta_chunk_scan, scans_chunks
from ..ops.kda_step import (heads_apart, heads_side_by_side, kda_live_step,
                            moves_live_states)
from ..ops.paged_attention import paged_decode_attention, walks_live_blocks
from . import delta_rule
from . import layers as L
from .llama import (SCOPE_ATTN_CORE, SCOPE_ATTN_PROJ, SCOPE_HEAD,
                    SCOPE_KV_VIEW, SCOPE_MLP, _swiglu)

__all__ = ["GatedDeltaConfig", "GATED_DELTA_PRESETS", "gated_delta_init",
           "gated_delta_forward", "GATED_DELTA_COUNTERS", "SCOPE_GDN_PROJ",
           "SCOPE_GDN_CONV", "SCOPE_GDN_STATE", "SCOPE_GDN_SCAN"]

SCOPE_GDN_PROJ = "aiko.gdn_proj"     # the six projections and W_o
SCOPE_GDN_CONV = "aiko.gdn_conv"     # convolution, SiLU, norms, gates
SCOPE_GDN_STATE = "aiko.gdn_state"   # the recurrence over S in the step
SCOPE_GDN_SCAN = "aiko.gdn_scan"     # the chunked form in admit and extend

# what a decode step counts, over the recurrent layers: the slot states S
# the token changed (the slots that decoded: what the kernel moves, once in
# and once out) and those the layers hold (every slot)
GATED_DELTA_COUNTERS = ("gdn_states_moved", "gdn_states_held")


@dataclass(frozen=True)
class GatedDeltaConfig:
    vocab: int = 100352
    dim: int = 3840
    layer_types: tuple = ("gdn", "gdn", "gdn", "full") * 8
    ffn_dim: int = 11008
    num_heads: int = 30              # the full layers': heads of head_dim
    head_dim: int = 128
    gdn_heads: int = 30              # linear_num_key_heads = .._value_heads
    key_dim: int = 96                # linear_key_head_dim
    value_dim: int = 192             # linear_value_head_dim
    conv_width: int = 4              # linear_conv_kernel_dim
    neg_eigval: bool = True          # linear_allow_neg_eigval
    norm_eps: float = 1e-6           # rms_norm_eps (layers.rms_norm's own)
    max_seq_len: int = 65536
    dtype: object = jnp.float32

    def __post_init__(self):
        if self.norm_eps != 1e-6:
            raise ValueError("models/layers.rms_norm computes with 1e-6")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def conv_channels(self) -> int:
        return self.gdn_heads * (2 * self.key_dim + self.value_dim)

    @property
    def layer_cache_leaves(self) -> tuple:
        """Layer by layer, (heads, lanes, tokens a row) of each leaf: a
        full layer its K and V rows, a recurrent layer none."""
        kv = (self.num_heads, self.head_dim, 1)
        return tuple((kv, kv) if kind == "full" else ()
                     for kind in self.layer_types)

    @property
    def slot_state(self) -> tuple:
        """Layer by layer, (shape, dtype) of what a SLOT holds: a
        recurrent layer its state S, the heads side by side, and the
        convolution's tail, its conv-1 positions side by side on the lanes
        (layers.conv_tail); a full layer nothing."""
        gdn = (((self.key_dim, self.gdn_heads * self.value_dim),
                jnp.float32),
               (((self.conv_width - 1) * self.conv_channels,), self.dtype))
        return tuple(gdn if kind == "gdn" else ()
                     for kind in self.layer_types)

    def paged_model(self):
        return _paged_model()


GATED_DELTA_PRESETS = {
    # every mechanism at a size a CPU test holds: unequal head sides, a
    # head count that is no multiple of 8, a period and one more layer
    "tiny": GatedDeltaConfig(
        vocab=256, dim=64, layer_types=("gdn", "gdn", "gdn", "full", "gdn"),
        ffn_dim=128, num_heads=4, head_dim=16, gdn_heads=6, key_dim=8,
        value_dim=16, max_seq_len=128),
}


# -- parameters ------------------------------------------------------------------

def _lin(key, fan_in: int, fan_out: int, dtype):
    return L.linear_init(key, fan_in, fan_out, bias=False, dtype=dtype)


def _gdn_init(key, config: GatedDeltaConfig):
    keys = jax.random.split(key, 10)
    dim, dtype, heads = config.dim, config.dtype, config.gdn_heads
    keys_wide, values_wide = heads * config.key_dim, heads * config.value_dim
    return {"q": _lin(keys[0], dim, keys_wide, dtype),
            "k": _lin(keys[1], dim, keys_wide, dtype),
            "v": _lin(keys[2], dim, values_wide, dtype),
            "conv": {"w": (jax.random.normal(
                keys[3], (config.conv_width, config.conv_channels)) *
                config.conv_width ** -0.5).astype(dtype)},
            "a": _lin(keys[4], dim, heads, dtype),
            "a_log": jax.random.uniform(keys[5], (heads,), jnp.float32,
                                        -1.0, 1.0),
            "dt_bias": jax.random.normal(keys[6], (heads,)) - 2.0,
            "b": _lin(keys[7], dim, heads, dtype),
            "g": _lin(keys[8], dim, values_wide, dtype),
            "o_norm": L.rms_norm_init(config.value_dim, dtype),
            "o": _lin(keys[9], values_wide, dim, dtype)}


def _full_init(key, config: GatedDeltaConfig):
    keys = jax.random.split(key, 4)
    dim, dtype = config.dim, config.dtype
    wide = config.num_heads * config.head_dim
    return {"q": _lin(keys[0], dim, wide, dtype),
            "k": _lin(keys[1], dim, wide, dtype),
            "v": _lin(keys[2], dim, wide, dtype),
            "o": _lin(keys[3], wide, dim, dtype),
            "q_norm": L.rms_norm_init(wide, dtype),
            "k_norm": L.rms_norm_init(wide, dtype)}


def _layer_init(key, config: GatedDeltaConfig, index: int):
    keys = jax.random.split(key, 4)
    dim, dtype = config.dim, config.dtype
    layer = {"ln_attn": L.rms_norm_init(dim, dtype),
             "ln_mlp": L.rms_norm_init(dim, dtype),
             "gate": _lin(keys[1], dim, config.ffn_dim, dtype),
             "up": _lin(keys[2], dim, config.ffn_dim, dtype),
             "down": _lin(keys[3], config.ffn_dim, dim, dtype)}
    if config.layer_types[index] == "gdn":
        return layer | {"gdn": _gdn_init(keys[0], config)}
    return layer | {"attn": _full_init(keys[0], config)}


def gated_delta_init(key, config: GatedDeltaConfig):
    keys = jax.random.split(key, config.num_layers + 2)
    return {"embed": L.embedding_init(keys[0], config.vocab, config.dim,
                                      config.dtype),
            "layers": [_layer_init(keys[i + 1], config, i)
                       for i in range(config.num_layers)],
            "ln_out": L.rms_norm_init(config.dim, config.dtype),
            "lm_head": _lin(keys[-1], config.dim, config.vocab,
                            config.dtype)}


# -- the recurrent layer ---------------------------------------------------------

def _gdn_inputs(gdn, config: GatedDeltaConfig, x, tail, live):
    """x [A, T, dim], tail [A, (conv-1) x channels] the convolution's
    inputs before position 0 of x, live [A, T] -> q, k [A, T, H, Dk], v [A,
    T, H, Dv] f32, g, beta [A, T, H] f32, the output gate [A, T, H x Dv]
    f32, and the new tail: the inputs of the last conv-1 LIVE positions
    (live positions lead each row)."""
    heads, dk, dv = config.gdn_heads, config.key_dim, config.value_dim
    a, t, _ = x.shape
    with jax.named_scope(SCOPE_GDN_PROJ):
        pre = jnp.concatenate([L.linear(gdn[name], x) for name in "qkv"],
                              axis=-1)
        rate = L.linear(gdn["a"], x)
        write = L.linear(gdn["b"], x)
        gate = L.linear(gdn["g"], x)
    with jax.named_scope(SCOPE_GDN_CONV):
        mixed, tail = L.conv_tail(pre, tail, gdn["conv"]["w"], None, live)
        q, k, v = (z.reshape(a, t, heads, -1) for z in jnp.split(
            mixed, [heads * dk, 2 * heads * dk], axis=-1))

        def unit(z):
            return z * jax.lax.rsqrt((z * z).sum(axis=-1, keepdims=True)
                                     + 1e-6)

        q, k = unit(q) * dk ** -0.5, unit(k)
        g = -jnp.exp(gdn["a_log"].astype(jnp.float32)) * jax.nn.softplus(
            rate.astype(jnp.float32) + gdn["dt_bias"].astype(jnp.float32))
        beta = jax.nn.sigmoid(write.astype(jnp.float32)) * (
            2.0 if config.neg_eigval else 1.0)
        gate = jax.nn.silu(gate.astype(jnp.float32))
        # a position that is not live leaves S as it was: no decay, no write
        g = g * live[:, :, None]
        beta = beta * live[:, :, None]
    return q, k, v, g, beta, gate, tail


def _gdn_output(gdn, config: GatedDeltaConfig, out, gate, dtype):
    """out [A, T, H, Dv] f32 -> [A, T, dim]: the norm a head, the gate,
    W_o."""
    a, t = out.shape[:2]
    with jax.named_scope(SCOPE_GDN_CONV):
        normed = out * jax.lax.rsqrt(
            jnp.mean(out * out, axis=-1, keepdims=True) + config.norm_eps) \
            * gdn["o_norm"]["scale"].astype(jnp.float32)
        gated = (normed.reshape(a, t, -1) * gate).astype(dtype)
    with jax.named_scope(SCOPE_GDN_PROJ):
        return L.linear(gdn["o"], gated)


def _gdn_block(layer, config: GatedDeltaConfig, x, state, live,
               live_only: bool = False):
    """A recurrent layer's token mixing over a block x [A, T, dim] from the
    slot state (S [A, Dk, H x Dv], tail): -> (out [A, T, dim], the state
    after the block's live positions).  `live_only` (a block of one
    token): the kernel that moves the state of the live rows and no
    other."""
    gdn, heads = layer["gdn"], config.gdn_heads
    memory, tail = state
    q, k, v, g, beta, gate, tail = _gdn_inputs(gdn, config, x, tail, live)
    if x.shape[1] == 1:
        one = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        with jax.named_scope(SCOPE_GDN_STATE):
            if live_only:
                out, memory = kda_live_step(*one, memory, live[:, 0])
            else:
                out, memory = delta_rule.recurrent(
                    *one, heads_apart(memory, heads))
                memory = heads_side_by_side(memory)
        out = out[:, None]
    else:
        with jax.named_scope(SCOPE_GDN_SCAN):
            if _scan_kernel(config, jax.default_backend() != "tpu"):
                out, memory = delta_chunk_scan(q, k, v, g, beta, memory)
            else:
                out, memory = delta_rule.chunked(q, k, v, g, beta,
                                                 heads_apart(memory, heads))
                memory = heads_side_by_side(memory)
    return _gdn_output(gdn, config, out, gate, x.dtype), (memory, tail)


# -- the full layer --------------------------------------------------------------

def _full_project(layer, config: GatedDeltaConfig, x):
    """x [A, T, dim] -> q, k, v [A, H, T, D]: the norm over the WHOLE
    width of q and of k before the split, no rotary."""
    attn, heads = layer["attn"], config.num_heads
    q = L.rms_norm(attn["q_norm"], L.linear(attn["q"], x), config.norm_eps)
    k = L.rms_norm(attn["k_norm"], L.linear(attn["k"], x), config.norm_eps)
    return (L._split_heads(q, heads), L._split_heads(k, heads),
            L._split_heads(L.linear(attn["v"], x), heads))


def _softmax_attention(config: GatedDeltaConfig, q, k, v, mask):
    """q [A, H, C, D] over k, v [A, H, T, D] where mask [A, 1, C, T]."""
    scores = jnp.einsum("ahcd,ahtd->ahct", q, k,
                        preferred_element_type=jnp.float32) * \
        config.head_dim ** -0.5
    weights = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    return jnp.einsum("ahct,ahtd->ahcd", weights.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _full_block(layer, config: GatedDeltaConfig, x, prefix=None):
    """The full layer over a block x [A, C, dim]: causal among its own
    positions and, where `prefix` = (K rows, V rows [A, H, P, D], mask
    [A, P]) is given (an extend), after the pool's rows.  -> (out [A, C,
    dim], the block's K and V rows [A, H, C, D])."""
    with jax.named_scope(SCOPE_ATTN_PROJ):
        q, k, v = _full_project(layer, config, x)
    c = x.shape[1]
    with jax.named_scope(SCOPE_ATTN_CORE):
        keys, values = k, v
        mask = jnp.tril(jnp.ones((c, c), bool))[None, None]
        if prefix is not None:
            keys = jnp.concatenate([prefix[0], k], axis=2)
            values = jnp.concatenate([prefix[1], v], axis=2)
            mask = jnp.concatenate(
                [jnp.broadcast_to(prefix[2][:, None, None, :],
                                  (x.shape[0], 1, c, prefix[2].shape[1])),
                 jnp.broadcast_to(mask, (x.shape[0], 1, c, c))], axis=-1)
        out = _softmax_attention(config, q, keys, values, mask)
    with jax.named_scope(SCOPE_ATTN_PROJ):
        return L.linear(layer["attn"]["o"], L._merge_heads(out)), (k, v)


def _full_step(layer, config: GatedDeltaConfig, kernel: bool, x, tables,
               leaves, views, sides, entry_lengths, lengths, step_index,
               entry_active):
    """The full layer in a decode step, x [S, 1, dim]: the slot's pool
    rows before the round (`kernel`: read by ops/paged_attention, which
    walks each slot's live blocks where the head is whole lanes; else the
    gathered views) and the round's own in the side buffers, as
    serving._slot_attention_block masks them."""
    k_side, v_side = sides
    with jax.named_scope(SCOPE_ATTN_PROJ):
        q, k, v = _full_project(layer, config, x)
        k_side = jax.lax.dynamic_update_slice_in_dim(k_side, k, step_index,
                                                     axis=2)
        v_side = jax.lax.dynamic_update_slice_in_dim(v_side, v, step_index,
                                                     axis=2)
    at = jnp.arange(k_side.shape[2])
    side_valid = (at[None] <= step_index) & (
        at[None] < (lengths - entry_lengths + 1)[:, None])       # [S, P]
    with jax.named_scope(SCOPE_ATTN_CORE):
        if kernel:
            # a slot that was not live at round entry walks nothing: its
            # stale length may point anywhere
            out = paged_decode_attention(
                q, leaves[0], leaves[1], tables, k_side, v_side,
                side_valid[:, None], jnp.where(entry_active, entry_lengths,
                                               0), groups=1,
                scale=config.head_dim ** -0.5).astype(x.dtype)
        else:
            held = jnp.arange(views[0].shape[2])[None] < \
                entry_lengths[:, None]
            out = _softmax_attention(
                config, q, jnp.concatenate([views[0], k_side], axis=2),
                jnp.concatenate([views[1], v_side], axis=2),
                jnp.concatenate([held, side_valid], axis=1)[:, None, None])
    with jax.named_scope(SCOPE_ATTN_PROJ):
        return (L.linear(layer["attn"]["o"], L._merge_heads(out)),
                (k_side, v_side))


# -- whole passes ----------------------------------------------------------------

def _after(layer, config: GatedDeltaConfig, x, mixed):
    """The block around its token mixing, the reordered norm: h = x +
    rms(mixed), out = h + rms(mlp(h))."""
    with jax.named_scope(SCOPE_GDN_CONV if "gdn" in layer
                         else SCOPE_ATTN_PROJ):
        x = x + L.rms_norm(layer["ln_attn"], mixed, config.norm_eps)
    with jax.named_scope(SCOPE_MLP):
        return x + L.rms_norm(layer["ln_mlp"], _swiglu(layer, x),
                              config.norm_eps)


def _zero_state(config: GatedDeltaConfig, rows: int) -> list:
    return [tuple(jnp.zeros((rows,) + shape, dtype)
                  for shape, dtype in layer)
            for layer in config.slot_state]


def _block_layer(layer, config: GatedDeltaConfig, x, live, state,
                 prefix=None):
    """One layer over a block of tokens x [A, C, dim]: -> (x, the rows of
    each pool leaf or (), the slot state after or ())."""
    if "gdn" in layer:
        mixed, state = _gdn_block(layer, config, x, state, live)
        rows = ()
    else:
        mixed, rows = _full_block(layer, config, x, prefix)
    return _after(layer, config, x, mixed), rows, state


def gated_delta_hidden(params, config: GatedDeltaConfig, tokens, live=None):
    """tokens [A, T] from position 0 -> (hidden after the last norm [A, T,
    dim], per layer the rows of its pool leaves, per layer the slot state
    after each row's live positions)."""
    if live is None:
        live = jnp.ones(tokens.shape, bool)
    x = L.embedding(params["embed"], tokens).astype(config.dtype)
    rows, states = [], []
    for layer, state in zip(params["layers"],
                            _zero_state(config, tokens.shape[0])):
        x, own, state = _block_layer(layer, config, x, live, state)
        rows.append(own)
        states.append(state)
    with jax.named_scope(SCOPE_HEAD):
        return L.rms_norm(params["ln_out"], x, config.norm_eps), rows, states


def gated_delta_forward(params, config: GatedDeltaConfig, tokens):
    """Teacher-forced full-sequence forward: tokens [A, T] -> f32 logits
    [A, T, vocab]."""
    hidden, _, _ = gated_delta_hidden(params, config, tokens)
    return L.linear_logits(params["lm_head"], hidden)


# -- as a PagedModel (what serving_paged's builders call) ------------------------

def _rope(config: GatedDeltaConfig):
    """No rotary anywhere (`rope_theta` null): a table of one position that
    nothing reads, for the builders' signature."""
    return L.rope_frequencies(config.head_dim, 1, 10000.0)


def _step_argmax(params, config: GatedDeltaConfig, token_block, attend,
                 live):
    """The decode step's pass over its [S, 1] block: `attend(i, layer, x)`
    is every layer's token mixing of the block's INPUT (the norm comes
    after it here; the builder hands it the layer's leaves, side rows and
    slot state)."""
    x = L.embedding(params["embed"], token_block).astype(config.dtype)
    for i, layer in enumerate(params["layers"]):
        x = _after(layer, config, x, attend(i, layer, x))
    with jax.named_scope(SCOPE_HEAD):
        logits = L.linear_logits(
            params["lm_head"], L.rms_norm(params["ln_out"], x,
                                          config.norm_eps))
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tokens, jnp.zeros((len(GATED_DELTA_COUNTERS),), jnp.int32)


def _state_kernel(config: GatedDeltaConfig, interpret: bool) -> bool:
    return moves_live_states(config.gdn_heads, config.key_dim, interpret,
                             value_dim=config.value_dim, by_head=True)


def _scan_kernel(config: GatedDeltaConfig, interpret: bool) -> bool:
    """Whether a prompt's piece runs the chunked form as ops/delta_chunk's
    kernel: on the chip, where the heads tile.  Read off the geometry at
    trace time; the interpreter is never taken unasked."""
    return not interpret and scans_chunks(
        config.gdn_heads, config.key_dim, config.value_dim, by_head=True)


def _walks(config: GatedDeltaConfig, kv_int8: bool,
           interpret: bool) -> str | None:
    return "kernel" if walks_live_blocks(config.head_dim, kv_int8,
                                         interpret) else None


def _step_attention(kernel: bool):
    """A layer's token mixing in the decode step.  `kernel` (the decoder's
    `step_kernel`: on a TPU, weights and state on one device, nothing else
    asked for) is true for BOTH of this model's reasons at once: a full
    layer then reads its slots' blocks through ops/paged_attention (else
    it attends the gathered views), a recurrent layer takes ops/kda_step's
    kernel over the slots that decode where its geometry lets it (else the
    recurrence over every slot)."""

    def attend(tables, layer, config, x, cos, sin, leaves, views, sides,
               entry_lengths, lengths, step_index, entry_active, state,
               active):
        interpret = jax.default_backend() != "tpu"
        if "gdn" in layer:
            # a slot that is not live neither decays nor writes, and its
            # convolution tail stays: no pass of its own over the state
            out, state = _gdn_block(
                layer, config, x, state, active[:, None],
                live_only=kernel and _state_kernel(config, interpret))
            return out, sides, state, jnp.stack(
                [active.sum(), active.size]).astype(jnp.int32)
        out, sides = _full_step(
            layer, config, kernel, x, tables, leaves, views, sides,
            entry_lengths, lengths, step_index, entry_active)
        return out, sides, (), None

    return attend


def _prefill(params, config: GatedDeltaConfig, prompts, valid, true_lens):
    live = valid[:, None] & (jnp.arange(prompts.shape[1])[None] <
                             true_lens[:, None])
    return gated_delta_hidden(params, config, prompts, live)


def _extend_prepare(config: GatedDeltaConfig, chunk_len: int, kernel: bool,
                    ctx):
    """Which of the chunk's tokens are real, and which positions of the
    pool lie before each row's chunk."""
    live = ctx["valid"][:, None] & (
        ~ctx["finish"][:, None] |
        (jnp.arange(chunk_len)[None] <= ctx["final_idx"][:, None]))
    before = jnp.arange(ctx["t_cap"])[None] < ctx["offsets"][:, None]
    return {"live": live, "before": before}


def _extend_layer(kernel: bool):
    """A layer over a prompt's chunk.  A full layer reads its prefix as
    gathered views of the rows' tables, as a dense model's extend does;
    the kernel, where it was asked for, is the decode step's only."""

    def extend_layer(layer, config, x, cos, sin, leaves, ctx, prepared,
                     state):
        prefix = None
        if "gdn" not in layer:
            from ..serving_paged import _slice_time
            with jax.named_scope(SCOPE_KV_VIEW):
                prefix = tuple(
                    _slice_time(L.gather_paged_kv(leaf, ctx["tables_rows"]),
                                ctx["t_cap"]) for leaf in leaves) + (
                    prepared["before"],)
        return _block_layer(layer, config, x, prepared["live"], state,
                            prefix)

    return extend_layer


@functools.cache
def _paged_model():
    from ..serving_paged import PagedModel
    # the paths slot state is carried through: none beyond the paged
    # decoder itself (no snapshot of S to alias, ship or roll back)
    return PagedModel(
        rope=_rope, token_block_argmax=_step_argmax,
        step_attention=_step_attention, prefill=_prefill,
        extend_prepare=_extend_prepare, extend_layer=_extend_layer,
        walks=_walks, step_kernel=_state_kernel, scan_kernel=_scan_kernel,
        counters=GATED_DELTA_COUNTERS, supports=frozenset())
