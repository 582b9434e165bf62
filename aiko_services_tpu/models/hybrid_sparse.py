# Hybrid decoder: recurrent (KDA) layers beside sparse-selected latent
# attention, every sublayer inside manifold-constrained hyper-connections
# (ISSUE 33).  What is published for each part: Kimi Delta Attention
# (arXiv:2510.26692), DeepSeek Sparse Attention over MLA
# (DeepSeek-V3.2-Exp), mHC (arXiv:2512.24880), DeepSeek-V3's MLA and
# `noaux_tc` router (models/latent_moe.py, whose expert layer and
# absorption this file calls).
#
#   streams   the residual is X [n, dim] (n = hc_mult).  Around every
#             sublayer F:  x~ = rms(vec X);  pre = sigmoid(a0 x~ Phi_pre +
#             b), post = 2 sigmoid(a1 x~ Phi_post + b), res = Sinkhorn
#             (exp(a2 x~ Phi_res + b)), rounds of row-then-column
#             normalisation;  u = pre . X;  X <- res X + post^T F(rms(u)).
#             The embedding is copied into the n streams; they are summed
#             before the final norm.
#   KDA       q, k, v = silu(causal conv of width 4 over x W_q, x W_k,
#             x W_v); q, k L2-normalised a head, q x D^-0.5; log-decay a
#             channel g = lower_bound x sigmoid(exp(A_log)(x W_f1 W_f2 +
#             dt_bias)) in [lower_bound, 0]; beta = sigmoid(x W_b);
#             S_t = (I - beta k k^T) diag(exp g) S_{t-1} + beta k v^T;
#             o_t = S_t^T q_t; out = W_o (rms_head(o) * sigmoid(x W_g1
#             W_g2)).  The layer keeps NO row a token: a slot holds S
#             [H, D, D] in float32 and the convolution's last 3 inputs.
#   sparse    MLA without rotary: c_q = rms(x W_qa), q = c_q W_qb;
#             c_kv = rms(x W_kva) is the cached row (512 lanes); k, v =
#             c_kv W_kvb.  Indexer: q_I = c_q W_qI (heads of 128), k_I =
#             layernorm(x W_kI), w = x W_w, rotary on the leading lanes;
#             score(t, s) = sum_j w_tj relu(q_I,tj . k_I,s) x (heads x
#             128)^-0.5.  The keys are MEAN-POOLED over each aligned group
#             of `index_pool` positions and only the pooled key is cached
#             (a second leaf, one row every `index_pool` tokens); a query
#             attends the positions of its own still-open group always
#             and the best `index_topk / index_pool - 1` complete groups.
#             The attention itself is absorbed everywhere in the program.
#   ffn       dense SwiGLU with a clamp, or the expert layer of
#             models/latent_moe.py with a correction bias in the choice.
#
# Through the paged decoder the KDA state and the open group's key sum
# are SLOT STATE (serving_paged.SlotState), which step, admit and extend
# take and hand back rewritten.

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..ops.delta_chunk import delta_chunk_scan, scans_chunks
from ..ops.kda_step import _live_ids, kda_live_step, moves_live_states
from . import delta_rule
from . import layers as L
from .latent_moe import (MOE_COUNTERS, absorb_output, absorb_queries,
                         moe_ffn, swiglu)
from .llama import (SCOPE_ATTN_CORE, SCOPE_ATTN_PROJ, SCOPE_HEAD,
                    SCOPE_MLP)

__all__ = ["HybridSparseConfig", "HYBRID_SPARSE_PRESETS",
           "hybrid_sparse_init", "hybrid_sparse_forward", "kda_chunked",
           "kda_recurrent", "mhc_maps", "HYBRID_COUNTERS",
           "SCOPE_KDA_CORE", "SCOPE_DSA_INDEX", "SCOPE_DSA_RELAYOUT",
           "SCOPE_MHC"]

SCOPE_KDA_CORE = "aiko.kda_core"     # conv, gates, scan or recurrence (the
                                     # step's: ops/kda_step.py on the chip)
SCOPE_DSA_INDEX = "aiko.dsa_index"   # indexer projections, scores, top-k
SCOPE_MHC = "aiko.mhc"               # mappings, Sinkhorn, mixing
# inside aiko.attn_core in the step: the latent leaf seen by the whole
# tiles its rows lie in, a bitcast and no operation.  The scope stays so
# that what reads it (`dsa_step_relayout_ms`) reads 0.0, and a view for
# which XLA copies the leaf (by groups it did, once a round) shows there
SCOPE_DSA_RELAYOUT = "aiko.dsa_relayout"

# what a decode step counts: the expert layers' four, then over the
# sparse-attention layers and the slots that decoded the positions that
# were live, those that were attended and the latent rows that the gather
# of the chosen groups fetched for them (a whole tile a group), then over
# the KDA layers the slot states S the token changed (the slots that
# decoded: what the kernel moves, once in and once out) and those the
# layer holds (every slot: what XLA's form of the recurrence passes over),
# then over the sparse-attention layers again the slots the step computed
# for (whole windows of `_STEP_WINDOW`, of the slots live at the round's
# entry) and the slots that decoded in it
_DSA_COUNTERS = ("dsa_positions_live", "dsa_positions_attended",
                 "dsa_rows_fetched")
_KDA_COUNTERS = ("kda_states_moved", "kda_states_held")
_WINDOW_COUNTERS = ("dsa_slots_computed", "dsa_slots_decoding")
HYBRID_COUNTERS = MOE_COUNTERS + _DSA_COUNTERS + _KDA_COUNTERS + \
    _WINDOW_COUNTERS
_OWN_COUNTERS = len(HYBRID_COUNTERS) - len(MOE_COUNTERS)
# where a KDA layer's two and a sparse layer's five lie in the counts
_KDA_AT = tuple(map(HYBRID_COUNTERS.index, _KDA_COUNTERS))
_DSA_AT = tuple(map(HYBRID_COUNTERS.index, _DSA_COUNTERS + _WINDOW_COUNTERS))

_HIGHEST = jax.lax.Precision.HIGHEST
_PREFIX_PIECE = 512    # positions of the prefix an extend attends at once
_TILE_ROWS = 8         # rows of a leaf that lie together in the chip's memory
_STEP_WINDOW = 8       # slots the sparse layer's decode step takes at once


@dataclass(frozen=True)
class HybridSparseConfig:
    vocab: int = 154880
    dim: int = 4096
    layer_types: tuple = ("kda", "kda", "kda", "dsa") * 11 + ("kda",)
    mlp_types: tuple = ("dense",) * 3 + ("sparse",) * 42
    kda_heads: int = 64
    kda_head_dim: int = 128
    conv_width: int = 4              # short_conv_kernel_size
    gate_rank: int = 128             # low-rank width of the two gates
    gate_lower_bound: float = -5.0
    num_heads: int = 64
    q_rank: int = 1536               # q_lora_rank
    kv_rank: int = 512               # kv_lora_rank
    nope_dim: int = 256              # qk_nope_head_dim (no rotary at all)
    v_dim: int = 256                 # v_head_dim
    index_heads: int = 32
    index_dim: int = 128
    index_rope_dim: int = 64
    index_topk: int = 2048           # positions attended at most
    index_pool: int = 4              # index_kpool
    rope_theta: float = 10000.0
    dense_ffn_dim: int = 12288
    expert_ffn_dim: int = 2048
    shared_experts: int = 1
    num_experts: int = 288           # the router's width, always whole
    top_k: int = 8
    routed_scale: float = 2.5
    experts_first: int = 0           # the experts held here:
    experts_held: int = 288          #   [first, first + held)
    swiglu_limit: float = 10.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    norm_eps: float = 1e-5           # rms_norm_eps
    max_seq_len: int = 32768
    dtype: object = jnp.float32

    rope_dim = 0                     # what latent_moe's absorption reads

    def __post_init__(self):
        if _TILE_ROWS % self.index_pool:
            # the step fetches a chosen group by the tile that holds it
            raise ValueError(
                f"index_pool must divide a tile of {_TILE_ROWS} rows, got "
                f"{self.index_pool}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def row_lanes(self) -> int:
        return self.kv_rank          # 512: whole lanes, no pad

    @property
    def softmax_scale(self) -> float:
        return self.nope_dim ** -0.5

    @property
    def top_groups(self) -> int:
        """Complete groups a query attends at most: its own open group
        stands for one of index_topk / index_pool."""
        return self.index_topk // self.index_pool - 1

    @property
    def layer_cache_leaves(self) -> tuple:
        """Layer by layer, (heads, lanes, tokens a row) of each leaf: a
        KDA layer keeps none, a sparse layer its latent row a token and
        one pooled indexer key every `index_pool` tokens."""
        sparse = ((1, self.kv_rank, 1),
                  (1, self.index_dim, self.index_pool))
        return tuple(sparse if kind == "dsa" else ()
                     for kind in self.layer_types)

    @property
    def slot_state(self) -> tuple:
        """Layer by layer, (shape, dtype) of what a SLOT holds: a KDA
        layer its state S and the convolution's tail, its conv-1
        positions side by side on the lanes (layers.conv_tail), a sparse
        layer the key sum of its open group."""
        heads, d = self.kda_heads, self.kda_head_dim
        kda = (((heads, d, d), jnp.float32),
               (((self.conv_width - 1) * 3 * heads * d,), self.dtype))
        sparse = (((self.index_dim,), jnp.float32),)
        return tuple(sparse if kind == "dsa" else kda
                     for kind in self.layer_types)

    def paged_model(self):
        return _paged_model()


HYBRID_SPARSE_PRESETS = {
    # every mechanism at a size a CPU test holds: KDA + dense, two KDA and
    # one sparse-attention layer with experts; 8 positions attended at most
    "tiny": HybridSparseConfig(
        vocab=256, dim=64, layer_types=("kda", "kda", "kda", "dsa"),
        mlp_types=("dense", "sparse", "sparse", "sparse"),
        kda_heads=2, kda_head_dim=16, gate_rank=8, num_heads=4, q_rank=32,
        kv_rank=32, nope_dim=16, v_dim=16, index_heads=8, index_dim=16,
        index_rope_dim=8, index_topk=16, dense_ffn_dim=128,
        expert_ffn_dim=32, num_experts=8, top_k=2, experts_held=8,
        hc_mult=4, max_seq_len=128),
}


# -- parameters ------------------------------------------------------------------

def _lin(key, fan_in: int, fan_out: int, dtype):
    return L.linear_init(key, fan_in, fan_out, bias=False, dtype=dtype)


def _hc_init(key, config: HybridSparseConfig):
    n, wide = config.hc_mult, config.hc_mult * config.dim
    keys = jax.random.split(key, 2)
    return {"norm": L.rms_norm_init(wide, config.dtype),
            "phi": (jax.random.normal(keys[0], (wide, 2 * n + n * n)) *
                    wide ** -0.5).astype(config.dtype),
            "alpha": jnp.ones((3,), jnp.float32),
            "bias": jax.random.normal(keys[1], (2 * n + n * n,)) * 0.5}


def _kda_init(key, config: HybridSparseConfig):
    keys = jax.random.split(key, 12)
    dim, dtype = config.dim, config.dtype
    heads, d = config.kda_heads, config.kda_head_dim
    wide = heads * d
    return {"q": _lin(keys[0], dim, wide, dtype),
            "k": _lin(keys[1], dim, wide, dtype),
            "v": _lin(keys[2], dim, wide, dtype),
            "conv": {"w": (jax.random.normal(
                keys[3], (config.conv_width, 3 * wide)) *
                config.conv_width ** -0.5).astype(dtype)},
            "f_a": _lin(keys[4], dim, config.gate_rank, dtype),
            "f_b": _lin(keys[5], config.gate_rank, wide, dtype),
            "a_log": jax.random.uniform(keys[6], (heads,), jnp.float32,
                                        -1.0, 1.0),
            "dt_bias": jax.random.normal(keys[7], (wide,)) - 2.0,
            "b": _lin(keys[8], dim, heads, dtype),
            "g_a": _lin(keys[9], dim, config.gate_rank, dtype),
            "g_b": _lin(keys[10], config.gate_rank, wide, dtype),
            "o_norm": L.rms_norm_init(d, dtype),
            "o": _lin(keys[11], wide, dim, dtype)}


def _dsa_init(key, config: HybridSparseConfig):
    keys = jax.random.split(key, 8)
    dim, dtype, heads = config.dim, config.dtype, config.num_heads
    attn = {"q_a": _lin(keys[0], dim, config.q_rank, dtype),
            "q_norm": L.rms_norm_init(config.q_rank, dtype),
            "q_b": _lin(keys[1], config.q_rank, heads * config.nope_dim,
                        dtype),
            "kv_a": _lin(keys[2], dim, config.kv_rank, dtype),
            "kv_norm": L.rms_norm_init(config.kv_rank, dtype),
            "kv_b": _lin(keys[3], config.kv_rank,
                         heads * (config.nope_dim + config.v_dim), dtype),
            "o": _lin(keys[4], heads * config.v_dim, dim, dtype)}
    indexer = {"q": _lin(keys[5], config.q_rank,
                         config.index_heads * config.index_dim, dtype),
               "k": _lin(keys[6], dim, config.index_dim, dtype),
               "k_norm": L.layer_norm_init(config.index_dim, dtype),
               "w": _lin(keys[7], dim, config.index_heads, dtype)}
    return attn, indexer


def _layer_init(key, config: HybridSparseConfig, index: int):
    keys = jax.random.split(key, 10)
    dim, dtype = config.dim, config.dtype
    layer = {"hc_attn": _hc_init(keys[0], config),
             "ln_attn": L.rms_norm_init(dim, dtype),
             "hc_mlp": _hc_init(keys[1], config),
             "ln_mlp": L.rms_norm_init(dim, dtype)}
    if config.layer_types[index] == "kda":
        layer["kda"] = _kda_init(keys[2], config)
    else:
        layer["attn"], layer["indexer"] = _dsa_init(keys[2], config)

    def ffn(k, width):
        ks = jax.random.split(k, 3)
        return {"gate": _lin(ks[0], dim, width, dtype),
                "up": _lin(ks[1], dim, width, dtype),
                "down": _lin(ks[2], width, dim, dtype)}

    if config.mlp_types[index] == "dense":
        return layer | ffn(keys[3], config.dense_ffn_dim)
    held, width = config.experts_held, config.expert_ffn_dim

    def stacked(k, fan_in, fan_out):
        return {"w": (jax.random.normal(k, (held, fan_in, fan_out)) *
                      fan_in ** -0.5).astype(dtype)}

    layer["router"] = _lin(keys[4], dim, config.num_experts, dtype) | {
        "bias": jax.random.normal(keys[5], (config.num_experts,)) * 0.05}
    layer["shared"] = ffn(keys[6], width * config.shared_experts)
    ks = jax.random.split(keys[7], 3)
    layer["experts"] = {"gate": stacked(ks[0], dim, width),
                        "up": stacked(ks[1], dim, width),
                        "down": stacked(ks[2], width, dim)}
    return layer


def hybrid_sparse_init(key, config: HybridSparseConfig):
    keys = jax.random.split(key, config.num_layers + 2)
    return {"embed": L.embedding_init(keys[0], config.vocab, config.dim,
                                      config.dtype),
            "layers": [_layer_init(keys[i + 1], config, i)
                       for i in range(config.num_layers)],
            "ln_out": L.rms_norm_init(config.dim, config.dtype),
            "lm_head": _lin(keys[-1], config.dim, config.vocab,
                            config.dtype)}


# -- streams (mHC) ---------------------------------------------------------------

def mhc_maps(hc, config: HybridSparseConfig, streams):
    """streams [..., n, dim] -> (pre [..., n], post [..., n], res [..., n,
    n]) in float32: the three mappings of one sublayer."""
    n = config.hc_mult
    flat = streams.reshape(streams.shape[:-2] + (-1,)).astype(jnp.float32)
    normed = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + config.hc_eps) * \
        hc["norm"]["scale"].astype(jnp.float32)
    raw = jnp.einsum("...k,km->...m", normed,
                     hc["phi"].astype(jnp.float32), precision=_HIGHEST)
    alpha = hc["alpha"].astype(jnp.float32)
    bias = hc["bias"].astype(jnp.float32)
    pre = jax.nn.sigmoid(alpha[0] * raw[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * raw[..., n:2 * n] +
                                bias[n:2 * n])
    logits = alpha[2] * raw[..., 2 * n:] + bias[2 * n:]
    # exp up to a factor a matrix, which the first normalisation removes
    res = jnp.exp(logits - logits.max(axis=-1, keepdims=True)).reshape(
        logits.shape[:-1] + (n, n))
    for _ in range(config.hc_sinkhorn_iters):
        res = res / res.sum(axis=-1, keepdims=True)
        res = res / res.sum(axis=-2, keepdims=True)
    return pre, post, res


def _sublayer(hc, norm, config: HybridSparseConfig, streams, fn):
    """One sublayer inside its hyper-connection: streams [..., n, dim];
    fn(normed [..., dim]) -> [..., dim]."""
    with jax.named_scope(SCOPE_MHC):
        pre, post, res = mhc_maps(hc, config, streams)
        # a handful of streams: sums of scaled streams, which fuse into
        # one pass over X (a batched 4 x 4 product would not)
        wide = [streams[..., j, :].astype(jnp.float32)
                for j in range(config.hc_mult)]
        mixed = sum(pre[..., j, None] * wide[j]
                    for j in range(config.hc_mult))
        normed = L.rms_norm(norm, mixed.astype(streams.dtype),
                            config.norm_eps)
    out = fn(normed)
    with jax.named_scope(SCOPE_MHC):
        out = out.astype(jnp.float32)
        return jnp.stack(
            [sum(res[..., i, j, None] * wide[j]
                 for j in range(config.hc_mult)) + post[..., i, None] * out
             for i in range(config.hc_mult)], axis=-2).astype(streams.dtype)


def _streams_in(config: HybridSparseConfig, x):
    return jnp.broadcast_to(x[..., None, :],
                            x.shape[:-1] + (config.hc_mult, x.shape[-1]))


def _head_hidden(params, config: HybridSparseConfig, streams):
    """The streams summed, then the final norm."""
    return L.rms_norm(params["ln_out"],
                      streams.astype(jnp.float32).sum(axis=-2)
                      .astype(streams.dtype), config.norm_eps)


# -- KDA -------------------------------------------------------------------------

def _kda_inputs(kda, config: HybridSparseConfig, x, tail, live):
    """x [A, T, dim] (normed), tail [A, (conv-1) x 3HD] the convolution's
    inputs before position 0 of x, live [A, T] -> q, k, v, g [A, T, H, D]
    f32, beta [A, T, H] f32, the output gate [A, T, HD] f32, and the new
    tail: the inputs of the last conv-1 LIVE positions (live positions
    lead each row)."""
    heads, d = config.kda_heads, config.kda_head_dim
    a, t, _ = x.shape
    pre = jnp.concatenate([L.linear(kda[name], x) for name in "qkv"],
                          axis=-1)
    mixed, tail = L.conv_tail(pre, tail, kda["conv"]["w"], None, live)
    mixed = mixed.reshape(a, t, 3, heads, d)
    q, k, v = mixed[:, :, 0], mixed[:, :, 1], mixed[:, :, 2]

    def unit(z):
        return z * jax.lax.rsqrt((z * z).sum(axis=-1, keepdims=True) + 1e-6)

    q, k = unit(q) * d ** -0.5, unit(k)
    rate = L.linear(kda["f_b"], L.linear(kda["f_a"], x)).astype(
        jnp.float32) + kda["dt_bias"].astype(jnp.float32)
    rate = rate.reshape(a, t, heads, d) * \
        jnp.exp(kda["a_log"].astype(jnp.float32))[:, None]
    g = config.gate_lower_bound * jax.nn.sigmoid(rate)
    beta = jax.nn.sigmoid(L.linear(kda["b"], x).astype(jnp.float32))
    gate = jax.nn.sigmoid(L.linear(kda["g_b"], L.linear(kda["g_a"], x))
                          .astype(jnp.float32))
    # a position that is not live leaves S as it was: no decay, no write
    g = g * live[:, :, None, None]
    beta = beta * live[:, :, None]
    return q, k, v, g, beta, gate, tail


def _kda_output(kda, config: HybridSparseConfig, out, gate, dtype):
    """out [A, T, H, D] f32 -> [A, T, dim]: the norm a head, the gate,
    W_o."""
    a, t = out.shape[:2]
    normed = out * jax.lax.rsqrt(
        jnp.mean(out * out, axis=-1, keepdims=True) + config.norm_eps) * \
        kda["o_norm"]["scale"].astype(jnp.float32)
    return L.linear(kda["o"],
                    (normed.reshape(a, t, -1) * gate).astype(dtype))


# the gated delta rule itself, token by token and chunked, is
# models/delta_rule.py's (a second model shares it, ISSUE 40): here a gate
# a CHANNEL, square heads
kda_recurrent = delta_rule.recurrent
kda_chunked = delta_rule.chunked


def _kda_block(layer, config: HybridSparseConfig, x, state, live,
               live_only: bool = False):
    """A KDA layer's token mixing over a block x [A, T, dim] (normed)
    from the slot state (S, tail): -> (out [A, T, dim], the state after
    the block's live positions).  `live_only` (a block of one token):
    the kernel that moves the state of the live rows and no other."""
    kda = layer["kda"]
    memory, tail = state
    with jax.named_scope(SCOPE_KDA_CORE):
        q, k, v, g, beta, gate, tail = _kda_inputs(kda, config, x, tail,
                                                   live)
        if x.shape[1] == 1:
            one = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], memory)
            out, memory = kda_live_step(*one, live[:, 0]) if live_only \
                else kda_recurrent(*one)
            out = out[:, None]
        elif _scan_kernel(config, jax.default_backend() != "tpu"):
            out, memory = delta_chunk_scan(q, k, v, g, beta, memory)
        else:
            out, memory = kda_chunked(q, k, v, g, beta, memory)
    with jax.named_scope(SCOPE_ATTN_PROJ):
        return _kda_output(kda, config, out, gate, x.dtype), (memory, tail)


# -- sparse latent attention -----------------------------------------------------

def rope_tables(config: HybridSparseConfig):
    return L.rope_frequencies(config.index_rope_dim, config.max_seq_len,
                              config.rope_theta)


def _rotate(config: HybridSparseConfig, x, cos, sin, positions):
    """Rotary on the leading index_rope_dim lanes of x [B, H, T, D]."""
    r = config.index_rope_dim
    return jnp.concatenate(
        [L.apply_rope(x[..., :r], cos, sin, positions), x[..., r:]],
        axis=-1)


def _dsa_project(layer, config: HybridSparseConfig, x, cos, sin, positions):
    """x [A, T, dim] at positions[a] + [0, T) -> (absorbed queries [A, 1,
    H*T, kv_rank] (H-major), latent rows [A, 1, T, kv_rank], indexer
    queries [A, J, T, 128], keys [A, T, 128] f32 and weights [A, T, J]
    f32)."""
    attn, indexer = layer["attn"], layer["indexer"]
    with jax.named_scope(SCOPE_ATTN_PROJ):
        c_q = L.rms_norm(attn["q_norm"], L.linear(attn["q_a"], x),
                         config.norm_eps)
        q = L._split_heads(L.linear(attn["q_b"], c_q), config.num_heads)
        rows = L.rms_norm(attn["kv_norm"], L.linear(attn["kv_a"], x),
                          config.norm_eps)[:, None]
        q_full = absorb_queries(attn, config, q, q[..., config.nope_dim:])
    with jax.named_scope(SCOPE_DSA_INDEX):
        q_i = L._split_heads(L.linear(indexer["q"], c_q),
                             config.index_heads)
        q_i = _rotate(config, q_i, cos, sin, positions)
        k_i = L.layer_norm(indexer["k_norm"], L.linear(indexer["k"], x),
                           config.norm_eps)[:, None]
        k_i = _rotate(config, k_i, cos, sin, positions)[:, 0]
        weights = L.linear(indexer["w"], x).astype(jnp.float32) * \
            (config.index_heads * config.index_dim) ** -0.5
    return q_full, rows, q_i, k_i.astype(jnp.float32), weights


def _index_scores(q_i, weights, keys):
    """score[a, t, g] = sum_j w[a, t, j] relu(q_i[a, j, t] . keys[a, g])
    in float32 (the scale is in the weights)."""
    dots = jnp.einsum("ajtd,agd->ajtg", q_i, keys.astype(q_i.dtype),
                      preferred_element_type=jnp.float32)
    return jnp.einsum("atj,ajtg->atg", weights, jax.nn.relu(dots))


def _dsa_block(layer, config: HybridSparseConfig, x, cos, sin, offsets,
               live, prefix=None):
    """The sparse layer over a block x [A, C, dim] (normed) at positions
    offsets[a] + [0, C), offsets and C whole groups: causal among its
    own positions and, where `prefix` is given (an extend), after the
    pool's rows.  A query attends its own open group and the best
    `top_groups` complete groups.  Returns (out [A, C, dim], latent rows
    [A, 1, C, kv_rank], pooled keys [A, 1, C / pool, 128], the key sum of
    the group left open [A, 128])."""
    pool, rank = config.index_pool, config.kv_rank
    a, c, _ = x.shape
    groups = c // pool
    offsets = jnp.broadcast_to(jnp.asarray(offsets, jnp.int32), (a,))
    q_full, rows, q_i, k_i, weights = _dsa_project(layer, config, x, cos,
                                                   sin, offsets)
    with jax.named_scope(SCOPE_DSA_INDEX):
        sums = (k_i * live[:, :, None]).reshape(a, groups, pool, -1).sum(2)
        pooled = (sums / pool).astype(x.dtype)
        count = live.sum(axis=1).astype(jnp.int32)
        left = jnp.take_along_axis(
            sums, jnp.clip(count // pool, 0, groups - 1)[:, None, None],
            axis=1)[:, 0]
        left = jnp.where((count % pool > 0)[:, None], left, 0.0)
        # complete groups before each query: its own block's, the pool's
        mine = jnp.arange(c) // pool                          # [C]
        scores = _index_scores(q_i, weights, pooled)          # [A, C, groups]
        scores = jnp.where(jnp.arange(groups)[None, :] < mine[:, None],
                           scores, -jnp.inf)
        if prefix is not None:
            scores = jnp.concatenate(
                [prefix.scores(q_i, weights, offsets), scores], axis=-1)
        limit = config.top_groups
        if scores.shape[-1] > limit:
            floor = jax.lax.cond(
                (offsets + c).max() > (limit + 1) * pool,
                lambda s: jax.lax.top_k(s, limit)[0][..., -1:],
                lambda s: jnp.full(s.shape[:-1] + (1,), -jnp.inf, s.dtype),
                scores)
            chosen = (scores >= floor) & (scores > -jnp.inf)
        else:
            chosen = scores > -jnp.inf
    heads = config.num_heads
    scale = config.softmax_scale
    with jax.named_scope(SCOPE_ATTN_CORE):
        q_rows = q_full[:, 0].reshape(a, heads, c, rank)

        def attend(carry, keys, mask):
            """Online softmax over one more piece of latent rows keys
            [A, P, rank]; mask [A, C, P], the same for every head."""
            row_max, row_sum, acc = carry
            s = jnp.einsum("ahcd,apd->ahcp", q_rows, keys,
                           preferred_element_type=jnp.float32) * scale
            mask = mask[:, None]
            s = jnp.where(mask, s, -1e30)
            new_max = jnp.maximum(row_max, s.max(axis=-1, keepdims=True))
            w = jnp.where(mask, jnp.exp(s - new_max), 0.0)
            fade = jnp.exp(row_max - new_max)
            return (new_max, row_sum * fade + w.sum(-1, keepdims=True),
                    acc * fade + jnp.einsum(
                        "ahcp,apd->ahcd", w.astype(keys.dtype), keys,
                        preferred_element_type=jnp.float32))

        carry = (jnp.full((a, heads, c, 1), -1e30, jnp.float32),
                 jnp.zeros((a, heads, c, 1), jnp.float32),
                 jnp.zeros((a, heads, c, rank), jnp.float32))
        own = chosen[..., -groups:]                           # [A, C, groups]
        position = jnp.arange(c)
        own_mask = (position[None, :] <= position[:, None])[None] & (
            (mine[None, :] == mine[:, None])[None] |
            jnp.repeat(own, pool, axis=-1))
        carry = attend(carry, rows[:, 0], own_mask)
        if prefix is not None:
            carry = prefix.attend(carry, attend, chosen[..., :-groups],
                                  offsets)
        _, row_sum, acc = carry
        o_lat = (acc / row_sum).astype(x.dtype).reshape(
            a, 1, heads * c, rank)
    with jax.named_scope(SCOPE_ATTN_PROJ):
        out = absorb_output(layer["attn"], config, o_lat, c)
    return out, rows, pooled[:, None], left


class _PoolPrefix:
    """What the pool holds of each row's prefix, read through the row's
    table a piece of `_PREFIX_PIECE` positions at a time, as far as the
    longest live prefix reaches."""

    def __init__(self, config, leaves, tables, block: int, longest):
        self.config = config
        self.latent, self.keys = leaves
        self.block = block
        self.piece_blocks = max(1, min(tables.shape[1],
                                       _PREFIX_PIECE // block))
        pad = -tables.shape[1] % self.piece_blocks
        self.tables = jnp.pad(tables, ((0, 0), (0, pad))) if pad else tables
        self.pieces = -(-longest // (self.piece_blocks * block))
        self.groups = self.tables.shape[1] * block // config.index_pool

    def _piece(self, pool, j):
        ids = jax.lax.dynamic_slice_in_dim(
            self.tables, j * self.piece_blocks, self.piece_blocks, axis=1)
        rows = jnp.take(pool, ids, axis=0)[:, :, 0]   # [A, pb, rows, lanes]
        a, pb, r, lanes = rows.shape
        return rows.reshape(a, pb * r, lanes)

    def scores(self, q_i, weights, offsets):
        """Index scores of the block's queries over the pool's groups
        [A, C, groups], -inf past each row's own prefix."""
        a, _, c, _ = q_i.shape
        per = self.piece_blocks * self.block // self.config.index_pool

        def piece(j, out):
            part = _index_scores(q_i, weights, self._piece(self.keys, j))
            return jax.lax.dynamic_update_slice_in_dim(out, part, j * per,
                                                       axis=2)

        out = jax.lax.fori_loop(
            0, self.pieces, piece,
            jnp.full((a, c, self.groups), -jnp.inf, jnp.float32))
        whole = (offsets // self.config.index_pool)[:, None, None]
        return jnp.where(jnp.arange(self.groups)[None, None] < whole, out,
                         -jnp.inf)

    def attend(self, carry, attend, chosen, offsets):
        pool = self.config.index_pool
        span = self.piece_blocks * self.block

        def piece(j, carry):
            picked = jax.lax.dynamic_slice_in_dim(
                chosen, j * (span // pool), span // pool, axis=2)
            mask = jnp.repeat(picked, pool, axis=-1) & (
                (j * span + jnp.arange(span))[None, None, :] <
                offsets[:, None, None])
            return attend(carry, self._piece(self.latent, j), mask)

        return jax.lax.fori_loop(0, self.pieces, piece, carry)


def _dsa_step(layer, config: HybridSparseConfig, x, cos, sin, tables,
              leaves, sides, left, entry_lengths, lengths, step_index,
              entry_active, active):
    """The sparse layer in a decode step, x [S, 1, dim] at position
    lengths[s].  The projections run once over every slot (their cost
    is their weights', whatever decodes); what grows with the slots,
    scores, choice, gather and softmax, runs for the slots that were
    live at the round's entry alone: their ids compacted (`entry_active`
    does not change inside a round, and a slot only LEAVES `active` in
    it), `_STEP_WINDOW` of them at a time through `_dsa_window`, what it
    made written back to their rows.  A slot that was not live at entry
    costs no window and keeps its side rows, its side keys and its
    `left` (it may stand between two pieces of its prompt) as they were;
    its `out` is zeros.  Returns (out, the sides rewritten, the new sum,
    `_dsa_window`'s three counts over the slots that decode and then the
    slots computed for, whole windows)."""
    slots_n = x.shape[0]
    width = min(_STEP_WINDOW, slots_n)
    ids, count = _live_ids(entry_active)
    # past the count an id no slot has: clipped where a window reads,
    # dropped where it writes (slot 0 is written by its own lane alone)
    pad = -slots_n % width
    ids = jnp.where(jnp.arange(slots_n + pad) < count,
                    jnp.pad(ids, (0, pad)), slots_n)
    windows = -(-count[0] // width)
    projected = _dsa_project(layer, config, x, cos, sin, lengths)

    def window(j, carried):
        o_lat, side_rows, side_keys, left, counted = carried
        at = jax.lax.dynamic_slice_in_dim(ids, j * width, width)

        def held(whole):
            return jnp.take(whole, at, axis=0, mode="clip")

        made, rewritten, summed, tally = _dsa_window(
            config, tuple(map(held, projected)), held(tables), leaves,
            (held(side_rows), held(side_keys)), held(left),
            held(entry_lengths), held(lengths), step_index,
            held(active) & (at < slots_n))
        return tuple(whole.at[at].set(part, mode="drop")
                     for whole, part in zip(
                         (o_lat, side_rows, side_keys, left),
                         (made,) + rewritten + (summed,))) + \
            (counted + tally,)

    o_lat, side_rows, side_keys, left, counted = jax.lax.fori_loop(
        0, windows, window,
        (jnp.zeros((slots_n, 1, config.num_heads, config.kv_rank),
                   x.dtype),) + tuple(sides) + (
            left, jnp.zeros((len(_DSA_COUNTERS),), jnp.int32)))
    with jax.named_scope(SCOPE_ATTN_PROJ):
        out = absorb_output(layer["attn"], config, o_lat, 1)
    return out, (side_rows, side_keys), left, jnp.concatenate(
        [counted, jnp.stack([windows * width, active.sum()]).astype(
            jnp.int32)])


def _dsa_window(config: HybridSparseConfig, projected, tables, leaves,
                sides, left, entry_lengths, lengths, step_index, active):
    """One window of `_dsa_step`: `projected` is `_dsa_project`'s five
    and every other argument a slot's, at the window's W slots (every
    slot's arithmetic is its own): index scores over the pooled keys of
    the slot's whole length, the best groups' latent rows GATHERED from
    the pool, one softmax over them, the open group's rows and this
    round's.  The leaf keeps its rows in tiles of `_TILE_ROWS`, so the
    gather takes the whole tile that holds a chosen group from the leaf
    as it lies (seen by tiles the leaf is the same bytes:
    SCOPE_DSA_RELAYOUT holds no operation) and the tile's other groups
    are masked: it ATTENDS the chosen rows alone and READS `_TILE_ROWS /
    index_pool` times as many.  `left` [W, 128] is the key sum of the
    slot's open group.  Returns (the attended latents [W, 1, H, rank],
    the sides rewritten, the new sum, [positions live, positions
    attended, rows fetched] over the slots that decode)."""
    pool_n, rank = config.index_pool, config.kv_rank
    latent, keys = leaves
    side_rows, side_keys = sides
    q_full, rows, q_i, k_i, weights = projected
    slots_n = q_full.shape[0]
    block = latent.shape[2]
    steps = side_rows.shape[2]
    open_group = entry_lengths // pool_n                       # G0 [S]
    group = lengths // pool_n
    with jax.named_scope(SCOPE_ATTN_PROJ):
        side_rows = jax.lax.dynamic_update_slice_in_dim(
            side_rows, rows, step_index, axis=2)
    with jax.named_scope(SCOPE_DSA_INDEX):
        # the group that this token completes gets its pooled key, in
        # the side row of its distance from the round's first group
        total = left + k_i[:, 0]
        closes = lengths % pool_n == pool_n - 1
        at = group - open_group                                # [S]
        fresh = (total / pool_n).astype(side_keys.dtype)
        mark = closes[:, None] & (jnp.arange(side_keys.shape[2])[None] ==
                                  at[:, None])
        side_keys = jnp.where(mark[:, None, :, None],
                              fresh[:, None, None, :], side_keys)
        left = jnp.where(active[:, None],
                         jnp.where(closes[:, None], 0.0, total), left)
        # scores over the pool's complete groups and the round's
        table_groups = tables.shape[1] * block // pool_n
        # every id is a block of the pool: no fill for one that is not
        pooled = jnp.take(keys, tables, axis=0,
                          mode="clip")[:, :, 0]               # [W, nb, B/p, 128]
        pooled = pooled.reshape(slots_n, table_groups, -1)
        everything = jnp.concatenate([pooled, side_keys[:, 0]], axis=1)
        scores = _index_scores(q_i, weights, everything)[:, 0]  # [S, G + P]
        index = jnp.arange(scores.shape[1])
        whole = jnp.where(
            index[None] < table_groups,
            index[None] < open_group[:, None],
            open_group[:, None] + index[None] - table_groups <
            group[:, None])
        scores = jnp.where(whole, scores, -jnp.inf)
        limit = min(config.top_groups, scores.shape[1])
        best, picked = jax.lax.top_k(scores, limit)            # [S, K]
        taken = best > -jnp.inf
        from_pool = taken & (picked < table_groups)
        round_group = (taken[:, :, None] &
                       (picked[:, :, None] - table_groups ==
                        jnp.arange(side_keys.shape[2])[None, None])).any(1)
    with jax.named_scope(SCOPE_ATTN_CORE):
        per_block = block // pool_n
        per_tile = _TILE_ROWS // pool_n
        where = jnp.take_along_axis(
            tables, jnp.clip(picked // per_block, 0, tables.shape[1] - 1),
            axis=1) * per_block + picked % per_block           # [S, K]
        where = jnp.where(from_pool, where, 0)
        with jax.named_scope(SCOPE_DSA_RELAYOUT):
            tiles = latent.reshape(-1, _TILE_ROWS, rank)
        chosen = jnp.take(tiles, where // per_tile, axis=0, mode="clip")
        chosen = chosen.reshape(slots_n, limit * _TILE_ROWS, rank)
        chosen_ok = (from_pool[:, :, None] & (
            jnp.arange(_TILE_ROWS)[None, None] // pool_n ==
            (where % per_tile)[:, :, None])).reshape(slots_n, -1)
        # the rows of the round's first group that the pool holds
        recent_at = open_group[:, None] * pool_n + \
            jnp.arange(pool_n - 1)[None]                       # [S, p-1]
        recent_id = jnp.take_along_axis(
            tables, jnp.clip(recent_at // block, 0, tables.shape[1] - 1),
            axis=1)
        recent = latent[recent_id, 0, recent_at % block]       # [S, p-1, rank]
        side_at = entry_lengths[:, None] + jnp.arange(steps)[None]
        near = jnp.concatenate([recent, side_rows[:, 0]], axis=1)
        near_at = jnp.concatenate([recent_at, side_at], axis=1)
        near_group = near_at // pool_n - open_group[:, None]
        near_ok = jnp.concatenate(
            [recent_at < entry_lengths[:, None],
             (jnp.arange(steps)[None] <= step_index) &
             (side_at <= lengths[:, None])], axis=1) & (
            (near_at // pool_n == group[:, None]) |
            # a group past the round's last side key cannot close in it
            ((near_group < round_group.shape[1]) & jnp.take_along_axis(
                round_group, jnp.clip(near_group, 0,
                                      round_group.shape[1] - 1), axis=1)))

        # ONE softmax over the chosen rows and the near ones, scored
        # apart (a maximum and a sum shared): joined into one array the
        # fetched tiles would be copied once more
        def scores(rows, ok):
            s = jnp.einsum("srd,spd->srp", q_full[:, 0], rows,
                           preferred_element_type=jnp.float32) * \
                config.softmax_scale
            return jnp.where(ok[:, None], s, -1e30)

        def weighed(e, rows):
            return jnp.einsum("srp,spd->srd", e.astype(rows.dtype), rows,
                              preferred_element_type=jnp.float32)

        s_far, s_near = scores(chosen, chosen_ok), scores(near, near_ok)
        top = jnp.maximum(s_far.max(axis=-1), s_near.max(axis=-1))[..., None]
        e_far, e_near = jnp.exp(s_far - top), jnp.exp(s_near - top)
        total = e_far.sum(axis=-1) + e_near.sum(axis=-1)
        o_lat = ((weighed(e_far, chosen) + weighed(e_near, near)) /
                 total[..., None]).astype(rows.dtype)[:, None]
        counted = jnp.stack([
            jnp.where(active, lengths + 1, 0).sum(),
            jnp.where(active, chosen_ok.sum(axis=1) + near_ok.sum(axis=1),
                      0).sum(),
            jnp.where(active, from_pool.sum(axis=1) * _TILE_ROWS, 0).sum()
        ]).astype(jnp.int32)
    return o_lat, (side_rows, side_keys), left, counted


# -- feed-forward ----------------------------------------------------------------

def _ffn(layer, config: HybridSparseConfig, x, live):
    """-> (y, the expert layer's counts or None)."""
    if "experts" not in layer:
        with jax.named_scope(SCOPE_MLP):
            return swiglu(layer, x, config.swiglu_limit), None
    return moe_ffn(layer, config, x, live)


# -- whole passes ----------------------------------------------------------------

def _zero_state(config: HybridSparseConfig, rows: int) -> list:
    return [tuple(jnp.zeros((rows,) + shape, dtype)
                  for shape, dtype in layer)
            for layer in config.slot_state]


def _block_layer(layer, config: HybridSparseConfig, streams, cos, sin,
                 offsets, live, state, prefix=None):
    """One layer over a block of tokens, streams [A, C, n, dim]: ->
    (streams, the rows of each pool leaf or (), the slot state after)."""
    kept = {}

    def mix(normed):
        if "kda" in layer:
            out, kept["state"] = _kda_block(layer, config, normed, state,
                                            live)
            kept["rows"] = ()
            return out
        out, rows, pooled, left = _dsa_block(layer, config, normed, cos,
                                             sin, offsets, live, prefix)
        kept["rows"], kept["state"] = (rows, pooled), (left,)
        return out

    streams = _sublayer(layer["hc_attn"], layer["ln_attn"], config,
                        streams, mix)
    streams = _sublayer(layer["hc_mlp"], layer["ln_mlp"], config, streams,
                        lambda normed: _ffn(layer, config, normed, live)[0])
    return streams, kept["rows"], kept["state"]


def hybrid_sparse_hidden(params, config: HybridSparseConfig, tokens,
                         live=None):
    """tokens [A, T] from position 0, T whole groups -> (hidden after
    the last norm [A, T, dim], per layer the rows of its pool leaves,
    per layer the slot state after each row's live positions)."""
    cos, sin = rope_tables(config)
    if live is None:
        live = jnp.ones(tokens.shape, bool)
    x = L.embedding(params["embed"], tokens).astype(config.dtype)
    streams = _streams_in(config, x)
    rows, states = [], []
    zero = _zero_state(config, tokens.shape[0])
    for layer, state in zip(params["layers"], zero):
        streams, own, state = _block_layer(
            layer, config, streams, cos, sin, jnp.int32(0), live, state)
        rows.append(own)
        states.append(state)
    with jax.named_scope(SCOPE_HEAD):
        return _head_hidden(params, config, streams), rows, states


def hybrid_sparse_forward(params, config: HybridSparseConfig, tokens):
    """Teacher-forced full-sequence forward: tokens [A, T] -> f32 logits
    [A, T, vocab]."""
    t = tokens.shape[1]
    pad = -t % config.index_pool
    hidden, _, _ = hybrid_sparse_hidden(
        params, config, jnp.pad(tokens, ((0, 0), (0, pad))),
        jnp.broadcast_to(jnp.arange(t + pad) < t, (tokens.shape[0],
                                                   t + pad)))
    return L.linear_logits(params["lm_head"], hidden[:, :t])


# -- as a PagedModel (what serving_paged's builders call) ------------------------

def _step_argmax(params, config: HybridSparseConfig, token_block, attend,
                 live):
    """The decode step's pass over its [S, 1] block: `attend(i, layer,
    normed)` is every layer's token mixing (the builder hands it the
    layer's leaves, side rows and slot state)."""
    alive = jnp.broadcast_to(live[:, None], token_block.shape)
    x = L.embedding(params["embed"], token_block).astype(config.dtype)
    streams = _streams_in(config, x)
    counted = []

    def ffn(layer, normed):
        y, counts = _ffn(layer, config, normed, alive)
        if counts is not None:
            counted.append(counts)
        return y

    for i, layer in enumerate(params["layers"]):
        streams = _sublayer(
            layer["hc_attn"], layer["ln_attn"], config, streams,
            functools.partial(attend, i, layer))
        streams = _sublayer(layer["hc_mlp"], layer["ln_mlp"], config,
                            streams, functools.partial(ffn, layer))
    with jax.named_scope(SCOPE_HEAD):
        logits = L.linear_logits(params["lm_head"],
                                 _head_hidden(params, config, streams))
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    moe = sum(counted, jnp.zeros((len(MOE_COUNTERS),), jnp.int32))
    return tokens, jnp.concatenate(
        [moe, jnp.zeros((_OWN_COUNTERS,), jnp.int32)])


def _state_kernel(config: HybridSparseConfig, interpret: bool) -> bool:
    return moves_live_states(config.kda_heads, config.kda_head_dim,
                             interpret)


def _scan_kernel(config: HybridSparseConfig, interpret: bool) -> bool:
    """Whether a prompt's piece runs the chunked form as ops/delta_chunk's
    kernel: on the chip, where the heads tile.  Read off the geometry at
    trace time; the interpreter is never taken unasked."""
    return not interpret and scans_chunks(
        config.kda_heads, config.kda_head_dim, config.kda_head_dim,
        by_head=False)


def _step_attention(kernel: bool):
    """A layer's token mixing in the decode step: the KDA recurrence
    over the slot's state, or the sparse layer over the pool (neither
    builds a view).  `kernel` (the decoder's `step_kernel`: on a TPU,
    the state on one device, nothing else asked for) lets a KDA layer
    whose head is whole lanes take ops.kda_step's kernel over the slots
    that decode; the sparse layer reads its pool the same either way."""

    def attend(tables, layer, config, x, cos, sin, leaves, views, sides,
               entry_lengths, lengths, step_index, entry_active, state,
               active):
        counts = jnp.zeros((len(HYBRID_COUNTERS),), jnp.int32)
        if "kda" in layer:
            # a slot that is not live neither decays nor writes, and its
            # convolution tail stays: no pass of its own over the state
            out, state = _kda_block(
                layer, config, x, state, active[:, None],
                live_only=kernel and _state_kernel(
                    config, jax.default_backend() != "tpu"))
            return out, sides, state, counts.at[jnp.asarray(_KDA_AT)].set(
                jnp.stack([active.sum(), active.size]).astype(jnp.int32))
        out, sides, left, counted = _dsa_step(
            layer, config, x, cos, sin, tables, leaves, sides, state[0],
            entry_lengths, lengths, step_index, entry_active, active)
        return out, sides, (left,), counts.at[
            jnp.asarray(_DSA_AT)].set(counted)

    return attend


def _prefill(params, config: HybridSparseConfig, prompts, valid, true_lens):
    live = valid[:, None] & (jnp.arange(prompts.shape[1])[None] <
                             true_lens[:, None])
    return hybrid_sparse_hidden(params, config, prompts, live)


def _extend_prepare(config: HybridSparseConfig, chunk_len: int,
                    kernel: bool, ctx):
    """The longest live prefix, and which of the chunk's tokens are
    real."""
    longest = jnp.max(jnp.where(ctx["valid"], ctx["offsets"], 0))
    live = ctx["valid"][:, None] & (
        ~ctx["finish"][:, None] |
        (jnp.arange(chunk_len)[None] <= ctx["final_idx"][:, None]))
    return {"longest": longest, "live": live}


def _extend_layer(kernel: bool):
    def extend_layer(layer, config, streams, cos, sin, leaves, ctx,
                     prepared, state):
        prefix = None
        if "kda" not in layer:
            prefix = _PoolPrefix(config, leaves, ctx["tables_rows"],
                                 ctx["block_tokens"], prepared["longest"])
        return _block_layer(layer, config, streams, cos, sin,
                            ctx["offsets"], prepared["live"], state, prefix)

    return extend_layer


def _walks(config: HybridSparseConfig, kv_int8: bool, interpret: bool) -> str:
    return "model"


@functools.cache
def _paged_model():
    from ..serving_paged import PagedModel
    # the paths slot state and two grains of leaf are carried through:
    # none beyond the paged decoder itself
    return PagedModel(
        rope=rope_tables, token_block_argmax=_step_argmax,
        step_attention=_step_attention, prefill=_prefill,
        extend_prepare=_extend_prepare, extend_layer=_extend_layer,
        walks=_walks, step_kernel=_state_kernel, scan_kernel=_scan_kernel,
        counters=HYBRID_COUNTERS, supports=frozenset(),
        block_multiple=_TILE_ROWS,
        residual_in=_streams_in, final_norm=_head_hidden)
