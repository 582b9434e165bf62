# Decoder-only transformer with multi-head LATENT attention (MLA) and
# dropless sigmoid-routed experts, the block of the DeepSeek-V3 family
# (arXiv:2412.19437; `modeling_deepseek.py`), TPU-native (ISSUE 31).
#
# Per layer, pre-norm residual:
#
#   attention   c_q = rms(x W_qa); q = c_q W_qb -> heads of [nope | rope]
#               [c_kv | k_rope] = x W_kva; c_kv = rms(c_kv); k_rope is ONE
#               head shared by all, rotated with q_rope (YaRN table).
#               The CACHE holds one row a token and layer:
#               [c_kv after its norm | k_rope after rotation | zero pad]
#               (`row_lanes` wide: whole lanes, see LatentMoeConfig).
#     expanded  [k_nope | v] = c_kv W_kvb per head; ordinary causal
#               attention over per-head keys and values (admit, extend,
#               the uncached forward)
#     absorbed  the same numbers with W_kvb folded away: q_lat = q_nope
#               W_UK, scores (q_lat . c_kv + q_rope . k_rope) * s, o_lat =
#               p . c_kv, o = o_lat W_UV: 64 query rows against ONE shared
#               row a token, V the leading lanes of the row that K is
#               (the decode step)
#   layer 0..   dense SwiGLU (`dense_layers` of them), then sparse layers:
#               g = sigmoid(x W_r) in float32 over ALL `num_experts`; the
#               `top_k` largest; weights g_i / sum(g) * routed_scale;
#               y = shared(x) + sum_i w_i expert_i(x); no token dropped.
#
# An expert layer is TOLD which experts it holds (`experts_first`,
# `experts_held`: one chip's share of an expert-parallel deployment).
# It routes over all of them and computes the part of the result that
# its own experts give; what the absent ones would add is left out, and
# a token none of whose experts is held gets its shared expert only.
# Nothing here stands in for the other chips or their exchange.

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import layers as L
from .llama import (SCOPE_ATTN_CORE, SCOPE_ATTN_PROJ, SCOPE_HEAD,
                    SCOPE_MLP, _swiglu)

__all__ = ["LatentMoeConfig", "LATENT_MOE_PRESETS", "latent_moe_init",
           "latent_moe_forward", "yarn_rope_tables", "select_experts",
           "swiglu",
           "SCOPE_MOE_ROUTE", "SCOPE_MOE_SHARED", "SCOPE_MOE_EXPERTS",
           "SCOPE_MLA_EXPAND", "MOE_COUNTERS"]

# jax.named_scope regions beside models/llama.py's six (same rules: HLO
# metadata only; the benchmark's region metrics read these names)
SCOPE_MOE_ROUTE = "aiko.moe_route"       # router, top-k, who goes where
SCOPE_MOE_SHARED = "aiko.moe_shared"     # the shared expert
SCOPE_MOE_EXPERTS = "aiko.moe_experts"   # the routed experts held here
SCOPE_MLA_EXPAND = "aiko.mla_expand"     # W_kvb over latent rows (prefill)

# what a decode step counts of its expert layers, in this order (the
# decoder adds them to its stats under these names): sparse layers run,
# held experts that saw at least one token, token-expert pairs that
# landed on held experts, and all pairs routed
MOE_COUNTERS = ("moe_layer_steps", "moe_experts_hit", "moe_pairs_here",
                "moe_pairs_routed")

# rows an expert computes at once where a block brings more tokens than
# this (a prefill chunk): its tokens are compacted into tiles of this
# many rows, so an expert's work follows what was routed to it
_EXPERT_TILE = 128
# positions of the prefix an extend expands and attends at once
_PREFIX_PIECE = 512


@dataclass(frozen=True)
class LatentMoeConfig:
    vocab: int = 163840
    dim: int = 7168
    num_layers: int = 61
    num_heads: int = 64
    q_rank: int = 1536               # q_lora_rank
    kv_rank: int = 512               # kv_lora_rank
    nope_dim: int = 128              # qk_nope_head_dim
    rope_dim: int = 64               # qk_rope_head_dim
    v_dim: int = 128                 # v_head_dim
    dense_ffn_dim: int = 18432       # intermediate_size
    dense_layers: int = 1            # first_k_dense_replace
    expert_ffn_dim: int = 2048       # moe_intermediate_size
    shared_experts: int = 1          # n_shared_experts
    num_experts: int = 192           # the router's width, always whole
    top_k: int = 8                   # num_experts_per_tok
    routed_scale: float = 2.5        # routed_scaling_factor
    experts_first: int = 0           # the experts held here:
    experts_held: int = 192          #   [first, first + held)
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    yarn_factor: float = 32.0
    yarn_original: int = 4096        # original_max_position_embeddings
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    dtype: object = jnp.float32

    @property
    def row_lanes(self) -> int:
        """Lanes of one cached row: kv_rank + rope_dim (576) padded up
        to whole lanes of 128 (640).  Mosaic slices a block out of an
        HBM operand only where its minor axis is whole lanes (PERF.md
        §6, PR 30), so neither a 576-lane row nor a 64-lane leaf of its
        own can be walked by hand; the pad lanes hold zeros."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128

    @property
    def cache_leaves(self) -> tuple:
        """(heads, lanes) of each leaf a layer keeps of a token: ONE
        shared latent row, and no V leaf."""
        return ((1, self.row_lanes),)

    @property
    def softmax_scale(self) -> float:
        """(nope + rope)^-0.5 * m^2, m = YaRN's attention factor over
        `mscale_all_dim` (DeepSeek-V3's `softmax_scale`)."""
        m = _yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim)
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m

    def paged_model(self):
        """The layer functions the paged decoder serves this model
        through (serving_paged.PagedModel): below, "as a PagedModel"."""
        return _paged_model()


LATENT_MOE_PRESETS = {
    # every mechanism at a size a CPU test holds: 2 dense-then-sparse
    # layers, 8 experts, top 2, heads of 16 + 8, rows of 128 lanes
    "tiny": LatentMoeConfig(
        vocab=256, dim=64, num_layers=3, num_heads=4, q_rank=32,
        kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16, dense_ffn_dim=128,
        expert_ffn_dim=32, num_experts=8, top_k=2, experts_held=8,
        max_seq_len=128, yarn_original=32, yarn_factor=4.0),
}


# -- YaRN rotary table ---------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_rope_tables(config: LatentMoeConfig):
    """cos/sin [max_seq_len, rope_dim // 2] of the YaRN-scaled rotary
    (arXiv:2309.00071 as DeepSeek-V3 applies it): frequencies below the
    `beta_slow` correction rotate `yarn_factor` times slower, those
    above `beta_fast` as published, a linear ramp between; the tables
    carry mscale / mscale_all_dim."""
    dim, base = config.rope_dim, config.rope_theta
    exponents = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extrapolated = 1.0 / base ** exponents
    interpolated = extrapolated / config.yarn_factor

    def correction(rotations: float) -> float:
        return dim * math.log(config.yarn_original /
                              (rotations * 2 * math.pi)) / \
            (2 * math.log(base))

    low = max(math.floor(correction(config.yarn_beta_fast)), 0)
    high = min(math.ceil(correction(config.yarn_beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) /
                    max(high - low, 1e-3), 0.0, 1.0)
    inv = interpolated * ramp + extrapolated * (1.0 - ramp)
    angles = jnp.arange(config.max_seq_len,
                        dtype=jnp.float32)[:, None] * inv[None, :]
    scale = _yarn_mscale(config.yarn_factor, config.yarn_mscale) / \
        _yarn_mscale(config.yarn_factor, config.yarn_mscale_all_dim)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


# -- parameters ------------------------------------------------------------------

def _is_sparse(config: LatentMoeConfig, index: int) -> bool:
    return index >= config.dense_layers


def _ffn_init(key, dim: int, ffn: int, dtype):
    keys = jax.random.split(key, 3)
    return {"gate": L.linear_init(keys[0], dim, ffn, bias=False,
                                  dtype=dtype),
            "up": L.linear_init(keys[1], dim, ffn, bias=False,
                                dtype=dtype),
            "down": L.linear_init(keys[2], ffn, dim, bias=False,
                                  dtype=dtype)}


def _layer_init(key, config: LatentMoeConfig, index: int):
    keys = jax.random.split(key, 10)
    dim, dtype, heads = config.dim, config.dtype, config.num_heads
    q_out = heads * (config.nope_dim + config.rope_dim)
    kv_out = heads * (config.nope_dim + config.v_dim)

    def lin(k, fan_in, fan_out):
        return L.linear_init(k, fan_in, fan_out, bias=False, dtype=dtype)

    layer = {
        "ln_attn": L.rms_norm_init(dim, dtype),
        "attn": {"q_a": lin(keys[0], dim, config.q_rank),
                 "q_norm": L.rms_norm_init(config.q_rank, dtype),
                 "q_b": lin(keys[1], config.q_rank, q_out),
                 "kv_a": lin(keys[2], dim,
                             config.kv_rank + config.rope_dim),
                 "kv_norm": L.rms_norm_init(config.kv_rank, dtype),
                 "kv_b": lin(keys[3], config.kv_rank, kv_out),
                 "o": lin(keys[4], heads * config.v_dim, dim)},
        "ln_mlp": L.rms_norm_init(dim, dtype),
    }
    if not _is_sparse(config, index):
        return layer | _ffn_init(keys[5], dim, config.dense_ffn_dim, dtype)
    held, ffn = config.experts_held, config.expert_ffn_dim

    def stacked(k, fan_in, fan_out):
        return {"w": (jax.random.normal(k, (held, fan_in, fan_out)) *
                      fan_in ** -0.5).astype(dtype)}

    layer["router"] = lin(keys[6], dim, config.num_experts)
    layer["shared"] = _ffn_init(keys[7], dim,
                                ffn * config.shared_experts, dtype)
    # the experts held HERE, stacked on a leading axis: expert
    # experts_first + e is row e
    e_keys = jax.random.split(keys[8], 3)
    layer["experts"] = {"gate": stacked(e_keys[0], dim, ffn),
                        "up": stacked(e_keys[1], dim, ffn),
                        "down": stacked(e_keys[2], ffn, dim)}
    return layer


def latent_moe_init(key, config: LatentMoeConfig):
    keys = jax.random.split(key, config.num_layers + 2)
    return {
        "embed": L.embedding_init(keys[0], config.vocab, config.dim,
                                  config.dtype),
        "layers": [_layer_init(keys[i + 1], config, i)
                   for i in range(config.num_layers)],
        "ln_out": L.rms_norm_init(config.dim, config.dtype),
        "lm_head": L.linear_init(keys[-1], config.dim, config.vocab,
                                 bias=False, dtype=config.dtype),
    }


# -- attention: projections ------------------------------------------------------

def project_block(layer, config: LatentMoeConfig, x, cos, sin, positions):
    """x [S, W, dim] at per-row positions `positions` (scalar or [S]) ->
    (q_nope [S, H, W, nope], q_rope [S, H, W, rope] rotated,
     rows [S, 1, W, row_lanes]: what the cache keeps of these tokens)."""
    attn = layer["attn"]
    c_q = L.rms_norm(attn["q_norm"], L.linear(attn["q_a"], x))
    q = L._split_heads(L.linear(attn["q_b"], c_q), config.num_heads)
    q_nope, q_rope = q[..., :config.nope_dim], q[..., config.nope_dim:]
    kv = L.linear(attn["kv_a"], x)
    c_kv = L.rms_norm(attn["kv_norm"], kv[..., :config.kv_rank])
    k_rope = kv[..., config.kv_rank:][:, None]           # one shared head
    q_rope = L.apply_rope(q_rope, cos, sin, positions)
    k_rope = L.apply_rope(k_rope, cos, sin, positions)
    pad = config.row_lanes - config.kv_rank - config.rope_dim
    rows = jnp.concatenate(
        [c_kv[:, None], k_rope,
         jnp.zeros(k_rope.shape[:3] + (pad,), k_rope.dtype)], axis=-1)
    return q_nope, q_rope, rows


def _w_kvb(attn, config: LatentMoeConfig):
    """W_kvb as [kv_rank, H, nope + v]: W_UK its leading lanes a head,
    W_UV the rest."""
    return attn["kv_b"]["w"].reshape(
        config.kv_rank, config.num_heads, config.nope_dim + config.v_dim)


def absorb_queries(attn, config: LatentMoeConfig, q_nope, q_rope):
    """W_UK folded into the queries: [S, H, W, *] -> [S, 1, H*W,
    row_lanes] (H-major rows), to be dotted with cached rows as they
    are; the pad lanes are zeros."""
    w_uk = _w_kvb(attn, config)[..., :config.nope_dim]
    q_lat = jnp.einsum("shwd,chd->shwc", q_nope, w_uk,
                       preferred_element_type=jnp.float32
                       ).astype(q_nope.dtype)
    pad = config.row_lanes - config.kv_rank - config.rope_dim
    q_full = jnp.concatenate(
        [q_lat, q_rope,
         jnp.zeros(q_rope.shape[:3] + (pad,), q_rope.dtype)], axis=-1)
    s, h, w, lanes = q_full.shape
    return q_full.reshape(s, 1, h * w, lanes)


def absorb_output(attn, config: LatentMoeConfig, o_lat, width: int):
    """W_UV applied to the attended latents [S, 1, H*W, kv_rank], then
    W_o: -> [S, W, dim]."""
    s = o_lat.shape[0]
    o_lat = o_lat.reshape(s, config.num_heads, width, config.kv_rank)
    w_uv = _w_kvb(attn, config)[..., config.nope_dim:]
    out = jnp.einsum("shwc,chd->shwd", o_lat, w_uv,
                     preferred_element_type=jnp.float32
                     ).astype(o_lat.dtype)
    return L.linear(attn["o"], L._merge_heads(out))


def expand_rows(attn, config: LatentMoeConfig, rows):
    """Cached rows [A, T, row_lanes] -> per-head keys [A, H, T, nope +
    rope] and values [A, H, T, v]: the latent times W_kvb, the shared
    rotary key repeated to every head."""
    c_kv = rows[..., :config.kv_rank]
    k_rope = rows[..., config.kv_rank:config.kv_rank + config.rope_dim]
    kv = jnp.einsum("atc,chd->ahtd", c_kv, _w_kvb(attn, config),
                    preferred_element_type=jnp.float32).astype(rows.dtype)
    k_rope = jnp.broadcast_to(
        k_rope[:, None], kv.shape[:3] + (config.rope_dim,))
    return (jnp.concatenate([kv[..., :config.nope_dim], k_rope], axis=-1),
            kv[..., config.nope_dim:])


# -- attention: the absorbed decode step over gathered views (the oracle) --------

def absorbed_attention(config: LatentMoeConfig, q_full, view, side,
                       main_valid, side_valid):
    """q_full [S, 1, R, lanes] against the read-only main rows `view`
    [S, 1, T, lanes] and this round's `side` rows [S, 1, P, lanes]; V is
    the leading kv_rank lanes of the row that K is.  One softmax over
    (main ++ side), f32 accumulation; returns [S, 1, R, kv_rank]."""
    scale = config.softmax_scale
    rank = config.kv_rank
    scores_main = jnp.einsum("skrd,sktd->skrt", q_full, view,
                             preferred_element_type=jnp.float32) * scale
    scores_side = jnp.einsum("skrd,sktd->skrt", q_full, side,
                             preferred_element_type=jnp.float32) * scale
    main_t = view.shape[2]
    scores = jnp.concatenate(
        [jnp.where(main_valid, scores_main, -1e30),
         jnp.where(side_valid, scores_side, -1e30)], axis=-1)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("skrt,sktd->skrd",
                     weights[..., :main_t].astype(view.dtype),
                     view[..., :rank],
                     preferred_element_type=jnp.float32) + \
        jnp.einsum("skrt,sktd->skrd",
                   weights[..., main_t:].astype(side.dtype),
                   side[..., :rank], preferred_element_type=jnp.float32)
    return out.astype(q_full.dtype)


# -- attention: expanded, one piece of keys at a time ----------------------------

def _attend_piece(state, q, k, v, mask, scale: float):
    """Online softmax over one more piece of keys: state (row max, row
    sum, accumulator), q [A, H, C, D], k/v [A, H, T, *], mask
    broadcastable to [A, H, C, T]."""
    row_max, row_sum, acc = state
    scores = jnp.einsum("ahcd,ahtd->ahct", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask, scores, -1e30)
    new_max = jnp.maximum(row_max, scores.max(axis=-1, keepdims=True))
    weights = jnp.exp(scores - new_max)
    fade = jnp.exp(row_max - new_max)
    row_sum = row_sum * fade + weights.sum(axis=-1, keepdims=True)
    acc = acc * fade + jnp.einsum(
        "ahct,ahtd->ahcd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return new_max, row_sum, acc


def expanded_attention(layer, config: LatentMoeConfig, x, cos, sin,
                       offsets, prefix=None):
    """The expanded path over a block of tokens x [A, C, dim] that sit
    at positions offsets[a] + [0, C): causal among themselves, and
    after `prefix(piece) -> (rows [A, T, lanes], first position)` for
    piece in [0, prefix.pieces) where given (the extend's walk over the
    pool).  Returns (attention output [A, C, dim], the block's own rows
    [A, 1, C, lanes] for the cache)."""
    attn = layer["attn"]
    with jax.named_scope(SCOPE_ATTN_PROJ):
        q_nope, q_rope, rows = project_block(layer, config, x, cos, sin,
                                             offsets)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
    with jax.named_scope(SCOPE_MLA_EXPAND):
        k_own, v_own = expand_rows(attn, config, rows[:, 0])
    a, heads, c, _ = q.shape
    scale = config.softmax_scale
    with jax.named_scope(SCOPE_ATTN_CORE):
        state = (jnp.full((a, heads, c, 1), -1e30, jnp.float32),
                 jnp.zeros((a, heads, c, 1), jnp.float32),
                 jnp.zeros((a, heads, c, config.v_dim), jnp.float32))
        causal = jnp.tril(jnp.ones((c, c), bool))[None, None]
        state = _attend_piece(state, q, k_own, v_own, causal, scale)
    if prefix is not None:
        starts = jnp.asarray(offsets)

        def piece(j, state):
            piece_rows, first = prefix(j)
            with jax.named_scope(SCOPE_MLA_EXPAND):
                k, v = expand_rows(attn, config, piece_rows)
            with jax.named_scope(SCOPE_ATTN_CORE):
                pos = first + jnp.arange(piece_rows.shape[1])
                mask = (pos[None, :] < starts[:, None])[:, None, None, :]
                return _attend_piece(state, q, k, v, mask, scale)

        state = jax.lax.fori_loop(0, prefix.pieces, piece, state)
    with jax.named_scope(SCOPE_ATTN_CORE):
        _, row_sum, acc = state
        out = (acc / row_sum).astype(x.dtype)
    with jax.named_scope(SCOPE_ATTN_PROJ):
        return L.linear(attn["o"], L._merge_heads(out)), rows


# -- the expert layer --------------------------------------------------------------

def router_scores(config, logits):
    """A token's scores over ALL experts from the router's logits [N,
    num_experts] f32: each logit's sigmoid (`scoring_func` "sigmoid":
    DeepSeek-V3's, the default), or the softmax over all the experts
    where the configuration says `router_scores = "softmax"` (Qwen3-MoE's
    rule, ISSUE 38)."""
    if getattr(config, "router_scores", "sigmoid") == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    return jax.nn.sigmoid(logits)


def select_experts(config: LatentMoeConfig, scores, bias=None):
    """Which experts a token goes to, from its scores over ALL experts
    [N, num_experts] f32, sigmoids or a softmax (router_scores): (ids
    [N, top_k], weights [N, top_k] f32).
    `topk_method` "none", read as the plain rule: the top_k largest
    scores, no group restriction; the weights are the chosen scores
    over their sum, times routed_scale (a softmax's chosen
    probabilities renormalised, `norm_topk_prob`, at scale 1).  With a
    correction `bias` [num_experts] (`noaux_tc`) the choice is by
    score + bias and the weights are still the scores'."""
    if bias is None:
        chosen, ids = jax.lax.top_k(scores, config.top_k)
    else:
        _, ids = jax.lax.top_k(scores + bias, config.top_k)
        chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = chosen / chosen.sum(axis=-1, keepdims=True) * \
        config.routed_scale
    return ids.astype(jnp.int32), weights


def _gated(gate, up, limit=None):
    """silu(gate) * up(); with a `limit` (`swiglu_limit`) the gate is cut
    from above before its SiLU and the linear branch clipped to [-limit,
    limit].  `up` is called after the gate's SiLU, as the layers always
    ordered the two."""
    if limit is None:
        return jax.nn.silu(gate) * up()
    return jax.nn.silu(jnp.minimum(gate, limit)) * \
        jnp.clip(up(), -limit, limit)


def swiglu(layer, x, limit=None):
    """SwiGLU, clamped where the model states a `swiglu_limit`."""
    if limit is None:
        return _swiglu(layer, x)
    return L.linear(layer["down"], _gated(
        L.linear(layer["gate"], x), lambda: L.linear(layer["up"], x),
        limit))


def _expert_rows(experts, index: int, rows, limit=None):
    """Held expert `index` over rows [R, dim]: SwiGLU, f32 out."""
    hidden = _gated(
        L.linear({"w": experts["gate"]["w"][index]}, rows),
        lambda: L.linear({"w": experts["up"]["w"][index]}, rows), limit)
    return jnp.einsum("rf,fd->rd", hidden, experts["down"]["w"][index],
                      preferred_element_type=jnp.float32)


def moe_ffn(layer, config: LatentMoeConfig, x, live=None):
    """The sparse layer's feed-forward over x [..., dim]: the shared
    expert, where the layer has one (`layer["shared"]`; a model of
    routed experts alone has none, ISSUE 38), plus what the experts HELD
    HERE give the tokens routed to them by sigmoid or softmax scores
    (router_scores).  `live` [...] bool leaves tokens out of the routing
    (a slot that decodes nothing, a prompt's padding): they cost no
    expert its weights.  Returns (y, counts int32 [4] in MOE_COUNTERS'
    order)."""
    shape = x.shape
    tokens = x.reshape(-1, shape[-1])
    n = tokens.shape[0]
    held, first = config.experts_held, config.experts_first
    with jax.named_scope(SCOPE_MOE_ROUTE):
        # in float32, as published: a router rounded to bfloat16 picks
        # other experts at near-ties
        logits = jnp.einsum(
            "nd,de->ne", tokens.astype(jnp.float32),
            layer["router"]["w"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        ids, weights = select_experts(config, router_scores(config, logits),
                                      layer["router"].get("bias"))
        alive = jnp.ones((n,), bool) if live is None \
            else live.reshape(-1)
        local = ids - first
        here = (local[:, :, None] == jnp.arange(held)) & \
            alive[:, None, None]                              # [N, k, E]
        weight_of = (weights[:, :, None] * here).sum(axis=1)  # [N, E] f32
        routed = here.any(axis=1)                             # [N, E]
        loads = routed.sum(axis=0).astype(jnp.int32)          # [E]
        counts = jnp.stack([
            jnp.int32(1), (loads > 0).sum().astype(jnp.int32),
            loads.sum(), alive.sum().astype(jnp.int32) * config.top_k])
        if n > _EXPERT_TILE:
            place = jnp.cumsum(routed, axis=0) - 1            # [N, E]
    limit = getattr(config, "swiglu_limit", None)
    y = None
    if "shared" in layer:
        with jax.named_scope(SCOPE_MOE_SHARED):
            y = swiglu(layer["shared"], tokens, limit)
    with jax.named_scope(SCOPE_MOE_EXPERTS):
        experts = layer["experts"]
        out = jnp.zeros((n, shape[-1]), jnp.float32)
        for e in range(held):
            if n <= _EXPERT_TILE:
                # a decode block: every row through the expert, weighted
                # (zero where it was not routed), and nothing at all
                # where no token came: its weights are not read
                def run(out, e=e):
                    return out + _expert_rows(experts, e, tokens, limit) * \
                        weight_of[:, e, None]

                out = jax.lax.cond(loads[e] > 0, run, lambda out: out, out)
                continue

            def tile(i, out, e=e):
                # the expert's tokens i*R .. (i+1)*R-1, in token order:
                # selection and scatter-back are exact 0/1 matrix
                # products, which the MXU takes as they come
                slots = i * _EXPERT_TILE + jnp.arange(_EXPERT_TILE)
                pick = (place[None, :, e] == slots[:, None]) & \
                    routed[None, :, e]                        # [R, N]
                rows = jnp.einsum(
                    "rn,nd->rd", pick.astype(tokens.dtype), tokens,
                    preferred_element_type=jnp.float32
                ).astype(tokens.dtype)
                gains = pick.astype(jnp.float32) @ weight_of[:, e]
                given = (_expert_rows(experts, e, rows, limit) *
                         gains[:, None]).astype(tokens.dtype)
                return out + jnp.einsum(
                    "rn,rd->nd", pick.astype(tokens.dtype), given,
                    preferred_element_type=jnp.float32)

            out = jax.lax.fori_loop(
                0, -(-loads[e] // _EXPERT_TILE), tile, out)
        y = out.astype(tokens.dtype) if y is None \
            else y + out.astype(y.dtype)
    return y.reshape(shape), counts


def layer_ffn(layer, config: LatentMoeConfig, x, live=None):
    """A layer's feed-forward with its norm: (x + ffn(norm(x)), counts
    or None for a dense layer), under the scopes of each part."""
    if "experts" not in layer:
        with jax.named_scope(SCOPE_MLP):
            return x + _swiglu(layer, L.rms_norm(layer["ln_mlp"], x)), None
    with jax.named_scope(SCOPE_MOE_ROUTE):
        normed = L.rms_norm(layer["ln_mlp"], x)
    y, counts = moe_ffn(layer, config, normed, live)
    return x + y, counts


# -- whole passes ------------------------------------------------------------------

def latent_moe_hidden(params, config: LatentMoeConfig, tokens, live=None):
    """tokens [A, T] from position 0 -> (final hidden states [A, T, dim]
    after the last norm, per-layer cache rows [A, 1, T, lanes]): the
    expanded path with no prefix, which an admit and the uncached
    forward share."""
    cos, sin = yarn_rope_tables(config)
    x = L.embedding(params["embed"], tokens).astype(config.dtype)
    rows = []
    for layer in params["layers"]:
        with jax.named_scope(SCOPE_ATTN_PROJ):
            normed = L.rms_norm(layer["ln_attn"], x)
        attended, own = expanded_attention(layer, config, normed, cos, sin,
                                           jnp.int32(0))
        x = x + attended
        x, _ = layer_ffn(layer, config, x, live)
        rows.append(own)
    with jax.named_scope(SCOPE_HEAD):
        return L.rms_norm(params["ln_out"], x), rows


def latent_moe_forward(params, config: LatentMoeConfig, tokens):
    """Teacher-forced full-sequence forward: tokens [A, T] -> f32 logits
    [A, T, vocab]."""
    hidden, _ = latent_moe_hidden(params, config, tokens)
    return L.linear_logits(params["lm_head"], hidden)


# -- as a PagedModel (what serving_paged's builders call) ------------------------

def _step_argmax(params, config: LatentMoeConfig, token_block, attend,
                 live):
    """serving._token_block_argmax (the embedding, the attention norm,
    the head) with this model's feed-forward in its seam; the expert
    layers' counts add up over the layers."""
    from ..serving import _token_block_argmax
    alive = jnp.broadcast_to(live[:, None], token_block.shape)
    counted = []

    def ffn(layer, x):
        x, counts = layer_ffn(layer, config, x, alive)
        if counts is not None:
            counted.append(counts)
        return x

    tokens = _token_block_argmax(params, config, token_block, attend, ffn)
    return tokens, sum(counted, jnp.zeros((len(MOE_COUNTERS),), jnp.int32))


def _step_attention(kernel: bool):
    """The ABSORBED path of a decode step over the round's [S, 1] block:
    through the pallas walk over the slot's own live blocks (kernel), or
    over gathered views (the CPU's path and the oracle)."""

    def attend(tables, layer, config, x, cos, sin, leaves, views, sides,
               entry_lengths, lengths, step_index, entry_active, state,
               active):
        (side,) = sides
        attn = layer["attn"]
        with jax.named_scope(SCOPE_ATTN_PROJ):
            q_nope, q_rope, rows = project_block(layer, config, x, cos,
                                                 sin, lengths)
            side = jax.lax.dynamic_update_slice_in_dim(
                side, rows, step_index, axis=2)
            q_full = absorb_queries(attn, config, q_nope, q_rope)
        # this round's rows a slot may see: those written so far, up to
        # its own take (serving._slot_attention_block's mask)
        side_positions = jnp.arange(side.shape[2])
        side_ok = ((side_positions[None] <= step_index) &
                   (side_positions[None] <
                    (lengths - entry_lengths + 1)[:, None]))      # [S, P]
        with jax.named_scope(SCOPE_ATTN_CORE):
            if kernel:
                from ..ops.paged_attention import paged_decode_attention
                walk = entry_lengths if entry_active is None \
                    else jnp.where(entry_active, entry_lengths, 0)
                o_lat = paged_decode_attention(
                    q_full, leaves[0], None, tables, side,
                    side[..., :config.kv_rank], side_ok[:, None, :],
                    walk, groups=config.num_heads,
                    scale=config.softmax_scale).astype(x.dtype)
            else:
                view = views[0]
                main_valid = (jnp.arange(view.shape[2])[None] <
                              entry_lengths[:, None])[:, None, None]
                o_lat = absorbed_attention(
                    config, q_full, view, side, main_valid,
                    side_ok[:, None, None])
        with jax.named_scope(SCOPE_ATTN_PROJ):
            return absorb_output(attn, config, o_lat, x.shape[1]), \
                (side,), (), None

    return attend


def _prefill(params, config: LatentMoeConfig, prompts, valid, true_lens):
    live = valid[:, None] & (jnp.arange(prompts.shape[1])[None] <
                             true_lens[:, None])
    hidden, rows = latent_moe_hidden(params, config, prompts, live)
    return hidden, [(own,) for own in rows]


class _PoolPrefix:
    """The rows a pool holds of each row's prefix, handed out a piece of
    `piece_blocks` blocks at a time through the row's table; as many
    pieces as the longest live prefix needs."""

    def __init__(self, pool, tables, piece_blocks: int, pieces):
        self.pool, self.tables = pool, tables
        self.piece_blocks, self.pieces = piece_blocks, pieces

    def __call__(self, j):
        ids = jax.lax.dynamic_slice_in_dim(
            self.tables, j * self.piece_blocks, self.piece_blocks, axis=1)
        rows = jnp.take(self.pool, ids, axis=0)[:, :, 0]  # [A, pb, B, lanes]
        a, pb, b, lanes = rows.shape
        return rows.reshape(a, pb * b, lanes), j * pb * b


def _extend_prepare(config: LatentMoeConfig, chunk_len: int, kernel: bool,
                    ctx):
    """The table padded to whole pieces, how many pieces the longest
    live prefix spans, and which of the chunk's tokens are real."""
    block = ctx["block_tokens"]
    tables = ctx["tables_rows"]
    piece_blocks = max(1, min(tables.shape[1], _PREFIX_PIECE // block))
    pad = -tables.shape[1] % piece_blocks
    if pad:
        tables = jnp.pad(tables, ((0, 0), (0, pad)))     # the null block
    longest = jnp.max(jnp.where(ctx["valid"], ctx["offsets"], 0))
    pieces = -(-longest // (piece_blocks * block))
    # a finishing row's tokens past its prompt's end are padding
    live = ctx["valid"][:, None] & (
        ~ctx["finish"][:, None] |
        (jnp.arange(chunk_len)[None] <= ctx["final_idx"][:, None]))
    return {"tables": tables, "piece_blocks": piece_blocks,
            "pieces": pieces, "live": live}


def _extend_layer(kernel: bool):
    """The EXPANDED path of a chunk: per-head keys and values rebuilt
    from the prefix's rows piece by piece (kernel or not: the pallas
    walk is the absorbed path's)."""

    def extend_layer(layer, config, x, cos, sin, leaves, ctx, prepared):
        prefix = _PoolPrefix(leaves[0], prepared["tables"],
                             prepared["piece_blocks"], prepared["pieces"])
        with jax.named_scope(SCOPE_ATTN_PROJ):
            normed = L.rms_norm(layer["ln_attn"], x)
        attended, rows = expanded_attention(
            layer, config, normed, cos, sin, ctx["offsets"], prefix)
        x, _ = layer_ffn(layer, config, x + attended, prepared["live"])
        return x, (rows,)

    return extend_layer


def _walks(config: LatentMoeConfig, kv_int8: bool,
           interpret: bool) -> str | None:
    from ..ops.paged_attention import walks_live_blocks
    return "kernel" if walks_live_blocks(config.row_lanes, kv_int8,
                                         interpret) else None


@functools.cache
def _paged_model():
    from ..serving_paged import PagedModel
    # the paths a latent pool is carried through: none beyond the paged
    # decoder itself (native rows, unshared, one device)
    return PagedModel(
        rope=yarn_rope_tables, token_block_argmax=_step_argmax,
        step_attention=_step_attention, prefill=_prefill,
        extend_prepare=_extend_prepare, extend_layer=_extend_layer,
        walks=_walks, counters=MOE_COUNTERS, supports=frozenset())
