"""Plain reference for a decoder-only language model with multi-head
latent attention (MLA), a YaRN rotary table, RMSNorm, leading dense SwiGLU
layers and then sigmoid-routed experts with a shared expert: the block of
DeepSeek-V3 (arXiv:2412.19437, `modeling_deepseek.py`), whose keys A.X-K1's
published config uses throughout.

float32 throughout, `jax.default_matmul_precision("highest")`, no kernels,
no cache, no batching, EXPANDED attention only (per-head keys and values
from the latent, never the absorbed form the program decodes with): one
causal forward over each prompt with its served tokens, padded to its own
length, one layer at a time so that the float32 weights of a single layer
(2.7 GB) are all that is ever resident.  It imports nothing of the program
and makes its own weights from the seed (benchmark/weights_latent_moe.py).

Per layer, pre-norm residual:
  c_q = rms(x W_qa); q = c_q W_qb -> heads of [q_nope | q_rope]
  [c_kv | k_rope] = x W_kva; c_kv = rms(c_kv); k_rope one head for all
  [k_nope | v] = c_kv W_kvb per head; scores (q_nope.k_nope + q_rope.k_rope)
  * s, s = (nope + rope)^-0.5 * m^2, m = 0.1 ln(factor) + 1; causal softmax;
  x v; W_o.  Dense layers: SwiGLU.  Sparse layers: g = sigmoid(x W_r) over
  ALL experts; the top_k largest; weights g_i / sum g * routed_scaling_factor;
  y = shared(x) + sum over the experts HELD of w_i expert_i(x).

Departures from the published description, each forced or stated:
  * `topk_method` is "none", neither of DeepSeek's names: read as the plain
    rule (`select`: no correction bias, no group restriction);
  * the experts held are the configuration's share (`n_routed_experts` from
    `deployment.experts_first`); what the absent ones would add is left
    out, as in the program;
  * rotary pairs are consecutive lanes (2i, 2i+1), DeepSeek's own
    `view_as_complex`, which the Hugging Face port reaches by a permutation.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_latent_moe as W
from benchmark.reference.decoder_lm import logit_gaps

QUERY_BLOCK = 512        # query rows attended at once: [H, 512, T] scores


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def yarn_inverse_frequencies(sizes: dict) -> np.ndarray:
    """rope_dim / 2 inverse frequencies of the YaRN-scaled rotary."""
    dim, base = sizes["qk_rope_head_dim"], float(sizes["rope_theta"])
    scaling = sizes["rope_scaling"]
    factor = float(scaling["factor"])
    original = scaling["original_max_position_embeddings"]
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def dimension_of(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dimension_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dimension_of(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / factor) * ramp + plain * (1 - ramp)


def attention_factor(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(sizes: dict) -> float:
    scaling = sizes["rope_scaling"]
    m = attention_factor(scaling["factor"], scaling["mscale_all_dim"])
    return (sizes["qk_nope_head_dim"]
            + sizes["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, inverse, table_scale: float):
    """x: [T, H, D]; rotates lanes (2i, 2i+1) by position * inverse[i]."""
    t = x.shape[0]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inverse[None, :]
    cos = jnp.cos(angles)[:, None, :] * table_scale
    sin = jnp.sin(angles)[:, None, :] * table_scale
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def select(scores, top_k: int, routed_scale: float):
    """The plain rule: the top_k largest of a token's scores over all
    experts -> (a 0/1 mask [T, E], the weight of each chosen expert [T, E])."""
    kth = jnp.sort(scores, axis=-1)[:, -top_k][:, None]
    chosen = scores >= kth
    kept = jnp.where(chosen, scores, 0.0)
    return chosen, kept / kept.sum(axis=-1, keepdims=True) * routed_scale


def _swiglu(ffn, h):
    return (jax.nn.silu(h @ ffn["gate"]["w"]) * (h @ ffn["up"]["w"])) \
        @ ffn["down"]["w"]


def attention(layer, h, sizes: dict):
    t = h.shape[0]
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank, eps = sizes["kv_lora_rank"], sizes["rms_norm_eps"]
    scaling = sizes["rope_scaling"]
    inverse = jnp.asarray(yarn_inverse_frequencies(sizes), jnp.float32)
    table = attention_factor(scaling["factor"], scaling["mscale"]) / \
        attention_factor(scaling["factor"], scaling["mscale_all_dim"])
    attn = layer["attn"]
    c_q = _rms_norm(attn["q_norm"]["scale"], h @ attn["q_a"]["w"], eps)
    q = (c_q @ attn["q_b"]["w"]).reshape(t, heads, nope + rope)
    kv = h @ attn["kv_a"]["w"]
    c_kv = _rms_norm(attn["kv_norm"]["scale"], kv[:, :rank], eps)
    k_rope = _rope(kv[:, None, rank:], inverse, table)        # [T, 1, rope]
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], inverse, table)], axis=-1)
    kvb = (c_kv @ attn["kv_b"]["w"]).reshape(
        t, heads, nope + sizes["v_head_dim"])
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_rope, (t, heads, rope))], -1)
    v = kvb[..., nope:]
    scale = softmax_scale(sizes)
    # a block of query rows at a time: all of [H, T, T] at 8,192 tokens
    # would be 17 GB
    rows_at_once = min(QUERY_BLOCK, t)
    if t % rows_at_once:
        raise ValueError(f"{t} tokens: pad to a multiple of {QUERY_BLOCK}")

    def block(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, rows_at_once, 0)
        scores = jnp.einsum("qhd,khd->hqk", rows, k) * scale
        causal = (start + jnp.arange(rows_at_once))[:, None] >= \
            jnp.arange(t)[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    attended = jax.lax.map(block, jnp.arange(0, t, rows_at_once))
    return attended.reshape(t, heads * sizes["v_head_dim"]) @ attn["o"]["w"]


def experts_part(layer, h, sizes: dict):
    """What the experts HELD give: sum over them of w_i expert_i(h)."""
    scores = jax.nn.sigmoid(h @ layer["router"]["w"])
    _, weights = select(scores, sizes["num_experts_per_tok"],
                        sizes["routed_scaling_factor"])
    first = W.experts_first(sizes)
    out = jnp.zeros_like(h)
    for e in range(sizes["n_routed_experts"]):
        one = {name: {"w": layer["experts"][name]["w"][e]}
               for name in ("gate", "up", "down")}
        out = out + weights[:, first + e, None] * _swiglu(one, h)
    return out


def layer_forward(layer, x, *, sizes: dict, sparse: bool):
    """One pre-norm block over one sequence x: [T, dim], causal."""
    eps = sizes["rms_norm_eps"]
    x = x + attention(layer, _rms_norm(layer["ln_attn"]["scale"], x, eps),
                      sizes)
    h = _rms_norm(layer["ln_mlp"]["scale"], x, eps)
    if not sparse:
        return x + _swiglu(layer, h)
    return x + _swiglu(layer["shared"], h) + experts_part(layer, h, sizes)


def _f32(tree):
    return jax.tree.map(lambda leaf: leaf.astype(jnp.float32), tree)


def forward_logits(tokens, sizes: dict, seed: int, dtype, transform=None):
    """Logits [T, vocab] of one sequence, the whole forward at once, for
    the tests (T at most QUERY_BLOCK, or a multiple of it)."""
    key = W.key_for(seed)
    transform = transform or (lambda tree: tree)
    with jax.default_matmul_precision("highest"):
        embed = W.decoder_embed(key, sizes, dtype)
        x = embed["table"][np.asarray(tokens)].astype(jnp.float32)
        for index in range(sizes["num_hidden_layers"]):
            sparse = W.is_sparse(sizes, index)
            layer = transform(_f32(W.decoder_layer(key, index, sizes, dtype,
                                                   sparse)))
            x = layer_forward(layer, x, sizes=sizes, sparse=sparse)
        head = transform(_f32(W.decoder_head(key, sizes, dtype)))
        hidden = _rms_norm(head["ln_out"]["scale"], x, sizes["rms_norm_eps"])
        return hidden @ head["lm_head"]["w"]


def check(samples: list, sizes: dict, seed: int, dtype, control: bool = False,
          say=lambda message: None) -> dict:
    """samples: [{"prompt": [...], "served": [...]}].  Returns
    {"positions": served tokens compared, "numbers": {name: [a value per
    sample]}, "control": the same names read off the control, or None}.
    Two numbers, both of a served token's gap below the reference's best
    in standard deviations of that position's logits (for the control: of
    the token that float8 weights put first): `served_token_gap_std`, the
    WIDEST over a sample's tokens, Mistral's number, a value a sample, and
    `served_token_gap_mean_std`, the MEAN over ALL the samples' served
    tokens, one value a run (a short answer's own mean would swing with
    one token).  With routed experts
    the widest is a heavy-tailed reading of sound runs: a bfloat16
    activation that lands the other side of a near-tie between a token's
    8th and 9th expert computes another function from there on, exactly
    and legitimately, and the token it picks can lie a whole deviation
    down (PERF.md, correctness: sound runs read 0.2-1.3 on the chip where
    the same program without a choice of experts reads 0.05).  The mean is
    what tells a sound run from a lower precision: a few flips in a
    hundred tokens against every token moved.  `dtype` is the type the
    weights are served in: the reference computes in float32 on exactly
    those values."""
    key = W.key_for(seed)
    eps = sizes["rms_norm_eps"]
    rows = [list(s["prompt"]) + list(s["served"])[:-1] for s in samples]
    lengths = [len(row) for row in rows]

    def padded(row):
        out = np.zeros((-(-len(row) // QUERY_BLOCK) * QUERY_BLOCK,), np.int32)
        out[:len(row)] = row
        return out

    with jax.default_matmul_precision("highest"):
        # the key is an argument: closed over, every seed would compile
        embed = jax.jit(lambda key: W.decoder_embed(key, sizes, dtype))(key)
        x = [embed["table"][padded(row)].astype(jnp.float32) for row in rows]
        del embed
        x_control = list(x) if control else None
        make = {sparse: jax.jit(lambda key, i, sparse=sparse: _f32(
            W.decoder_layer(key, i, sizes, dtype, sparse)))
            for sparse in (False, True)}
        forward = {sparse: jax.jit(functools.partial(
            layer_forward, sizes=sizes, sparse=sparse))
            for sparse in (False, True)}
        to_fp8 = jax.jit(W.round_to_fp8)
        for index in range(sizes["num_hidden_layers"]):
            sparse = W.is_sparse(sizes, index)
            layer = make[sparse](key, jnp.int32(index))
            x = [forward[sparse](layer, row) for row in x]
            if control:
                layer = to_fp8(layer)
                x_control = [forward[sparse](layer, row)
                             for row in x_control]
            del layer
        say(f"reference: {len(rows)} sequences of up to {max(lengths)} "
            f"tokens through {sizes['num_hidden_layers']} layers")

        head = jax.jit(lambda key: _f32(
            W.decoder_head(key, sizes, dtype)))(key)

        @jax.jit
        def project(head, hidden, positions):
            hidden = _rms_norm(head["ln_out"]["scale"], hidden[positions],
                               eps)
            return hidden @ head["lm_head"]["w"]

        head_control = to_fp8(head) if control else None
        gaps, control_gaps, count = [], [], 0
        means, control_means, sums = [], [], [0.0, 0.0]
        for i, sample in enumerate(samples):
            served = np.asarray(sample["served"], np.int32)
            # the logits that chose served[j] sit at the position before it
            positions = len(sample["prompt"]) - 1 + np.arange(len(served))
            logits = project(head, x[i], positions)
            control_logits = project(head_control, x_control[i], positions) \
                if control else None
            gap, control_gap = logit_gaps(logits, jnp.asarray(served),
                                          control_logits)
            gaps.append(float(jnp.max(gap)))
            means.append(float(jnp.mean(gap)))
            sums[0] += float(jnp.sum(gap))
            count += len(served)
            if control:
                control_gaps.append(float(jnp.max(control_gap)))
                control_means.append(float(jnp.mean(control_gap)))
                sums[1] += float(jnp.sum(control_gap))
    say(f"served token gaps, widest a sample {gaps}, mean a sample {means}"
        + (f"; the control's {control_gaps} and {control_means}"
           if control else ""))
    return {"positions": count,
            "numbers": {"served_token_gap_std": gaps,
                        "served_token_gap_mean_std": [sums[0] / count]},
            "control": {"served_token_gap_std": control_gaps,
                        "served_token_gap_mean_std": [sums[1] / count]}
            if control else None}
