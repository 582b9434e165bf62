"""Plain reference for a decoder-only language model with grouped-query
attention, rotary positions, RMSNorm and a SwiGLU feed-forward (Mistral
7B, arXiv:2310.06825, and mistral-inference's `transformer.py`).

float32 throughout, `jax.default_matmul_precision("highest")`, no
kernels, no cache, no batching: one causal forward over each prompt with
its served tokens, one layer at a time so that the float32 weights of a
single layer are all that is ever resident.  It imports nothing of the
program and makes its own weights from the seed (benchmark/weights.py).

Departures from the published description, each forced by the program:
  * rotary pairs are consecutive lanes (2i, 2i+1), as in mistral-inference,
    not the half-split of the Hugging Face port;
  * `rms_norm_eps` is read from the configuration file, which states the
    value the program computes with.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as W


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta: float):
    """x: [T, H, D]; rotates lanes (2i, 2i+1) by position * theta^(-2i/D)."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def layer_forward(layer, x, *, heads: int, kv_heads: int, head_dim: int,
                  theta: float, eps: float):
    """One pre-norm block over one sequence x: [T, dim], causal."""
    t = x.shape[0]
    h = _rms_norm(layer["ln_attn"]["scale"], x, eps)
    q = (h @ layer["attn"]["q"]["w"]).reshape(t, heads, head_dim)
    k = (h @ layer["attn"]["k"]["w"]).reshape(t, kv_heads, head_dim)
    v = (h @ layer["attn"]["v"]["w"]).reshape(t, kv_heads, head_dim)
    q, k = _rope(q, theta), _rope(k, theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(head_dim)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attended = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attended.reshape(t, heads * head_dim) @ layer["attn"]["o"]["w"]
    h = _rms_norm(layer["ln_mlp"]["scale"], x, eps)
    gated = jax.nn.silu(h @ layer["gate"]["w"]) * (h @ layer["up"]["w"])
    return x + gated @ layer["down"]["w"]


def _f32(tree):
    return jax.tree.map(lambda leaf: leaf.astype(jnp.float32), tree)


def logit_gaps(logits, served, control_logits=None):
    """Per position: how far the served token's logit lies below the
    best, in standard deviations of that position's logits; and, for the
    control, the same for the token the lower precision puts first."""
    std = jnp.std(logits, axis=-1)
    best = jnp.max(logits, axis=-1)
    chosen = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    gap = (best - chosen) / std
    if control_logits is None:
        return gap, None
    first = jnp.argmax(control_logits, axis=-1)
    theirs = jnp.take_along_axis(logits, first[:, None], axis=1)[:, 0]
    return gap, (best - theirs) / std


def check(samples: list, sizes: dict, seed: int, dtype, control: bool = False,
          say=lambda message: None) -> dict:
    """samples: [{"prompt": [...], "served": [...]}].  Returns
    {"positions": served tokens compared, "numbers": {name: [a value per
    sample]}, "control": the same names read off the control, or None}.
    The one number here is `served_token_gap_std`, the widest gap of a
    served token; for the control, of the token that float8 weights put
    first.  `dtype` is the type the weights are served in: the reference
    computes in float32 on exactly those values."""
    key = W.key_for(seed)
    shape = dict(heads=sizes["num_attention_heads"],
                 kv_heads=sizes["num_key_value_heads"],
                 head_dim=sizes["head_dim"], theta=sizes["rope_theta"],
                 eps=sizes["rms_norm_eps"])
    rows = [list(s["prompt"]) + list(s["served"])[:-1] for s in samples]
    longest = max(len(row) for row in rows)
    padded = -(-longest // 128) * 128
    tokens = np.zeros((len(rows), padded), np.int32)
    for i, row in enumerate(rows):
        tokens[i, :len(row)] = row

    with jax.default_matmul_precision("highest"):
        # the key is an argument: closed over, every seed would compile
        embed = jax.jit(lambda key: W.decoder_embed(key, sizes, dtype))(key)
        x = [embed["table"][tokens[i]].astype(jnp.float32)
             for i in range(len(rows))]
        del embed
        x_control = list(x) if control else None
        make = jax.jit(lambda key, i: _f32(
            W.decoder_layer(key, i, sizes, dtype)))
        forward = jax.jit(functools.partial(layer_forward, **shape))
        to_fp8 = jax.jit(W.round_to_fp8)
        for index in range(sizes["num_hidden_layers"]):
            layer = make(key, index)
            x = [forward(layer, row) for row in x]
            if control:
                layer = to_fp8(layer)
                x_control = [forward(layer, row) for row in x_control]
            del layer
        say(f"reference: {len(rows)} sequences of up to {longest} tokens "
            f"through {sizes['num_hidden_layers']} layers")

        head = jax.jit(lambda key: _f32(
            W.decoder_head(key, sizes, dtype)))(key)

        @jax.jit
        def project(head, hidden, positions):
            hidden = _rms_norm(head["ln_out"]["scale"], hidden[positions],
                               shape["eps"])
            return hidden @ head["lm_head"]["w"]

        head_control = to_fp8(head) if control else None
        gaps, control_gaps, count = [], [], 0
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
            count += len(served)
            if control:
                control_gaps.append(float(jnp.max(control_gap)))
    return {"positions": count, "numbers": {"served_token_gap_std": gaps},
            "control": {"served_token_gap_std": control_gaps}
            if control else None}
