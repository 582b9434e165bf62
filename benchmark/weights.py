"""Seeded random weights, made by the benchmark and by nothing else.

The drivers hand these to the program in the dtype it serves; the plain
references make the same values again from the same key, layer by layer,
and never see an array the program has touched.  The tree LAYOUTS are the
program's (its `llama_init`): a test holds them equal at
a tiny size.  The scales follow the usual fan-in rule so activations keep
an order-one spread through the depth.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def key_for(seed: int):
    """A PRNG key from any whole number up to a little over 2**31: the
    low 31 bits seed the key, what is above them is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _rounded(values, exponent_bits: int, mantissa_bits: int):
    """Rounded to a narrower float by an operation the compiler keeps: it
    is free to drop a convert down and up again (`allow excess
    precision`), which would leave the reference with other weights than
    the program serves."""
    return jax.lax.reduce_precision(values, exponent_bits, mantissa_bits)


def _normal(key, index: int, shape, scale: float, dtype):
    values = jax.random.normal(jax.random.fold_in(key, index), shape,
                               jnp.float32) * scale
    kind = jnp.finfo(dtype)
    return _rounded(values, kind.nexp, kind.nmant).astype(dtype)


def _linear(key, index: int, fan_in: int, fan_out: int, dtype) -> dict:
    return {"w": _normal(key, index, (fan_in, fan_out), fan_in ** -0.5,
                         dtype)}


# -- decoder-only language model (the program's llama tree) -------------------

def decoder_layer(key, index: int, sizes: dict, dtype) -> dict:
    """Layer `index` alone, so a reference can make one layer at a time."""
    key = jax.random.fold_in(key, 1 + index)
    dim, ffn = sizes["hidden_size"], sizes["intermediate_size"]
    head = sizes["head_dim"]
    q_out = sizes["num_attention_heads"] * head
    kv_out = sizes["num_key_value_heads"] * head
    return {
        "ln_attn": {"scale": jnp.ones((dim,), dtype)},
        "attn": {"q": _linear(key, 0, dim, q_out, dtype),
                 "k": _linear(key, 1, dim, kv_out, dtype),
                 "v": _linear(key, 2, dim, kv_out, dtype),
                 "o": _linear(key, 3, q_out, dim, dtype)},
        "ln_mlp": {"scale": jnp.ones((dim,), dtype)},
        "gate": _linear(key, 4, dim, ffn, dtype),
        "up": _linear(key, 5, dim, ffn, dtype),
        "down": _linear(key, 6, ffn, dim, dtype),
    }


def decoder_embed(key, sizes: dict, dtype) -> dict:
    return {"table": _normal(jax.random.fold_in(key, 0), 0,
                             (sizes["vocab_size"], sizes["hidden_size"]),
                             0.02, dtype)}


def decoder_head(key, sizes: dict, dtype) -> dict:
    """The final norm and the untied output head."""
    return {"ln_out": {"scale": jnp.ones((sizes["hidden_size"],), dtype)},
            "lm_head": _linear(jax.random.fold_in(key, 0), 1,
                               sizes["hidden_size"], sizes["vocab_size"],
                               dtype)}


def decoder_weights(key, sizes: dict, dtype) -> dict:
    """The whole tree; wrap in one `jax.jit` to make it on the device."""
    return {"embed": decoder_embed(key, sizes, dtype),
            "layers": [decoder_layer(key, i, sizes, dtype)
                       for i in range(sizes["num_hidden_layers"])],
            **decoder_head(key, sizes, dtype)}


def round_to_fp8(tree):
    """Every matrix rounded to a float8 (4 exponent bits, 3 of mantissa,
    one scale per tensor) and back: the nearest precision below bfloat16,
    for the control."""
    def one(leaf):
        if leaf.ndim < 2:
            return leaf
        wide = leaf.astype(jnp.float32)
        scale = jnp.max(jnp.abs(wide)) / 224.0
        return (_rounded(wide / scale, 4, 3) * scale).astype(leaf.dtype)
    return jax.tree.map(one, tree)
