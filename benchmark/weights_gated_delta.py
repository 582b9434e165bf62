"""Seeded random weights for the Gated-DeltaNet hybrid decoder (the program's
`models/gated_delta.py` tree: recurrent layers with one gate a head beside
full-attention layers with a norm over the whole width of q and k), made by
the benchmark and by nothing else, one layer at a time.

`sizes` is a configuration file's content under its published keys.
Matrices follow the fan-in rule.  The decay's parameters are drawn so that a
head forgets over a few tokens to a few hundred: g = -exp(A_log) softplus(
W_a u + dt_bias) with A_log uniform in [-0.5, 0.5] and dt_bias normal about
-3 (softplus(-3) = 0.05: twenty tokens; the published model's are trained
and not in the configuration).

THE EMBEDDING is drawn wide (EMBED_STD, below): the block adds RMSNorm(mix(
x)), of unit size whatever x is, so a row of the other configurations' 0.02
would be a fiftieth of the residual after one layer and the served tokens
would not depend on the token before them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import (_linear, _normal, decoder_head,  # noqa: F401
                               key_for, round_to_fp8)

EMBED_STD = 1.0

KINDS = {"linear_attention": "gdn", "full_attention": "full"}


def gdn_sizes(sizes: dict) -> tuple:
    """(heads, key lanes, value lanes a head, convolution taps)."""
    if sizes["linear_num_key_heads"] != sizes["linear_num_value_heads"]:
        raise ValueError("the program pairs one key head with one value head")
    return (sizes["linear_num_value_heads"], sizes["linear_key_head_dim"],
            sizes["linear_value_head_dim"], sizes["linear_conv_kernel_dim"])


def full_head_dim(sizes: dict) -> int:
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def decoder_embed(key, sizes: dict, dtype) -> dict:
    return {"table": _normal(jax.random.fold_in(key, 0), 0,
                             (sizes["vocab_size"], sizes["hidden_size"]),
                             EMBED_STD, dtype)}


def _ones(width: int, dtype) -> dict:
    return {"scale": jnp.ones((width,), dtype)}


def decoder_layer(key, index, sizes: dict, dtype, kind: str) -> dict:
    """Layer `index` alone, of `kind` "gdn" or "full" (`index` may be
    traced: one program makes every layer of a kind)."""
    key = jax.random.fold_in(key, 1 + index)
    dim, ffn = sizes["hidden_size"], sizes["intermediate_size"]
    layer = {"ln_attn": _ones(dim, dtype), "ln_mlp": _ones(dim, dtype),
             "gate": _linear(key, 20, dim, ffn, dtype),
             "up": _linear(key, 21, dim, ffn, dtype),
             "down": _linear(key, 22, ffn, dim, dtype)}
    if kind == "full":
        wide = sizes["num_attention_heads"] * full_head_dim(sizes)
        return layer | {"attn": {
            "q": _linear(key, 0, dim, wide, dtype),
            "k": _linear(key, 1, dim, wide, dtype),
            "v": _linear(key, 2, dim, wide, dtype),
            "o": _linear(key, 3, wide, dim, dtype),
            "q_norm": _ones(wide, dtype), "k_norm": _ones(wide, dtype)}}
    heads, dk, dv, taps = gdn_sizes(sizes)
    channels = heads * (2 * dk + dv)
    return layer | {"gdn": {
        "q": _linear(key, 0, dim, heads * dk, dtype),
        "k": _linear(key, 1, dim, heads * dk, dtype),
        "v": _linear(key, 2, dim, heads * dv, dtype),
        "conv": {"w": _normal(key, 3, (taps, channels), taps ** -0.5, dtype)},
        "a": _linear(key, 4, dim, heads, dtype),
        "a_log": jax.random.uniform(jax.random.fold_in(key, 5), (heads,),
                                    jnp.float32, -0.5, 0.5),
        "dt_bias": jax.random.normal(jax.random.fold_in(key, 6), (heads,),
                                     jnp.float32) - 3.0,
        "b": _linear(key, 7, dim, heads, dtype),
        "g": _linear(key, 8, dim, heads * dv, dtype),
        "o_norm": _ones(dv, dtype),
        "o": _linear(key, 9, heads * dv, dim, dtype)}}


def kinds(sizes: dict) -> list:
    if len(sizes["layer_types"]) != sizes["num_hidden_layers"]:
        raise ValueError("layer_types names every layer")
    return [KINDS[kind] for kind in sizes["layer_types"]]


def decoder_weights(key, sizes: dict, dtype, transform=None) -> dict:
    """The whole tree, made on the device layer by layer (one program a
    kind of layer); `transform` is applied to each piece as it is made
    (the float8 control)."""
    transform = transform or (lambda tree: tree)
    make = {kind: jax.jit(lambda key, i, kind=kind: transform(
        decoder_layer(key, i, sizes, dtype, kind))) for kind in ("gdn", "full")}
    ends = jax.jit(lambda key: transform(
        {"embed": decoder_embed(key, sizes, dtype),
         **decoder_head(key, sizes, dtype)}))(key)
    return {"embed": ends["embed"],
            "layers": [make[kind](key, jnp.int32(i))
                       for i, kind in enumerate(kinds(sizes))],
            "ln_out": ends["ln_out"], "lm_head": ends["lm_head"]}
