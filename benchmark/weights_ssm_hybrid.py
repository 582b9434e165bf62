"""Seeded random weights for the Mamba-2 hybrid decoder (the program's
`models/ssm_hybrid.py` tree: state-space layers whose B and C every head
shares beside grouped-query attention layers, a tied head), made by the
benchmark and by nothing else, one layer at a time.

`sizes` is a configuration file's content under its published keys.
Matrices follow the fan-in rule.  The decay's parameters are drawn as the
Mamba-2 paper initialises them, so that a head forgets over a few tokens to
a few hundred: A = -exp(A_log) with exp(A_log) uniform in [1, 16], dt =
softplus(dt~ + dt_bias) with softplus(dt_bias) log-uniform in [0.001, 0.1]
(exp(dt A) a token: 1 / (dt A) from 0.6 to 1,000 tokens before the
projection's own dt~ moves it; the published model's are trained and not in
the configuration), D = 1, the convolution's bias normal at 0.2.

THE EMBEDDING is drawn narrow (EMBED_STD, below) because the head is TIED to
it: the residual after the last layer is 12 E[token] + 0.22 x (80 sublayers'
outputs of unit size, some 2.0 a channel together), and the tied head reads
the token's own row back out of it, at 12 s sqrt(2048) / 2.0 standard
deviations of the other tokens' logits for a row of deviation s.  At the
other configurations' 0.02 that is 5.4: above the largest of 100,352 normal
draws (4.4), so every request would be served its own last token over and
over (the CPU rehearsal did exactly that), and neither the state nor the
pool would move a served token.  A shallow stack reads it back the louder
(four layers add 0.6 a channel, not 2.0: at a row of 0.004 the published
widths at four layers still served one request 2 distinct tokens in 12).
At 0.001 the own token stands at 0.3 deviations at 40 layers and 0.9 at
four, and the served tokens vary: the token still enters the first layer at
full size (the layer norms it) and is carried on by what the layers make of
it, as a trained model's is.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import (_linear, _normal, key_for,  # noqa: F401
                               round_to_fp8)

EMBED_STD = 0.001
CONV_BIAS_STD = 0.2
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)

KINDS = ("mamba", "attention")


def mamba_sizes(sizes: dict) -> tuple:
    """(heads, lanes a head, state lanes, convolution taps)."""
    heads, width = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    if sizes["mamba_n_groups"] != 1 or \
            heads * width != sizes["mamba_expand"] * sizes["hidden_size"]:
        raise ValueError("the program shares ONE B and ONE C between all "
                         "heads, and heads x d_head = expand x hidden")
    return heads, width, sizes["mamba_d_state"], sizes["mamba_d_conv"]


def head_dim(sizes: dict) -> int:
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def decoder_embed(key, sizes: dict, dtype) -> dict:
    return {"table": _normal(jax.random.fold_in(key, 0), 0,
                             (sizes["vocab_size"], sizes["hidden_size"]),
                             EMBED_STD, dtype)}


def _ones(width: int, dtype) -> dict:
    return {"scale": jnp.ones((width,), dtype)}


def _uniform(key, index: int, shape, low: float, high: float):
    return jax.random.uniform(jax.random.fold_in(key, index), shape,
                              jnp.float32, low, high)


def decoder_layer(key, index, sizes: dict, dtype, kind: str) -> dict:
    """Layer `index` alone, of `kind` "mamba" or "attention" (`index` may
    be traced: one program makes every layer of a kind)."""
    key = jax.random.fold_in(key, 1 + index)
    dim, ffn = sizes["hidden_size"], sizes["shared_intermediate_size"]
    layer = {"ln_attn": _ones(dim, dtype), "ln_mlp": _ones(dim, dtype),
             "gate": _linear(key, 20, dim, ffn, dtype),
             "up": _linear(key, 21, dim, ffn, dtype),
             "down": _linear(key, 22, ffn, dim, dtype)}
    if kind == "attention":
        d = head_dim(sizes)
        wide, narrow = (sizes[name] * d for name in (
            "num_attention_heads", "num_key_value_heads"))
        return layer | {"attn": {
            "q": _linear(key, 0, dim, wide, dtype),
            "k": _linear(key, 1, dim, narrow, dtype),
            "v": _linear(key, 2, dim, narrow, dtype),
            "o": _linear(key, 3, wide, dim, dtype)}}
    heads, width, state, taps = mamba_sizes(sizes)
    inner, channels = heads * width, heads * width + 2 * state
    step = jnp.exp(_uniform(key, 6, (heads,), *map(math.log, DT_RANGE)))
    return layer | {"mamba": {
        "in": _linear(key, 0, dim, inner + channels + heads, dtype),
        "conv": {"w": _normal(key, 3, (taps, channels), taps ** -0.5, dtype),
                 "b": _normal(key, 4, (channels,), CONV_BIAS_STD, dtype)},
        # softplus(dt_bias) = step
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "a_log": jnp.log(_uniform(key, 5, (heads,), *A_RANGE)),
        "d": jnp.ones((heads,), jnp.float32),
        "norm": _ones(inner, dtype),
        "out": _linear(key, 9, inner, dim, dtype)}}


def kinds(sizes: dict) -> list:
    if len(sizes["layer_types"]) != sizes["num_hidden_layers"] or \
            set(sizes["layer_types"]) - set(KINDS):
        raise ValueError("layer_types names every layer, mamba or attention")
    return list(sizes["layer_types"])


def decoder_head(key, sizes: dict, dtype) -> dict:
    """The final norm; the head is the embedding."""
    return {"ln_out": _ones(sizes["hidden_size"], dtype)}


def decoder_weights(key, sizes: dict, dtype, transform=None) -> dict:
    """The whole tree, made on the device layer by layer (one program a
    kind of layer); `transform` is applied to each piece as it is made
    (the float8 control)."""
    transform = transform or (lambda tree: tree)
    make = {kind: jax.jit(lambda key, i, kind=kind: transform(
        decoder_layer(key, i, sizes, dtype, kind))) for kind in KINDS}
    ends = jax.jit(lambda key: transform(
        {"embed": decoder_embed(key, sizes, dtype),
         **decoder_head(key, sizes, dtype)}))(key)
    return {"embed": ends["embed"],
            "layers": [make[kind](key, jnp.int32(i))
                       for i, kind in enumerate(kinds(sizes))],
            "ln_out": ends["ln_out"]}
