"""Seeded random weights for the hybrid decoder (the program's
`models/hybrid_sparse.py` tree: KDA or sparse latent attention, dense MLP
or held experts, a hyper-connection around each), made by the benchmark
and by nothing else, one layer at a time.

`sizes` is a configuration file's content under its published keys.  As in
weights_latent_moe.py the ROUTER is as wide as the published model and an
expert's values depend on its own number alone, so the eight shares of a
layer are eight views of one model.  Matrices follow the fan-in rule.  The
small parameters are drawn so that every mechanism has work to do: decay
rates from a token to hundreds (`dt_bias`, `a_log`), stream mappings that
differ a stream and a token (`phi`, `bias` of order one), a router bias
that moves the choice of some tokens (`noaux_tc`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import (_linear, _normal, decoder_embed,  # noqa: F401
                               decoder_head, key_for, round_to_fp8)
from benchmark.weights_latent_moe import (_experts, _ffn, experts_first,
                                          router_width)

KINDS = {"linear_attention": "kda", "deepseek_sparse_attention": "dsa"}


def layer_kind(sizes: dict, index: int) -> tuple:
    """(token mixing, feed-forward) of layer `index`: ("kda" | "dsa",
    "dense" | "sparse")."""
    return (KINDS[sizes["layer_types"][index]],
            sizes["mlp_layer_types"][index])


def kda_sizes(sizes: dict) -> tuple:
    group = sizes["linear_attn_config"]
    return (group["num_heads"], group["head_dim"],
            group["short_conv_kernel_size"],
            sizes["assumed_sizes"]["kda_gate_rank"])


def _vector(key, index: int, shape, scale: float, shift: float = 0.0):
    """A small float32 parameter (never rounded to the served type)."""
    return jax.random.normal(jax.random.fold_in(key, index), shape,
                             jnp.float32) * scale + shift


def _hyper(key, base: int, sizes: dict, dtype) -> dict:
    n = sizes["hc_mult"]
    wide = n * sizes["hidden_size"]
    return {"norm": {"scale": jnp.ones((wide,), dtype)},
            "phi": _normal(key, base, (wide, 2 * n + n * n), wide ** -0.5,
                           dtype),
            "alpha": jnp.ones((3,), jnp.float32),
            "bias": _vector(key, base + 1, (2 * n + n * n,), 0.5)}


def _kda(key, base: int, sizes: dict, dtype) -> dict:
    dim = sizes["hidden_size"]
    heads, d, taps, rank = kda_sizes(sizes)
    wide = heads * d
    return {"q": _linear(key, base, dim, wide, dtype),
            "k": _linear(key, base + 1, dim, wide, dtype),
            "v": _linear(key, base + 2, dim, wide, dtype),
            "conv": {"w": _normal(key, base + 3, (taps, 3 * wide),
                                  taps ** -0.5, dtype)},
            "f_a": _linear(key, base + 4, dim, rank, dtype),
            "f_b": _linear(key, base + 5, rank, wide, dtype),
            "a_log": jax.random.uniform(
                jax.random.fold_in(key, base + 6), (heads,), jnp.float32,
                -0.5, 0.5),
            "dt_bias": _vector(key, base + 7, (wide,), 1.0, -4.0),
            "b": _linear(key, base + 8, dim, heads, dtype),
            "g_a": _linear(key, base + 9, dim, rank, dtype),
            "g_b": _linear(key, base + 10, rank, wide, dtype),
            "o_norm": {"scale": jnp.ones((d,), dtype)},
            "o": _linear(key, base + 11, wide, dim, dtype)}


def _sparse_attention(key, base: int, sizes: dict, dtype) -> tuple:
    dim, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, v_dim = sizes["qk_nope_head_dim"], sizes["v_head_dim"]
    index_dim, index_heads = sizes["index_head_dim"], sizes["index_n_heads"]
    attn = {"q_a": _linear(key, base, dim, q_rank, dtype),
            "q_norm": {"scale": jnp.ones((q_rank,), dtype)},
            "q_b": _linear(key, base + 1, q_rank, heads * nope, dtype),
            "kv_a": _linear(key, base + 2, dim, kv_rank, dtype),
            "kv_norm": {"scale": jnp.ones((kv_rank,), dtype)},
            "kv_b": _linear(key, base + 3, kv_rank, heads * (nope + v_dim),
                            dtype),
            "o": _linear(key, base + 4, heads * v_dim, dim, dtype)}
    indexer = {"q": _linear(key, base + 5, q_rank, index_heads * index_dim,
                            dtype),
               "k": _linear(key, base + 6, dim, index_dim, dtype),
               "k_norm": {"scale": jnp.ones((index_dim,), dtype),
                          "bias": jnp.zeros((index_dim,), dtype)},
               "w": _linear(key, base + 7, dim, index_heads, dtype)}
    return attn, indexer


def decoder_layer(key, index, sizes: dict, dtype, kind: tuple) -> dict:
    """Layer `index` alone; `kind` is layer_kind's (static), `index` may be
    traced: one program makes every layer of a kind."""
    key = jax.random.fold_in(key, 1 + index)
    dim = sizes["hidden_size"]
    mixing, mlp = kind
    layer = {"hc_attn": _hyper(key, 40, sizes, dtype),
             "ln_attn": {"scale": jnp.ones((dim,), dtype)},
             "hc_mlp": _hyper(key, 42, sizes, dtype),
             "ln_mlp": {"scale": jnp.ones((dim,), dtype)}}
    if mixing == "kda":
        layer["kda"] = _kda(key, 20, sizes, dtype)
    else:
        layer["attn"], layer["indexer"] = _sparse_attention(key, 20, sizes,
                                                            dtype)
    if mlp == "dense":
        return layer | _ffn(key, 5, dim, sizes["intermediate_size"], dtype)
    ffn = sizes["moe_intermediate_size"]
    layer["router"] = _linear(key, 8, dim, router_width(sizes), dtype) | {
        "bias": _vector(key, 12, (router_width(sizes),), 0.05)}
    layer["shared"] = _ffn(key, 9, dim, ffn * sizes["n_shared_experts"],
                           dtype)
    layer["experts"] = _experts(key, experts_first(sizes),
                                sizes["n_routed_experts"], dim, ffn, dtype)
    return layer


def decoder_weights(key, sizes: dict, dtype, transform=None) -> dict:
    """The whole tree, made on the device layer by layer (one program a
    kind of layer); `transform` is applied to each piece as it is made
    (the float8 control)."""
    transform = transform or (lambda tree: tree)
    count = sizes["num_hidden_layers"]
    kinds = [layer_kind(sizes, i) for i in range(count)]
    make = {kind: jax.jit(
        lambda key, i, kind=kind: transform(
            decoder_layer(key, i, sizes, dtype, kind)))
        for kind in set(kinds)}
    ends = jax.jit(lambda key: transform(
        {"embed": decoder_embed(key, sizes, dtype),
         **decoder_head(key, sizes, dtype)}))(key)
    return {"embed": ends["embed"],
            "layers": [make[kinds[i]](key, jnp.int32(i))
                       for i in range(count)],
            "ln_out": ends["ln_out"], "lm_head": ends["lm_head"]}
