"""Seeded random weights for the latent-attention, routed-expert decoder
(the program's `models/latent_moe.py` tree), made by the benchmark and by
nothing else, one layer at a time: the reference makes one layer, uses
it and drops it.

`sizes` is a configuration file's content.  The ROUTER is always as wide
as the published model (`published.n_routed_experts`); `n_routed_experts`
counts the experts HELD, `deployment.experts_first` names the first of
them.  An expert's values depend on its own number alone, so the sixteen
shares of a layer are sixteen views of one model (a test adds them up).
Scales follow the fan-in rule, as in benchmark/weights.py, whose helpers
these are.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import (_linear, _normal, decoder_embed,  # noqa: F401
                               decoder_head, key_for, round_to_fp8)


def router_width(sizes: dict) -> int:
    return sizes.get("published", {}).get("n_routed_experts",
                                          sizes["n_routed_experts"])


def experts_first(sizes: dict) -> int:
    return sizes.get("deployment", {}).get("experts_first", 0)


def is_sparse(sizes: dict, index: int) -> bool:
    return index >= sizes["first_k_dense_replace"]


def _ffn(key, base: int, dim: int, ffn: int, dtype) -> dict:
    return {"gate": _linear(key, base, dim, ffn, dtype),
            "up": _linear(key, base + 1, dim, ffn, dtype),
            "down": _linear(key, base + 2, ffn, dim, dtype)}


def _experts(key, first, held: int, dim: int, ffn: int, dtype) -> dict:
    """Experts first .. first+held-1, stacked on a leading axis; expert g
    is drawn from fold_in(key, 1000 + g) whoever holds it."""
    def one(g):
        mine = jax.random.fold_in(key, 1000 + g)
        return (_normal(mine, 0, (dim, ffn), dim ** -0.5, dtype),
                _normal(mine, 1, (dim, ffn), dim ** -0.5, dtype),
                _normal(mine, 2, (ffn, dim), ffn ** -0.5, dtype))
    gate, up, down = jax.vmap(one)(first + jnp.arange(held))
    return {"gate": {"w": gate}, "up": {"w": up}, "down": {"w": down}}


def decoder_layer(key, index, sizes: dict, dtype, sparse: bool) -> dict:
    """Layer `index` alone (`index` may be traced: one program makes every
    sparse layer)."""
    key = jax.random.fold_in(key, 1 + index)
    dim, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    layer = {
        "ln_attn": {"scale": jnp.ones((dim,), dtype)},
        "attn": {
            "q_a": _linear(key, 0, dim, q_rank, dtype),
            "q_norm": {"scale": jnp.ones((q_rank,), dtype)},
            "q_b": _linear(key, 1, q_rank, heads * (nope + rope), dtype),
            "kv_a": _linear(key, 2, dim, kv_rank + rope, dtype),
            "kv_norm": {"scale": jnp.ones((kv_rank,), dtype)},
            "kv_b": _linear(key, 3, kv_rank,
                            heads * (nope + sizes["v_head_dim"]), dtype),
            "o": _linear(key, 4, heads * sizes["v_head_dim"], dim, dtype)},
        "ln_mlp": {"scale": jnp.ones((dim,), dtype)},
    }
    if not sparse:
        return layer | _ffn(key, 5, dim, sizes["intermediate_size"], dtype)
    ffn = sizes["moe_intermediate_size"]
    layer["router"] = _linear(key, 8, dim, router_width(sizes), dtype)
    layer["shared"] = _ffn(key, 9, dim, ffn * sizes["n_shared_experts"],
                           dtype)
    layer["experts"] = _experts(key, experts_first(sizes),
                                sizes["n_routed_experts"], dim, ffn, dtype)
    return layer


def decoder_weights(key, sizes: dict, dtype, transform=None) -> dict:
    """The whole tree, made on the device layer by layer (two programs:
    a dense layer's and a sparse layer's); `transform` is applied to each
    piece as it is made (the float8 control)."""
    transform = transform or (lambda tree: tree)
    make = {sparse: jax.jit(
        lambda key, i, sparse=sparse: transform(
            decoder_layer(key, i, sizes, dtype, sparse)))
        for sparse in (False, True)}
    ends = jax.jit(lambda key: transform(
        {"embed": decoder_embed(key, sizes, dtype),
         **decoder_head(key, sizes, dtype)}))(key)
    return {"embed": ends["embed"],
            "layers": [make[is_sparse(sizes, i)](key, jnp.int32(i))
                       for i in range(sizes["num_hidden_layers"])],
            "ln_out": ends["ln_out"], "lm_head": ends["lm_head"]}
