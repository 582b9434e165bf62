"""Seeded random weights for the sparse grouped-query decoder (the program's
`models/sparse_gqa.py` tree: grouped-query attention with a norm a head, a
lightning indexer, a softmax router over held experts and no shared
expert), made by the benchmark and by nothing else, one layer at a time.

`sizes` is a configuration file's content under its published keys:
`num_experts` counts the experts HELD here, `published.num_experts` the
router's width.  Matrices follow the fan-in rule; an expert's values depend
on its own number alone, so the eight shares of a layer are eight views of
one model.

THE ROUTER's columns are plain, one draw an expert, ROUTER_SCALE times as
wide as the fan-in rule's (below): a token's eight gates differ, so
softmax scores and sigmoid scores give different outputs and `correct`
sees the router's rule; a token hits none of the held experts a third of
the time and two or more a quarter.  With this configuration's wide
embedding a token's own row leads what the router sees, tokens differ,
and the held experts get 1/8 of the pairs in expectation: 12.3-12.5% over
1,500 tokens and 12 layers in six seeds (numpy, the router alone; PR 33's
10.6-15.5% from seed to seed was a model whose residual was the same for
every token).

THE EMBEDDING is drawn wide (EMBED_STD, below), so that the served tokens
do not collapse into one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import (_linear, _normal, decoder_head,  # noqa: F401
                               key_for, round_to_fp8)
from benchmark.weights_latent_moe import _experts, experts_first

# The standard deviation of an embedding row's values.  With the other
# configurations' 0.02 this model's served tokens said nothing: every
# layer's attention is a mean over 2,048 chosen positions, the same for
# every query of a request, which swamps a token's own 0.02 after one
# layer, so greedy decoding fell into ONE repeated token a request within
# a few steps, whose logit leads by so much that float8 weights SERVED
# picked the reference's own best at all 560 sampled positions (mean gap
# 0.0, `correct` true: PERF.md, correctness).  At 2.0 the token's own row
# leads its residual through all twelve layers (a layer adds some 0.04
# from attention and 0.08 from its expert), the served tokens do not
# repeat, every position is its own sample of the precision, and the
# layers still move the logits by a tenth of their spread.
EMBED_STD = 2.0

# The router's columns over the fan-in rule's.  At 1 a token's logits are
# N(0, 1): its eighth and ninth experts lie 0.06 apart, the eighth's gate
# is 0.07, and a residual rounded to bfloat16 swaps the two in a
# twentieth of a layer's tokens, which moves a sound run's served tokens
# as float8 weights do.  At 4 the same swaps move a gate of 0.01 (the
# best expert's is 0.7, as a trained router's is peaked), and a sigmoid in
# the softmax's place, which flattens the gates, reads five times the
# limit (PERF.md, correctness).
ROUTER_SCALE = 4.0


def router_width(sizes: dict) -> int:
    return sizes.get("published", {}).get("num_experts",
                                          sizes["num_experts"])


def decoder_embed(key, sizes: dict, dtype) -> dict:
    return {"table": _normal(jax.random.fold_in(key, 0), 0,
                             (sizes["vocab_size"], sizes["hidden_size"]),
                             EMBED_STD, dtype)}


def indexer_sizes(sizes: dict) -> tuple:
    """(heads, lanes a head) of the indexer's queries; its key has one
    head."""
    group = sizes["sa_config"]
    if group["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer caches ONE key head a token")
    return group["indexer_num_heads"], group["indexer_head_dim"]


def decoder_layer(key, index, sizes: dict, dtype) -> dict:
    """Layer `index` alone (`index` may be traced: one program makes every
    layer)."""
    key = jax.random.fold_in(key, 1 + index)
    dim, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    index_heads, index_dim = indexer_sizes(sizes)
    return {
        "ln_attn": {"scale": jnp.ones((dim,), dtype)},
        "attn": {"q": _linear(key, 0, dim, heads * d, dtype),
                 "k": _linear(key, 1, dim, kv * d, dtype),
                 "v": _linear(key, 2, dim, kv * d, dtype),
                 "o": _linear(key, 3, heads * d, dim, dtype),
                 "q_norm": {"scale": jnp.ones((d,), dtype)},
                 "k_norm": {"scale": jnp.ones((d,), dtype)}},
        "indexer": {"q": _linear(key, 4, dim, index_heads * index_dim,
                                 dtype),
                    "k": _linear(key, 5, dim, index_dim, dtype),
                    "k_norm": {"scale": jnp.ones((index_dim,), dtype),
                               "bias": jnp.zeros((index_dim,), dtype)},
                    "w": _linear(key, 6, dim, index_heads, dtype)},
        "ln_mlp": {"scale": jnp.ones((dim,), dtype)},
        "router": {"w": _normal(key, 8, (dim, router_width(sizes)),
                                ROUTER_SCALE * dim ** -0.5, dtype)},
        "experts": _experts(key, experts_first(sizes), sizes["num_experts"],
                            dim, sizes["moe_intermediate_size"], dtype)}


def decoder_weights(key, sizes: dict, dtype, transform=None) -> dict:
    """The whole tree, made on the device layer by layer (one program);
    `transform` is applied to each piece as it is made (the float8
    control)."""
    transform = transform or (lambda tree: tree)
    make = jax.jit(lambda key, i: transform(
        decoder_layer(key, i, sizes, dtype)))
    ends = jax.jit(lambda key: transform(
        {"embed": decoder_embed(key, sizes, dtype),
         **decoder_head(key, sizes, dtype)}))(key)
    return {"embed": ends["embed"],
            "layers": [make(key, jnp.int32(i))
                       for i in range(sizes["num_hidden_layers"])],
            "ln_out": ends["ln_out"], "lm_head": ends["lm_head"]}
