"""Operations and bytes that the latent-attention, routed-expert decoder
needs, from shapes alone (see benchmark/ops_bytes.py for the rules: the
least a chip must do, every weight read once per pass over it, two
operations per multiply-add).  Sizes are the configuration file's, under
their published names; `n_routed_experts` counts the experts held here.
"""

from __future__ import annotations

from benchmark.ops_bytes import ITEMSIZE, roofline_seconds  # noqa: F401


def attention_params(sizes: dict) -> int:
    dim, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    return (dim * q_rank + q_rank * heads * (nope + rope)
            + dim * (kv_rank + rope)
            + kv_rank * heads * (nope + sizes["v_head_dim"])
            + heads * sizes["v_head_dim"] * dim + q_rank + kv_rank + 2 * dim)


def expert_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def layer_counts(sizes: dict) -> tuple:
    dense = sizes["first_k_dense_replace"]
    return dense, sizes["num_hidden_layers"] - dense


def always_streamed_params(sizes: dict) -> int:
    """What every decode step reads whatever is routed where: attention,
    the dense layers' MLP, the shared experts, the routers, the final norm
    and the output head."""
    dim = sizes["hidden_size"]
    dense, sparse = layer_counts(sizes)
    router = dim * sizes.get("published", {}).get(
        "n_routed_experts", sizes["n_routed_experts"])
    return (sizes["num_hidden_layers"] * attention_params(sizes)
            + dense * 3 * dim * sizes["intermediate_size"]
            + sparse * (sizes["n_shared_experts"] * expert_params(sizes)
                        + router)
            + dim + dim * sizes["vocab_size"])


def params(sizes: dict) -> dict:
    _, sparse = layer_counts(sizes)
    experts = sparse * sizes["n_routed_experts"] * expert_params(sizes)
    embedding = sizes["vocab_size"] * sizes["hidden_size"]
    always = always_streamed_params(sizes)
    return {"always_streamed": always, "experts_held": experts,
            "embedding": embedding, "total": always + experts + embedding}


def row_values(sizes: dict) -> int:
    """Values the cache needs of a token and layer: the latent and the
    shared rotary key (the pool pads the row to whole lanes; the pad is
    the layout's, not the algorithm's)."""
    return sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]


def latent_attention(sizes: dict, itemsize: int, live_tokens: float) -> dict:
    """The absorbed attention of ONE decode step over `live_tokens` cached
    rows in all: every row read once (it is K and V both), each of the
    heads' scores over the row's 576 values and its output over the 512 of
    the latent."""
    layers = sizes["num_hidden_layers"]
    heads = sizes["num_attention_heads"]
    return {"bytes": row_values(sizes) * itemsize * live_tokens * layers,
            "flops": 2 * heads * (row_values(sizes) + sizes["kv_lora_rank"])
            * live_tokens * layers}


def routed_experts(sizes: dict, itemsize: int, experts_hit: float,
                   pairs_here: float) -> dict:
    """The routed experts' part of the steps that hit `experts_hit`
    experts (summed over sparse layers and steps) with `pairs_here`
    token-expert pairs: each hit expert's weights once, each pair once
    through them."""
    each = expert_params(sizes)
    return {"bytes": each * itemsize * experts_hit,
            "flops": 2 * each * pairs_here}


def decode_step(sizes: dict, itemsize: int, live_slots: float,
                live_tokens: float, experts_hit: float,
                pairs_here: float) -> dict:
    """One decode step: what is always streamed once, the experts that
    were hit, the live rows of the cache, one new row a slot."""
    always = always_streamed_params(sizes)
    attention = latent_attention(sizes, itemsize, live_tokens)
    experts = routed_experts(sizes, itemsize, experts_hit, pairs_here)
    new_rows = row_values(sizes) * itemsize * live_slots \
        * sizes["num_hidden_layers"]
    return {"bytes": always * itemsize + attention["bytes"]
            + experts["bytes"] + new_rows,
            "flops": 2 * always * live_slots + attention["flops"]
            + experts["flops"]}
