"""Operations and bytes that the hybrid decoder needs, from shapes alone
(see benchmark/ops_bytes.py for the rules: the least a chip must do, every
weight read once per pass over it, two operations per multiply-add), and
its parameter count from the published keys.  Sizes are the configuration
file's, under their published names; `n_routed_experts` counts the
experts held here, `published` holds what the cut changed.
"""

from __future__ import annotations

from benchmark.ops_bytes import ITEMSIZE, roofline_seconds  # noqa: F401

STATE_ITEMSIZE = 4          # KDA state is float32 whatever is served


# -- parameters ------------------------------------------------------------------

def kda_shape(sizes: dict) -> tuple:
    group = sizes["linear_attn_config"]
    return group["num_heads"], group["head_dim"]


def hyper_params(sizes: dict) -> int:
    """One hyper-connection: the norm of vec X, Phi, three scales, biases."""
    n = sizes["hc_mult"]
    wide = n * sizes["hidden_size"]
    return wide + wide * (2 * n + n * n) + 3 + 2 * n + n * n


def kda_params(sizes: dict) -> int:
    dim = sizes["hidden_size"]
    heads, d = kda_shape(sizes)
    wide, rank = heads * d, sizes["assumed_sizes"]["kda_gate_rank"]
    taps = sizes["linear_attn_config"]["short_conv_kernel_size"]
    return (3 * dim * wide + taps * 3 * wide + 2 * (dim * rank + rank * wide)
            + heads + wide + dim * heads + d + wide * dim)


def latent_params(sizes: dict) -> int:
    dim, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, v_dim = sizes["qk_nope_head_dim"], sizes["v_head_dim"]
    return (dim * q_rank + q_rank + q_rank * heads * nope + dim * kv_rank
            + kv_rank + kv_rank * heads * (nope + v_dim)
            + heads * v_dim * dim)


def indexer_params(sizes: dict) -> int:
    dim, width = sizes["hidden_size"], sizes["index_head_dim"]
    return (sizes["q_lora_rank"] * sizes["index_n_heads"] * width
            + dim * width + 2 * width + dim * sizes["index_n_heads"])


def expert_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def router_width(sizes: dict) -> int:
    return sizes.get("published", {}).get("n_routed_experts",
                                          sizes["n_routed_experts"])


def layer_params(sizes: dict, mixing: str, mlp: str, experts: int) -> int:
    """One layer holding `experts` routed experts: its token mixing, its
    feed-forward, two hyper-connections and two norms."""
    dim = sizes["hidden_size"]
    total = 2 * hyper_params(sizes) + 2 * dim + (
        kda_params(sizes) if mixing == "kda"
        else latent_params(sizes) + indexer_params(sizes))
    if mlp == "dense":
        return total + 3 * dim * sizes["intermediate_size"]
    width = router_width(sizes)
    return (total + dim * width + width
            + sizes["n_shared_experts"] * expert_params(sizes)
            + experts * expert_params(sizes))


def published_parameters(sizes: dict) -> dict:
    """The count of the PUBLISHED model from the file's keys: its depth,
    experts, vocabulary and leading dense layers under `published`, a
    sparse-attention layer every fourth; and the one multi-token-
    prediction layer, counted as a sparse-attention layer with all its
    experts and the two norms of its inputs (no key gives a projection of
    the two onto one; DeepSeek-V3's would add 33.6 M), which is not
    served."""
    whole = sizes["published"]
    dim = sizes["hidden_size"]
    layers = sum(layer_params(
        sizes, "dsa" if i % 4 == 3 else "kda",
        "dense" if i < whole["first_k_dense_replace"] else "sparse",
        whole["n_routed_experts"])
        for i in range(whole["num_hidden_layers"]))
    ends = 2 * whole["vocab_size"] * dim + dim
    mtp = layer_params(sizes, "dsa", "sparse", whole["n_routed_experts"]) \
        + 2 * dim
    return {"served_layers_and_ends": layers + ends, "mtp": mtp,
            "total": layers + ends + mtp}


def kinds(sizes: dict) -> list:
    names = {"linear_attention": "kda", "deepseek_sparse_attention": "dsa"}
    return [(names[a], b) for a, b in zip(sizes["layer_types"],
                                          sizes["mlp_layer_types"])]


def always_streamed_params(sizes: dict) -> int:
    """What every decode step reads whatever is routed where: everything
    but the routed experts and the embedding."""
    dim = sizes["hidden_size"]
    return sum(layer_params(sizes, mixing, mlp, 0)
               for mixing, mlp in kinds(sizes)) \
        + dim + dim * sizes["vocab_size"]


def params(sizes: dict) -> dict:
    sparse = sum(mlp == "sparse" for _, mlp in kinds(sizes))
    experts = sparse * sizes["n_routed_experts"] * expert_params(sizes)
    embedding = sizes["vocab_size"] * sizes["hidden_size"]
    always = always_streamed_params(sizes)
    return {"always_streamed": always, "experts_held": experts,
            "embedding": embedding, "total": always + experts + embedding}


# -- kernels ---------------------------------------------------------------------

def kda_recurrence(sizes: dict, tokens: float, passes: float) -> dict:
    """The gated delta rule's OWN count over `tokens` tokens (all KDA
    layers, one head after another), whatever form computes it: a token
    and head decays S (D x D), reads it against k, writes the rank-one
    update and reads it against q, 7 D^2 operations; `passes` times the
    state of a sequence comes in from memory and goes back (a step: once
    a token; a chunk: once a chunk); q, k, v, g in and o out a token."""
    heads, d = kda_shape(sizes)
    layers = sum(mixing == "kda" for mixing, _ in kinds(sizes))
    state = heads * d * d * STATE_ITEMSIZE
    return {"flops": 7 * heads * d * d * tokens * layers,
            "bytes": (2 * state * passes
                      + 5 * heads * d * STATE_ITEMSIZE * tokens) * layers}


def sparse_core(sizes: dict, itemsize: int, attended: float,
                live: float) -> dict:
    """The sparse layer's selection and attention of ONE decode step over
    slots that hold `live` positions between them and attend `attended`:
    the pooled indexer keys of the whole length once, the chosen latent
    rows once (each is K and V both), every head's score and output a
    chosen row, every indexer head's dot a group."""
    layers = sum(mixing == "dsa" for mixing, _ in kinds(sizes))
    rank, heads = sizes["kv_lora_rank"], sizes["num_attention_heads"]
    width, pool = sizes["index_head_dim"], sizes["index_kpool"]
    groups = live / pool
    return {"bytes": (rank * attended + width * groups) * itemsize * layers,
            "flops": (4 * heads * rank * attended
                      + 2 * sizes["index_n_heads"] * width * groups)
            * layers}


def routed_experts(sizes: dict, itemsize: int, experts_hit: float,
                   pairs_here: float) -> dict:
    each = expert_params(sizes)
    return {"bytes": each * itemsize * experts_hit,
            "flops": 2 * each * pairs_here}


def decode_step(sizes: dict, itemsize: int, live_slots: float,
                attended: float, live: float, experts_hit: float,
                pairs_here: float) -> dict:
    """One decode step: what is always streamed once, the experts that
    were hit, the state of the slots that decode in and out, the pooled
    keys and the chosen rows, one new row a slot."""
    always = always_streamed_params(sizes)
    state = kda_recurrence(sizes, live_slots, live_slots)
    core = sparse_core(sizes, itemsize, attended, live)
    experts = routed_experts(sizes, itemsize, experts_hit, pairs_here)
    new_rows = (sizes["kv_lora_rank"] + sizes["index_head_dim"]
                / sizes["index_kpool"]) * itemsize * live_slots
    return {"bytes": always * itemsize + state["bytes"] + core["bytes"]
            + experts["bytes"] + new_rows,
            "flops": 2 * always * live_slots + state["flops"]
            + core["flops"] + experts["flops"]}
