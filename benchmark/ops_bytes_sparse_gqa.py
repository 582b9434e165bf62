"""Operations and bytes that the sparse grouped-query decoder needs, from
shapes and from the counters of LOGICAL positions alone (see
benchmark/ops_bytes.py for the rules: the least a chip must do, every
weight read once per pass over it, two operations per multiply-add), so
that they read the same work whatever implements it: a position's indexer
key is `indexer_head_dim` lanes wide however the leaf pads it, a chosen
position is one K and one V row however many a gather fetches beside it.
And the parameter count from the published keys.  Sizes are the
configuration file's, under their published names; `num_experts` counts
the experts held here, `published` holds what the cut changed.
"""

from __future__ import annotations

from benchmark.ops_bytes import ITEMSIZE, roofline_seconds  # noqa: F401


# -- parameters ------------------------------------------------------------------

def attention_params(sizes: dict) -> int:
    """W_q, W_k, W_v, W_o and the two norms a head."""
    dim, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return 2 * dim * heads * d + 2 * dim * kv * d + 2 * d


def indexer_params(sizes: dict) -> int:
    """W_qI, W_kI with its layer norm, W_w."""
    dim, group = sizes["hidden_size"], sizes["sa_config"]
    heads, lanes = group["indexer_num_heads"], group["indexer_head_dim"]
    return dim * heads * lanes + dim * lanes + 2 * lanes + dim * heads


def expert_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def router_width(sizes: dict) -> int:
    return sizes.get("published", {}).get("num_experts",
                                          sizes["num_experts"])


def layer_params(sizes: dict, experts: int) -> int:
    """One layer holding `experts` routed experts: attention, indexer, two
    norms, the router over every published expert."""
    dim = sizes["hidden_size"]
    return (attention_params(sizes) + indexer_params(sizes) + 2 * dim
            + dim * router_width(sizes) + experts * expert_params(sizes))


def published_parameters(sizes: dict) -> dict:
    """The count of the PUBLISHED language model from the file's keys, its
    depth, experts and vocabulary under `published`: all of it, and what
    a token passes through (its `num_experts_per_tok` experts a layer)."""
    whole, dim = sizes["published"], sizes["hidden_size"]
    layers = whole["num_hidden_layers"]
    ends = 2 * whole["vocab_size"] * dim + dim
    return {"total": layers * layer_params(sizes, whole["num_experts"]) + ends,
            "active": layers * layer_params(
                sizes, sizes["num_experts_per_tok"]) + ends}


def always_streamed_params(sizes: dict) -> int:
    """What every decode step reads whatever is routed where: everything
    but the routed experts and the embedding."""
    dim = sizes["hidden_size"]
    return sizes["num_hidden_layers"] * layer_params(sizes, 0) \
        + dim + dim * sizes["vocab_size"]


def params(sizes: dict) -> dict:
    experts = sizes["num_hidden_layers"] * sizes["num_experts"] * \
        expert_params(sizes)
    embedding = sizes["vocab_size"] * sizes["hidden_size"]
    always = always_streamed_params(sizes)
    return {"always_streamed": always, "experts_held": experts,
            "embedding": embedding, "total": always + experts + embedding}


def token_row_bytes(sizes: dict, itemsize: int) -> int:
    """What a layer caches of a token, unpadded: K, V and the indexer
    key."""
    return (2 * sizes["num_key_value_heads"] * sizes["head_dim"]
            + sizes["sa_config"]["indexer_head_dim"]) * itemsize


# -- kernels ---------------------------------------------------------------------
# `live` and `attended` are the counters' sums over the layers and the
# slots that decoded, a step: no factor of the depth below.

def index_select(sizes: dict, itemsize: int, live: float) -> dict:
    """Scoring and choosing in ONE decode step over slots and layers that
    hold `live` positions between them: every live position's indexer key
    once, every indexer head's dot with it."""
    group = sizes["sa_config"]
    heads, lanes = group["indexer_num_heads"], group["indexer_head_dim"]
    return {"bytes": lanes * itemsize * live,
            "flops": 2 * heads * lanes * live}


def sparse_attention(sizes: dict, itemsize: int, attended: float) -> dict:
    """The attention of ONE decode step over the `attended` chosen
    positions: each one's K and V rows once, every head's score and
    output."""
    d = sizes["head_dim"]
    return {"bytes": 2 * sizes["num_key_value_heads"] * d * itemsize
            * attended,
            "flops": 4 * sizes["num_attention_heads"] * d * attended}


def routed_experts(sizes: dict, itemsize: int, experts_hit: float,
                   pairs_here: float) -> dict:
    each = expert_params(sizes)
    return {"bytes": each * itemsize * experts_hit,
            "flops": 2 * each * pairs_here}


def decode_step(sizes: dict, itemsize: int, live_slots: float,
                attended: float, live: float, experts_hit: float,
                pairs_here: float) -> dict:
    """One decode step: what is always streamed once (the head's slice
    with it), the experts that were hit, the indexer keys of everything
    live, the chosen rows, one new row a slot and layer."""
    always = always_streamed_params(sizes)
    index = index_select(sizes, itemsize, live)
    core = sparse_attention(sizes, itemsize, attended)
    experts = routed_experts(sizes, itemsize, experts_hit, pairs_here)
    new_rows = token_row_bytes(sizes, itemsize) * live_slots * \
        sizes["num_hidden_layers"]
    return {"bytes": always * itemsize + index["bytes"] + core["bytes"]
            + experts["bytes"] + new_rows,
            "flops": 2 * always * live_slots + index["flops"]
            + core["flops"] + experts["flops"]}
