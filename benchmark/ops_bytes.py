"""Operations and bytes that the algorithms need, from shapes alone.

These are the least a chip must do, not what a compiler emitted: every
weight read once per pass over it, every cached key and value read once
per step that attends to it, two operations per multiply-add.  The
roofline metrics divide them by the peaks in benchmark/peaks.json.
Sizes are the configuration files' `sizes`, under their published names.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def decoder_layer_params(sizes: dict) -> int:
    dim, ffn = sizes["hidden_size"], sizes["intermediate_size"]
    q_out = sizes["num_attention_heads"] * sizes["head_dim"]
    kv_out = sizes["num_key_value_heads"] * sizes["head_dim"]
    return dim * (q_out + 2 * kv_out) + q_out * dim + 3 * dim * ffn + 2 * dim


def decoder_params(sizes: dict) -> dict:
    """Parameter counts: what a decode step streams (the layers, the final
    norm and the output head) and the embedding table it only gathers."""
    dim, vocab = sizes["hidden_size"], sizes["vocab_size"]
    streamed = (sizes["num_hidden_layers"] * decoder_layer_params(sizes)
                + dim + dim * vocab)
    return {"streamed": streamed, "embedding": vocab * dim,
            "total": streamed + vocab * dim}


def kv_bytes_per_token(sizes: dict, itemsize: int) -> int:
    return (2 * sizes["num_key_value_heads"] * sizes["head_dim"] * itemsize
            * sizes["num_hidden_layers"])


def decode_step(sizes: dict, itemsize: int, slots: int,
                live_tokens: float) -> dict:
    """One decode step over `slots` sequences that hold `live_tokens` of
    context between them: every streamed weight once, every live key and
    value once, one new key and value a slot."""
    params = decoder_params(sizes)["streamed"]
    heads, head = sizes["num_attention_heads"], sizes["head_dim"]
    bytes_ = (params * itemsize
              + kv_bytes_per_token(sizes, itemsize) * (live_tokens + slots))
    flops = (2 * params * slots
             + 4 * heads * head * live_tokens * sizes["num_hidden_layers"])
    return {"flops": flops, "bytes": bytes_}


def roofline_seconds(work: dict, peaks: dict) -> dict:
    """The least time the chip could take, and which peak bounds it."""
    by_ops = work["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_ops, by_bytes),
            "bound": "operations" if by_ops > by_bytes else "bytes",
            "by_operations_s": by_ops, "by_bytes_s": by_bytes}
