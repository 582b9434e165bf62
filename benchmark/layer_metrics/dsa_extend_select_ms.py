from benchmark.readers_hybrid_sparse import extend_region_ms


def read(run):
    """Device ms a prefill chunk spends under `aiko.dsa_select` inside
    `jit_extend`: every query's own choice of `topk` positions of its
    prefix and its chunk (the threshold bit by bit, the ties), all
    layers, a chunk dispatched in the traced span."""
    return extend_region_ms(run, "aiko.dsa_select")
