from benchmark import readers_hybrid_sparse


def read(run):
    """Device ms a prefill chunk spends under `aiko.kda_core` (the
    convolution, the gates and the chunked scan of every KDA layer) inside
    `jit_extend`, a chunk dispatched in the traced span."""
    return readers_hybrid_sparse.extend_region_ms(run, "aiko.kda_core")
