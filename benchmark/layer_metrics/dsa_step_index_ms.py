from benchmark import readers_hybrid_sparse


def read(run):
    """Device ms a decode step spends under `aiko.dsa_index`: the
    indexer's projections, its scores over the pooled keys of every
    slot's whole length, the top groups."""
    return readers_hybrid_sparse.step_region_ms(run, "aiko.dsa_index")
