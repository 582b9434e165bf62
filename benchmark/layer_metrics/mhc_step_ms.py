from benchmark import readers_hybrid_sparse


def read(run):
    """Device ms a decode step spends under `aiko.mhc`: the streams'
    mappings, Sinkhorn and mixing around all ten sublayers."""
    return readers_hybrid_sparse.step_region_ms(run, "aiko.mhc")
