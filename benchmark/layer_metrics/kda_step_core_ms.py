from benchmark import readers_hybrid_sparse


def read(run):
    """Device ms a decode step spends under `aiko.kda_core`: the KDA
    layers' convolution, gates and one-token recurrence over every slot's
    state (trace/regions.py), over the steps run in the traced span."""
    return readers_hybrid_sparse.step_region_ms(run, "aiko.kda_core")
