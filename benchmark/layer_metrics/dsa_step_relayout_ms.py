from benchmark import readers_hybrid_sparse


def read(run):
    """Device ms a decode step spends under `aiko.dsa_relayout`: the copy
    of the WHOLE latent leaf into a tiling where a group's rows lie
    together, made once a round before the gather of the chosen groups,
    whatever is live (here over the steps run, as its siblings).  0.0 for
    a program of this model that makes no such copy."""
    return readers_hybrid_sparse.step_region_ms(run, "aiko.dsa_relayout")
