from benchmark import readers_hybrid_sparse


def read(run):
    """Device ms a decode step spends under `aiko.attn_core` in the
    sparse layer: the gather of the chosen groups' latent rows, scores,
    softmax, weights x rows; NOT the copy of the leaf before the gather,
    which `dsa_step_relayout_ms` reads (`aiko.dsa_relayout`, the innermost
    scope of those operations)."""
    return readers_hybrid_sparse.step_region_ms(run, "aiko.attn_core")
