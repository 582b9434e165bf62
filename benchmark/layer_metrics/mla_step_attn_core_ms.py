from benchmark.trace import regions


def read(run):
    """Device ms a decode step spends under `aiko.attn_core`: the latent walk (the pallas kernel over each slot's own live rows, and what feeds it);
    the own time of the device operations that carry the scope inside
    `jit_step`, and of the scopeless ones they adopt (trace/regions.py),
    over the steps run in the traced span."""
    return regions.step_region_ms(run, "aiko.attn_core")
