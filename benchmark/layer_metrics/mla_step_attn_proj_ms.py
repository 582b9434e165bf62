from benchmark.trace import regions


def read(run):
    """Device ms a decode step spends under `aiko.attn_proj`: attention's projections of the latent block (the norms, W_qa, W_qb, W_kva, the rotary, the two absorbs W_UK and W_UV, W_o);
    the own time of the device operations that carry the scope inside
    `jit_step`, and of the scopeless ones they adopt (trace/regions.py),
    over the steps run in the traced span."""
    return regions.step_region_ms(run, "aiko.attn_proj")
