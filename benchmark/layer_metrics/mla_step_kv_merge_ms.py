from benchmark.trace import regions


def read(run):
    """Device ms a decode step spends under `aiko.kv_merge`: the round's new latent rows scattered into the block pool, once a round after the loop;
    the own time of the device operations that carry the scope inside
    `jit_step`, and of the scopeless ones they adopt (trace/regions.py),
    over the steps run in the traced span."""
    return regions.step_region_ms(run, "aiko.kv_merge")
