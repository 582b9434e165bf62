from benchmark import readers_gated_delta


def read(run):
    """Device ms a decode step spends under `aiko.gdn_conv`: the recurrent layers' convolution, SiLU, norms and gates
    (trace/regions.py), over the steps run in the traced span."""
    return readers_gated_delta.step_region_ms(run, "aiko.gdn_conv")
