from benchmark import readers_gated_delta


def read(run):
    """Device ms a decode step spends under `aiko.head`: the final norm, the output head and the argmax
    (trace/regions.py), over the steps run in the traced span."""
    return readers_gated_delta.step_region_ms(run, "aiko.head")
