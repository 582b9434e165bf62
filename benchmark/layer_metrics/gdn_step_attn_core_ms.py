from benchmark import readers_gated_delta


def read(run):
    """Device ms a decode step spends under `aiko.attn_core`: the full layers' walk of each slot's live K and V blocks
    (trace/regions.py), over the steps run in the traced span."""
    return readers_gated_delta.step_region_ms(run, "aiko.attn_core")
