from benchmark import readers_gated_delta


def read(run):
    """Device ms a decode step spends under `aiko.gdn_state`: the recurrence over the state of the slots that decode (ops/kda_step.py on the chip) and what lays its operands out
    (trace/regions.py), over the steps run in the traced span."""
    return readers_gated_delta.step_region_ms(run, "aiko.gdn_state")
