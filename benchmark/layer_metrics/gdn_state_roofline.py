from benchmark import readers_gated_delta


def read(run):
    """The step's recurrence against the chip: the states it changed, each
    in and out once as laid out (padding included), over the memory
    bandwidth (or the rule's own 7 Dk Dv operations a head over the peak,
    whichever bounds), over the device time under `aiko.gdn_state`, which
    holds the kernel and what lays its operands out."""
    return readers_gated_delta.roofline_share(
        run, readers_gated_delta.state_work(run),
        readers_gated_delta.step_region_ms(run, "aiko.gdn_state"))
