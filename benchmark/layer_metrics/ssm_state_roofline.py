from benchmark import readers_ssm_hybrid


def read(run):
    """The step's recurrence against the chip: the states it changed, each
    in and out once as laid out (2 x 2,097,152 B a state at the published
    widths), over the memory bandwidth (or the rule's own 5 N P operations
    a head over the peak, whichever bounds), over the device time under
    `aiko.ssm_state`, which holds the kernel and what lays its operands
    out.  The count is benchmark/ops_bytes_ssm_hybrid.py's and the same
    whatever implements the rule."""
    return readers_ssm_hybrid.roofline_share(
        run, readers_ssm_hybrid.state_work(run),
        readers_ssm_hybrid.step_region_ms(run, "aiko.ssm_state"))
