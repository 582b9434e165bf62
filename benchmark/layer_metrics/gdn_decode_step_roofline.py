from benchmark import readers, readers_gated_delta


def read(run):
    """The whole decode step against the chip: every streamed weight once,
    the recurrent state of the slots that decode in and out, the live keys
    and values of the full layers, the head, over the memory bandwidth (or
    their operations over the peak, whichever bounds), over the time a step
    took."""
    return readers_gated_delta.roofline_share(
        run, readers_gated_delta.step_work(run), readers.decode_step_ms(run))
