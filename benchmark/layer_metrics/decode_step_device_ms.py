from benchmark import readers


def read(run):
    """Device time of the decode-step program, a step."""
    return readers.decode_step_ms(run)
