from benchmark import readers


def read(run):
    """Weights and live keys and values over the memory bandwidth, over
    the time a step took: bytes-bound at every batch this chip holds."""
    return readers.decode_step_roofline(run)
