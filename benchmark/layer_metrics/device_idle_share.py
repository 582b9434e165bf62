from benchmark import readers


def read(run):
    """1 - the union of device operations over the traced span."""
    return readers.idle_share(run)
