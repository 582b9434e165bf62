from benchmark import program_journeys


def read(run):
    """Of the median request's gap between tokens (`tpot_mid_ms`), the
    host's part, ms: between two rounds' token stamps everything but the
    later round's `host_sync`."""
    return program_journeys.mid_ms(run, "host")
