from benchmark import program_journeys


def read(run):
    """Of the median request's gap between tokens (`tpot_mid_ms`), the
    `host_sync` of the rounds whose step had no prefill ahead of it on
    the device (`prefill_ahead` 0), ms."""
    return program_journeys.mid_ms(run, "clean")
