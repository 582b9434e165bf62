from benchmark import program_journeys


def read(run):
    """Of the median request's gap between tokens (`tpot_mid_ms`), the
    `host_sync` of the rounds whose step stood behind an admit or extend
    program (`prefill_ahead` over 0), ms: the step's own time in those
    rounds and the wait for the pieces."""
    return program_journeys.mid_ms(run, "behind")
