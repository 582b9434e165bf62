from benchmark import program_journeys


def read(run):
    """The gap between tokens of the median request, from inside, ms: the
    mean over the median band (`program_journeys`) of a request's own gap
    as the ring's intervals give it, host + sync clean + sync behind a
    piece.  The judged `llm_tpot_p50_ms` is the same quantity from the
    benchmark's stamps."""
    return program_journeys.mid_ms(run, *program_journeys.PARTS)
