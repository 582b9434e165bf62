from benchmark import program_journeys


def read(run):
    """Of the rounds that the median band's requests lived through, the
    share whose step stood behind an admit or extend program, %: beside
    `rounds_with_prefill.*`, which is over all the window's rounds."""
    return program_journeys.rounds_behind_share(run)
