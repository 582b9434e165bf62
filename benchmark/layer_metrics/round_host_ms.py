from benchmark import program_rounds


def read(run):
    """The host's own work in a round, ms: the ring's `wall_s` less its
    `host_sync` field, the mean over the window's rounds."""
    return program_rounds.host_ms(run)
