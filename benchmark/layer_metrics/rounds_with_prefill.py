from benchmark import program_rounds


def read(run):
    """The share of the window's rounds with `prefill_tokens` > 0, %."""
    return program_rounds.prefill_share(run)
