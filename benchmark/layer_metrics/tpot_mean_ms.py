from benchmark import readers


def read(run):
    """The gap between output tokens over all the tokens of the window's
    requests: the sum of (last token - first token) over the sum of
    (tokens - 1).  It moves with every round, so it shows a change the
    median hides, and a stall of the host at its full length."""
    return readers.mean_token_gap_ms(run)
