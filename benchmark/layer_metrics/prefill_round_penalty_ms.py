from benchmark import program_rounds


def read(run):
    """What a round that carries prefill costs every live slot, ms:
    among the window's rounds of the commonest `num_steps`, the median
    `wall_s` of those that follow a round with `prefill_tokens` > 0 (the
    prefill queues behind its own round's step, so the next round pays)
    less the median of the others.  Reads 0.0 when one of the two
    classes is empty (a rehearsal of three seconds may have it so)."""
    return program_rounds.prefill_penalty_ms(run)
