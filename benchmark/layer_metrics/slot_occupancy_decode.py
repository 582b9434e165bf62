from benchmark import readers


def read(run):
    """Tokens the decode scan delivered over the slot-steps it ran."""
    steps = readers.delta(run, "steps")
    if not steps:
        return None
    return 100.0 * readers.delta(run, "tokens_decode") / (
        steps * run["counters"]["after"]["max_slots"])
