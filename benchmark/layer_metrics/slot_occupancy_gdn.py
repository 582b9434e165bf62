from benchmark import readers_gated_delta


def read(run):
    """Tokens the decode scan delivered over the slot-steps it ran, in the
    traced span (the window's last seconds: the steady state; the
    counters around the window hold the pre-roll's ramp): the share of the
    64 slots that decode, step by step."""
    return readers_gated_delta.traced_share(
        run, "tokens_decode", "steps", run["counters"]["after"]["max_slots"])
