from benchmark import readers


def read(run):
    """Device time of the admit and extend programs, per 1,000 prompt
    tokens prefilled (prompts of 4,096 to 30,720 tokens: chunks of 512
    whose queries each score the whole prefix, choose 2,048 positions of
    it and attend it masked, then the expert tiles)."""
    seconds = readers.program_seconds(run, "prefill")
    tokens = readers.delta(run, "tokens_prefill", "trace_counters")
    if seconds is None or not tokens:
        return None
    return 1e3 * seconds / (tokens / 1e3)
