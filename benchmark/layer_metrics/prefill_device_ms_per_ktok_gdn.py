from benchmark import readers


def read(run):
    """Device time of the admit and extend programs, per 1,000 prompt
    tokens prefilled (prompts of 128 to 512 tokens, admitted whole in a
    bucket of 256 or 512: the chunked scan of the recurrent layers, plain
    causal attention in the full ones)."""
    seconds = readers.program_seconds(run, "prefill")
    tokens = readers.delta(run, "tokens_prefill", "trace_counters")
    if seconds is None or not tokens:
        return None
    return 1e3 * seconds / (tokens / 1e3)
