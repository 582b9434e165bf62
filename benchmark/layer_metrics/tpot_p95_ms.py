from benchmark import readers


def read(run):
    """Per request, (last token - first token) / (tokens - 1); the 95th
    percentile over requests.  Tokens land in bursts of `steps_per_sync`,
    so this is the gap a reader feels, not the gap inside a burst.  Its
    tail is made of the shortest answers, where one stalled round is a
    tenth of the whole: a reading, too unsteady to judge (PERF.md)."""
    return readers.p95_with_misses_ms(
        run, readers.per_token_seconds(run["records"]))
