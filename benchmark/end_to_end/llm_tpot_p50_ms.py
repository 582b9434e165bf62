from benchmark import readers, stats


def read(run):
    """Per request, (last token - first token) / (tokens - 1); the median
    over all the requests due in the window, a failed one counted as a miss.
    Tokens land in bursts of `steps_per_sync`, so this is the gap a reader
    feels, not the gap inside a burst.  The median, because a whole-loop
    stall of a second or two (one in some thirty runs on the chip) moves it
    by 1.6% where it moves the mean by 4% and the p95 by 19% (PERF.md)."""
    values = readers.per_token_seconds(run["records"])
    if not values:
        return None
    return stats.percentile(stats.with_misses(values, run["miss_s"]), 50) * 1e3
