"""The arithmetic the metric files share.  A metric is a file under
end_to_end/ or layer_metrics/ named after it (dots become `_`) whose
`read(run)` returns a number, or None where it finds nothing to read.

`run` holds: records (the counted requests: due, sent, first, last, done,
tokens, failed, in seconds from the window's opening), all_records and
requests by id, seconds, miss_s, tokens_in_window, set_up_seconds, counters
{before, after} and trace_counters {before, after} (the driver's counters
around the window and around the traced span), trace (trace/reduce.py's
output, or None), config, traffic and peaks.
"""

from __future__ import annotations

from benchmark import ops_bytes, stats


def latencies(records: list, start: str, end: str) -> list:
    """end - start in seconds for every counted request; None where it
    failed."""
    return [None if r["failed"] or r[end] is None else r[end] - r[start]
            for r in records]


def per_token_seconds(records: list) -> list:
    """Per request, (last token - first token) / (tokens - 1); None where
    it failed.  A request of one token has no gap and is left out."""
    return [None if r["failed"] or r["last"] is None
            else (r["last"] - r["first"]) / (r["tokens"] - 1)
            for r in records if r["failed"] or r["tokens"] > 1]


def p95_with_misses_ms(run: dict, values: list) -> float | None:
    """95th percentile in ms; a failed request (None) is a miss at the
    time the run gave up."""
    if not values:
        return None
    return stats.percentile(stats.with_misses(values, run["miss_s"]), 95) * 1e3


def mean_token_gap_ms(run: dict) -> float | None:
    """Time from each request's first token to its last, summed over the
    counted requests, over the gaps between their tokens (tokens - 1): the
    gap between tokens taken over all the tokens of the window's requests,
    in ms.  A failed request adds one gap as long as the time at which the
    run gave up on it."""
    seconds, gaps = 0.0, 0
    for r in run["records"]:
        if r["failed"] or r["last"] is None:
            seconds, gaps = seconds + run["miss_s"], gaps + 1
        elif r["tokens"] > 1:
            seconds, gaps = (seconds + r["last"] - r["first"],
                             gaps + r["tokens"] - 1)
    return 1e3 * seconds / gaps if gaps else None


def delta(run: dict, key: str, span: str = "counters") -> float | None:
    counters = run[span]
    if "before" not in counters or key not in counters["before"]:
        return None
    return counters["after"][key] - counters["before"][key]


def ratio(numerator, denominator) -> float | None:
    if numerator is None or not denominator:
        return None
    return numerator / denominator


def program_seconds(run: dict, role: str) -> float | None:
    """Device seconds, inside the traced span, of the programs that the
    configuration lists under trace.programs.<role>."""
    if not run["trace"]:
        return None
    names = run["config"]["trace"]["programs"][role]
    found = [run["trace"]["programs"][n]["seconds"] for n in names
             if n in run["trace"]["programs"]]
    return sum(found) if found else None


def idle_share(run: dict) -> float | None:
    trace = run["trace"]
    if not trace or not trace["window_s"] or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def live_tokens(run: dict, start: float, end: float, points: int = 50) -> float:
    """Mean tokens of context held by running requests over [start, end),
    from the benchmark's own stamps: a request holds its prompt from its
    first token on, and grows evenly to its last."""
    total = 0.0
    for i in range(points):
        t = start + (end - start) * (i + 0.5) / points
        for rid, r in run["all_records"].items():
            if r["first"] is None or r["first"] > t or (
                    r["done"] is not None and r["done"] <= t):
                continue
            grown = r["tokens"] if r["last"] <= r["first"] else \
                r["tokens"] * min(1.0, (t - r["first"])
                                  / (r["last"] - r["first"]))
            total += run["requests"][rid]["prompt_tokens"] + grown
    return total / points


def decode_step_ms(run: dict) -> float | None:
    per_step = ratio(program_seconds(run, "decode_step"),
                     delta(run, "steps", "trace_counters"))
    return None if per_step is None else 1e3 * per_step


def decode_step_roofline(run: dict) -> float | None:
    step_ms = decode_step_ms(run)
    if step_ms is None:
        return None
    config, trace = run["config"], run["trace"]
    end = run["seconds"]
    held = live_tokens(run, end - trace["window_s"], end)
    work = ops_bytes.decode_step(config, ops_bytes.ITEMSIZE[config["dtype"]],
                                 config["serving"]["max_slots"], held)
    return 100.0 * ops_bytes.roofline_seconds(work, run["peaks"])["seconds"] \
        / (step_ms / 1e3)
