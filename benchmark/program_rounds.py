"""The decoder's own record of every round, cut to a span of the run.

`ContinuousDecoder.pump` leaves one plain tuple a committed round in a
bounded ring (`aiko_services_tpu/observe/profiler.py`, `ROUND_FIELDS`):
its sequence number, the decoder's `rounds` counter at commit, its begin
on `perf_counter`, the gap since the previous round ended, whether the
decoder was idle at that end, its wall seconds, the seconds of each
phase, the scan's steps and slots, the prompt tokens it prefilled and
the depth of the queue.  `observe.profiler.round_log(name)` hands the
ring to whoever knows the decoder's name; a metric reader is handed
`run` and not the session, so that is how it gets there.

The driver snapshots the decoder's counters around the window
(`run["counters"]`) and around the traced span (`run["trace_counters"]`);
a record belongs to a span when its `rounds` lies in (before, after].
A round that only prefilled does not move `rounds` and goes with the
scanned round before it.  The window's rounds END WHERE THE TRACED SPAN
BEGINS: a per-layer metric is read in a `--trace 1` run, where the
profiler runs for the window's last seconds, starting it stands the
loop still for 0.05-0.1 s and its Python tracer slows every call of the host,
so those rounds say what tracing costs and not what a round costs
(`table` keeps both for a look by hand).

A program without the ring (the parent of the PR that added it) reads
as None everywhere here: the metric is then left out of the line.
"""

from __future__ import annotations

import statistics

DECODER = "bench"       # the name drivers/continuous_decoder.py gives its decoder


def rounds(run: dict, span: str = "counters") -> list | None:
    """The span's records as dicts keyed by ROUND_FIELDS, oldest first."""
    try:
        from aiko_services_tpu.observe import profiler
        log, fields = profiler.round_log(DECODER), profiler.ROUND_FIELDS
    except (ImportError, AttributeError, LookupError):
        return None
    counters = run.get(span) or {}
    if "rounds" not in counters.get("before", {}):
        return None
    low, high = counters["before"]["rounds"], counters["after"]["rounds"]
    traced = (run.get("trace_counters") or {}).get("before", {})
    if span == "counters" and "rounds" in traced:
        high = min(high, traced["rounds"])
    at = fields.index("rounds")
    return [dict(zip(fields, record)) for record in log
            if low < record[at] <= high]


def host_ms(run: dict) -> float | None:
    """Mean over the window's rounds of the wall time less the
    `host_sync` phase: what the host does in a round while it is not
    waiting for the device, in ms."""
    found = rounds(run)
    if not found:
        return None
    return 1e3 * statistics.fmean(r["wall_s"] - r["host_sync"] for r in found)


def longest_ms(run: dict) -> float | None:
    """The largest `gap_s + wall_s` among the window's rounds that did
    not follow an idle decoder, in ms: a stall of the loop reads here in
    seconds against a round's 100 ms, on the host or on the device."""
    found = [r for r in rounds(run) or () if not r["idle_before"]]
    if not found:
        return None
    return 1e3 * max(r["gap_s"] + r["wall_s"] for r in found)


def prefill_classes(run: dict) -> tuple | None:
    """The window's rounds of the commonest `num_steps`, as the wall
    seconds of (those that pay for prefill, those that do not).  The
    round that pays is the one AFTER the round whose `prefill_tokens` is
    over 0: `pump` dispatches a round's admits and extends behind its
    own step, they run on the device while the host walks that step's
    tokens, and the next round's step waits for them (on the chip a
    round that prefills takes 97.06 ms like any other, the round after
    it 116-144: PERF.md, PR 24)."""
    found = rounds(run)
    scanned = [r["num_steps"] for r in found or () if r["num_steps"]]
    if not scanned:
        return None
    steps = statistics.mode(scanned)
    pays, free = [], []
    for before, this in zip(found, found[1:]):
        if this["num_steps"] == steps:
            (pays if before["prefill_tokens"] > 0 else free).append(
                this["wall_s"])
    return pays, free


def prefill_penalty_ms(run: dict) -> float | None:
    classes = prefill_classes(run)
    if classes is None:
        return None
    with_prefill, without = classes
    if not with_prefill or not without:
        return 0.0
    return 1e3 * (statistics.median(with_prefill) - statistics.median(without))


def prefill_share(run: dict) -> float | None:
    found = rounds(run)
    if not found:
        return None
    return 100.0 * sum(r["prefill_tokens"] > 0 for r in found) / len(found)


def table(run: dict) -> dict | None:
    """The records of the window and of the traced span, with the mean
    round of each, for .bench_out/<cell>/program_spans.json."""
    window, traced = rounds(run), rounds(run, "trace_counters")
    if window is None:
        return None

    def mean_ms(found, key):
        return 1e3 * statistics.fmean(r[key] for r in found) if found else None

    out = {"fields": list(window[0]) if window else []}
    for name, found in (("window", window), ("traced", traced or [])):
        out[name] = {"rounds": [list(r.values()) for r in found],
                     "mean_wall_ms": mean_ms(found, "wall_s"),
                     "mean_gap_ms": mean_ms(found, "gap_s"),
                     "mean_sync_ms": mean_ms(found, "host_sync")}
    return out
