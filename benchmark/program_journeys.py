"""The judged gap between tokens, split from inside for the median request.

`llm_tpot_p50_ms` is per request (last - first token) / (tokens - 1), the
median over requests; the ring of `program_rounds` is per round.  Since
ISSUE 36 the program joins the two: every finished request leaves one
plain tuple (`aiko_services_tpu/observe/journey.py`, `JOURNEY_RECORD`,
handed out by `journey_log(name)` as `round_log(name)` hands out the
rounds) that carries the `seq` of the round that handed over its first
token (`first_round`) and of the round that handed over its last
(`last_round`), and a round's record says what prefill stood ahead of its
step on the device (`ROUND_RECORD`: `prefill_ahead`, `prefill_pieces`,
`prefill_prefix_tokens`, behind `attend_width`).

`pump` stamps a round's tokens once, where it enters `wave_resolve`.  So
for each round r with first_round < seq <= last_round the interval from
the stamp of the round before r to the stamp of r is, field by field of
the two records,

    host           the earlier round's wave_resolve + deliver + other,
                   r's gap_s, plan, scan_dispatch, spec_verify,
                   admit_dispatch, extend_dispatch
    sync, clean    r's host_sync where r's prefill_ahead is 0
    sync, behind   r's host_sync where it is over 0: the step's own time
                   AND the wait for the pieces ahead of it

and the three, summed over the request's rounds and divided by its
tokens - 1, are its own gap on the ring's clock (`perf_counter`; the
journey's stamps are on `time.monotonic`, and nothing here subtracts one
from the other: a journey meets the ring by `seq` alone).

The MEDIAN BAND is the requests whose gap ranks from the 40th to the 60th
percentile, the five nearest the median where that is fewer, all where
there are under five.  It is taken over ONE population and no other: the
counted requests of the window (`0 <= due < seconds`) that finished with
two tokens or more and whose last round lies before the traced span (the
profiler's Python tracer slows the host there: `program_rounds`).  Where
there is none, as in the 1.5 s a 3 s rehearsal leaves before its span,
the metrics read 0.0 and never another population's number.  None is for
a program without `journey_log` or without the ring's new fields (the
parent of ISSUE 36), or with no decoder of the driver's name: the metric
is then left out.
"""

from __future__ import annotations

import math
import statistics

from benchmark import program_rounds

HOST_BEFORE = ("wave_resolve", "deliver", "other")
HOST_OF = ("gap_s", "plan", "scan_dispatch", "spec_verify",
           "admit_dispatch", "extend_dispatch")
PARTS = ("host", "clean", "behind")


def program() -> tuple | None:
    """(the newest finished journey of each request id as a dict over
    JOURNEY_RECORD, the ring's records as dicts over ROUND_RECORD by
    `seq`), or None (the module's last sentence)."""
    try:
        from aiko_services_tpu.observe import journey, profiler
        fields, names = profiler.ROUND_RECORD, journey.JOURNEY_RECORD
        finished = journey.journey_log(program_rounds.DECODER)
        log = profiler.round_log(program_rounds.DECODER)
    except (ImportError, AttributeError, LookupError):
        return None
    if "prefill_ahead" not in fields or \
            any(len(record) < len(fields) for record in log):
        return None
    return ({j[0]: dict(zip(names, j)) for j in finished},
            {r[0]: dict(zip(fields, r)) for r in log})


def parts(journey: dict, ring: dict) -> dict | None:
    """One request's gap between tokens in seconds as `host`, `clean` and
    `behind`, with the `rounds` it was split over and how many of them
    stood `rounds_behind` a piece; None for a request with no gap (under
    two tokens, never finished) or one whose rounds the ring has let go."""
    first, last = journey["first_round"], journey["last_round"]
    gaps = journey["tokens_total"] - 1
    if gaps < 1 or first < 0 or last < first or first not in ring:
        return None
    out = dict.fromkeys(PARTS, 0.0) | {"rounds": last - first,
                                       "rounds_behind": 0}
    for seq in range(first + 1, last + 1):
        before, this = ring.get(seq - 1), ring.get(seq)
        if before is None or this is None:
            return None
        out["host"] += sum(before[k] for k in HOST_BEFORE) \
            + sum(this[k] for k in HOST_OF)
        if this["prefill_ahead"] > 0:
            out["behind"] += this["host_sync"]
            out["rounds_behind"] += 1
        else:
            out["clean"] += this["host_sync"]
    for key in PARTS:
        out[key] /= gaps
    return out


def middle(ranked: list) -> list:
    """The 40th to the 60th percentile of a sorted list, five where that
    is fewer, all of it where it has under five."""
    n = len(ranked)
    low, high = math.floor(0.4 * n), math.ceil(0.6 * n)
    if high - low < 5:
        low = max(0, (n - 5) // 2)
        high = min(n, low + 5)
    return ranked[low:high]


def band(run: dict) -> list | None:
    """`parts` of each request of the median band; [] where the window
    counted no request of two tokens that finished before the traced
    span."""
    found = program()
    if found is None:
        return None
    journeys, ring = found
    traced_from = (run.get("trace_counters") or {}).get(
        "before", {}).get("rounds")
    clear = []
    for rid, record in run["all_records"].items():
        if record["failed"] or record["done"] is None or \
                rid not in journeys or \
                not 0.0 <= record["due"] < run["seconds"]:
            continue
        split = parts(journeys[rid], ring)
        if split is not None and (traced_from is None or ring[
                journeys[rid]["last_round"]]["rounds"] <= traced_from):
            clear.append(split)
    return middle(sorted(clear, key=lambda s: sum(s[key] for key in PARTS)))


def mid_ms(run: dict, *keys: str) -> float | None:
    """Mean over the median band of the named parts' sum, in ms."""
    found = band(run)
    if found is None:
        return None
    if not found:
        return 0.0
    return 1e3 * statistics.fmean(sum(s[key] for key in keys) for s in found)


def rounds_behind_share(run: dict) -> float | None:
    """Of the rounds the band's requests were split over, the share with
    `prefill_ahead` over 0, %."""
    found = band(run)
    if found is None:
        return None
    rounds = sum(s["rounds"] for s in found)
    return 100.0 * sum(s["rounds_behind"] for s in found) / rounds \
        if rounds else 0.0


def prefix_depth(run: dict) -> float | None:
    """Over the rounds of the TRACED span (the span that
    `prefill_device_ms_per_ktok.*` is read over), the positions already
    in the pool that a dispatched piece's rows read, a piece: sum of
    `prefill_prefix_tokens` over sum of `prefill_pieces`; 0.0 where the
    span dispatched no piece.  A covariate: the traffic and the chunk
    size set it, no optimisation is meant to lower it, and the manifest's
    `better` carries no meaning for it."""
    found, traced = program(), program_rounds.rounds(run, "trace_counters")
    if found is None or traced is None:
        return None
    records = [found[1][r["seq"]] for r in traced if r["seq"] in found[1]]
    pieces = sum(r["prefill_pieces"] for r in records)
    return sum(r["prefill_prefix_tokens"] for r in records) / pieces \
        if pieces else 0.0
