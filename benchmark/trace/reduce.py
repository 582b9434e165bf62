"""From a profiler trace to the numbers the per-layer metrics read.

`load` reads an `.xplane.pb` with `jax.profiler.ProfileData` and nothing
else, into plain lists; `reduce` works on those lists, so that it can be
tested on a small recorded trace (benchmark/tests/data/).

What a trace of this repo on a TPU v5e looks like (PERF.md, layers):
one plane per chip, `/device:TPU:<n>`, whose line `XLA Modules` has one
event per executed program, named `jit_<function>(<fingerprint>)`, and
whose line `XLA Ops` has the operations inside them, nested where one
contains others (a `while` holds its body).  The host is the plane
`/host:CPU`, one line per thread; the benchmark's own
`jax.profiler.TraceAnnotation`s are the events named `bench.*` on the
thread that drives the program.  All start times are nanoseconds on one
clock.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_TRACED = "bench.traced"
DEVICE_PREFIX, HOST_PREFIX = "/device:", "/host:"
MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"


def load(path: str) -> list:
    """[{"name": plane, "lines": [{"name": line, "events":
    [[name, start_ns, duration_ns], ...]}]}]"""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [{"name": line.name,
                  "events": [[event.name, float(event.start_ns),
                              float(event.duration_ns)]
                             for event in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union_seconds(intervals: list, start: float, end: float) -> tuple:
    """Seconds covered by the union of [a, b) intervals inside
    [start, end), and the gaps between them as [a, b) pairs."""
    clipped = sorted((max(a, start), min(b, end)) for a, b in intervals
                     if b > start and a < end)
    covered, cursor, gaps = 0.0, start, []
    for a, b in clipped:
        if a > cursor:
            gaps.append((cursor, a))
        if b > cursor:
            covered += b - max(a, cursor)
            cursor = b
    if end > cursor:
        gaps.append((cursor, end))
    return covered / 1e9, gaps


def self_times(events: list) -> dict:
    """Seconds by event name, each event's time less what the events
    nested inside it take (a `while` is charged its own time only)."""
    totals: dict = {}
    stack: list = []       # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(own, 0.0) / 1e9

    for name, start, duration in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= duration
        stack.append([name, start + duration, duration])
    close(float("inf"))
    return totals


def program_name(event_name: str) -> str:
    """`jit_step(1234567)` -> `jit_step`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def reduce(planes: list, labels: dict | None = None) -> dict:
    """Busy and idle seconds of the devices inside the benchmark's traced
    span, device seconds by program and by operation, the host's own
    spans, and the idle gaps by what the host was doing.

    `labels` maps a host span's name to the word `breakdown` uses for it
    (a configuration's `trace.idle_labels`)."""
    labels = labels or {}
    host_spans: dict = {}
    traced = None
    for plane in planes:
        if not plane["name"].startswith(HOST_PREFIX):
            continue
        for line in plane["lines"]:
            for name, start, duration in line["events"]:
                if name == SPAN_TRACED:
                    traced = (start, start + duration)
                elif name.startswith("bench."):
                    host_spans.setdefault(name, []).append(
                        (start, start + duration))
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)
               and (_line(p, OPS_LINE) or _line(p, MODULE_LINE))]
    if traced is None:
        every = [(s, s + d) for p in devices for line in p["lines"]
                 for _, s, d in line["events"]]
        if not every:
            return {"window_s": 0.0, "busy_s": 0.0, "devices": 0,
                    "programs": {}, "device_ops": [], "idle_gaps": [],
                    "host_spans": {}}
        traced = (min(a for a, _ in every), max(b for _, b in every))
    start, end = traced

    busy, programs, ops, gaps_by_label = [], {}, {}, {}
    for plane in devices:
        op_events = _line(plane, OPS_LINE) or _line(plane, MODULE_LINE)
        inside = [e for e in op_events if e[1] + e[2] > start and e[1] < end]
        covered, gaps = union_seconds([(s, s + d) for _, s, d in inside],
                                      start, end)
        busy.append(covered)
        for name, seconds in self_times(inside).items():
            ops[name] = ops.get(name, 0.0) + seconds
        for name, s, d in _line(plane, MODULE_LINE):
            if s + d > start and s < end:
                entry = programs.setdefault(program_name(name),
                                            {"count": 0, "seconds": 0.0})
                entry["count"] += 1
                entry["seconds"] += (min(s + d, end) - max(s, start)) / 1e9
        for a, b in gaps:
            middle, label, width = (a + b) / 2, "unlabelled", None
            for name, spans in host_spans.items():
                for s, e in spans:
                    if s <= middle < e and (width is None or e - s < width):
                        label, width = labels.get(name, name), e - s
            gaps_by_label[label] = gaps_by_label.get(label, 0.0) + (b - a) / 1e9
    count = max(1, len(devices))
    spans = {name: {"count": len(found),
                    "seconds": union_seconds(found, start, end)[0]}
             for name, found in host_spans.items()}

    def ranked(table: dict) -> list:
        return [[name, seconds] for name, seconds in
                sorted(table.items(), key=lambda item: -item[1])]

    return {"window_s": (end - start) / 1e9,
            "busy_s": sum(busy) / count, "devices": len(devices),
            "programs": {name: {"count": e["count"] / count,
                                "seconds": e["seconds"] / count}
                         for name, e in programs.items()},
            "device_ops": [[n, s / count] for n, s in ranked(ops)[:50]],
            "idle_gaps": [[n, s / count] for n, s in ranked(gaps_by_label)],
            "host_spans": spans}


def reduce_directory(trace_dir: str, labels: dict | None = None) -> dict:
    """The newest `.xplane.pb` under a `jax.profiler.start_trace` directory."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(load(found[-1]), labels)
