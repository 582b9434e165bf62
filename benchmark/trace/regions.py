#!/usr/bin/env python3
"""What `reduce.py` drops: the named regions of the device's operations and
the decoder's own spans on the host.

    python3 benchmark/trace/regions.py <trace directory or .xplane.pb> [out.json [start_s length_s]]

prints the regions and the idle table of a trace; with a second argument it
also cuts a recording for the tests (benchmark/tests/data/), in the plain
form `load` returns.

How a trace of this repo names a region (one looked at by hand, v5e, PR 24):
an `XLA Ops` event carries nothing but its times; its `jax.named_scope` is
in the METADATA of the event (`XEventMetadata.stats`, which
`jax.profiler.ProfileData` does not hand out), under the stat `tf_op`, as the
operation's whole path: `jit(step)/while/body/closed_call/aiko.mlp/...i,io->
...o/dot_general:`.  A fusion has one such path, its root's: it is charged
to the region its root carries.  The same metadata holds `program_id`, the
fingerprint in the brackets of the `XLA Modules` event `jit_step(<id>)`, so
an operation is tied to its program without a look at the clock.  The raw
trace has no line of name scopes (xprof derives its own).  So this file
reads the `.xplane.pb` wire format itself, the five messages it needs and
no more (tsl/profiler/protobuf/xplane.proto), with no package beyond the
standard library.

The decoder's spans (`aiko_services_tpu/observe/profiler.py`) are
`jax.profiler.TraceAnnotation`s on `/host:CPU`, on the clock of the device
planes: `aiko.decoder.round` around a working round, and inside it
`aiko.decoder.plan`, `.dispatch_step`, `.dispatch_prefill`, `.sync`,
`.deliver`.  A program without them, or without scopes, reads as nothing
here: every function returns None and the metric is left out.
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark import program_rounds  # noqa: E402
from benchmark.trace import reduce as R  # noqa: E402

SCOPE_PREFIX = "aiko."
SPAN_PREFIX = "aiko.decoder."
SPAN_ROUND = "aiko.decoder.round"
SPAN_SYNC = "aiko.decoder.sync"
UNSCOPED, COMPILER = "unscoped", "compiler"     # scopes 0 and 1 of a trace
BETWEEN, IN_ROUND = "between_rounds", "round"
CONTROL_FLOW = ("while", "body", "cond", "closed_call")


# -- the wire format ------------------------------------------------------------

def _varint(buf, pos: int) -> tuple:
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, pos
        shift += 7


def _fields(buf, pos: int, end: int):
    """(field number, value) of one message: an int for a varint, a
    (start, end) pair for a length-delimited field; fixed-width fields
    (a stat's double) are skipped."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield key >> 3, value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield key >> 3, (pos, pos + size)
            pos += size
        elif wire in (1, 5):
            pos += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _text(buf, span: tuple) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, spans: list):
    """A map<int64, message> field: (key, span of the message)."""
    for start, end in spans:
        key, message = 0, None
        for field, value in _fields(buf, start, end):
            if field == 1:
                key = value
            elif field == 2:
                message = value
        if message is not None:
            yield key, message


def _plane(buf, start: int, end: int) -> dict | None:
    """One XPlane of a device or of the host (None for any other):
    {"name", "lines": {line name: [[metadata id, start_ns, duration_ns]]},
    "metadata": {id: {"name", "scope", "program"}}}."""
    name, lines, event_md, stat_md = "", [], [], []
    for field, value in _fields(buf, start, end):
        if field == 2:
            name = _text(buf, value)
        elif field == 3:
            lines.append(value)
        elif field == 4:
            event_md.append(value)
        elif field == 5:
            stat_md.append(value)
    if not name.startswith((R.DEVICE_PREFIX, R.HOST_PREFIX)):
        return None
    stat_names = {}
    for key, (a, b) in _map_entries(buf, stat_md):
        for field, value in _fields(buf, a, b):
            if field == 2:
                stat_names[key] = _text(buf, value)
    metadata = {}
    for key, (a, b) in _map_entries(buf, event_md):
        entry = {"name": "", "scope": COMPILER, "program": None}
        for field, value in _fields(buf, a, b):
            if field == 2:
                entry["name"] = _text(buf, value)
            elif field == 5:                        # XStat of the metadata
                stat, text, number = None, None, None
                for f, v in _fields(buf, *value):
                    if f == 1:
                        stat = stat_names.get(v)
                    elif f in (3, 4):
                        number = v
                    elif f == 5:
                        text = v
                if stat == "tf_op" and text is not None:
                    entry["scope"] = scope_of(_text(buf, text))
                elif stat == "program_id" and number is not None:
                    entry["program"] = number % (1 << 64)
        metadata[key] = entry
    out = {}
    for a, b in lines:
        line_name, stamp_ns, events = "", 0, []
        for field, value in _fields(buf, a, b):
            if field == 2:
                line_name = _text(buf, value)
            elif field == 3:
                stamp_ns = value
            elif field == 4:
                events.append(value)
        rows = []
        for ea, eb in events:
            ident = offset_ps = duration_ps = 0
            for field, value in _fields(buf, ea, eb):
                if field == 1:
                    ident = value
                elif field == 2:
                    offset_ps = value
                elif field == 3:
                    duration_ps = value
            rows.append([ident, stamp_ns + offset_ps / 1e3, duration_ps / 1e3])
        out[line_name] = rows
    return {"name": name, "lines": out, "metadata": metadata}


def scope_of(op_path: str) -> str | None:
    """`jit(step)/while/body/aiko.mlp/dot_general:` -> `aiko.mlp`: the
    innermost component that is one of the program's regions.  A path
    that names no operation of the program (none, an argument's name
    `k_pools[3]:`, or a loop and no more, `jit(step)/while:`) is the
    compiler's: COMPILER.  Any other is an operation the program left
    outside its regions: None."""
    parts = op_path.rstrip(":").split("/")
    found = None
    for part in parts:
        if part.startswith(SCOPE_PREFIX):
            found = part
    if found is None and (len(parts) == 1 or parts[-1] in CONTROL_FLOW):
        return COMPILER
    return found


def load(path: str) -> dict:
    """An `.xplane.pb` as plain lists, all times in ns on one clock:

    {"devices": [{"name", "modules": [[program, start, duration]],
                  "ops": [[start, duration, scope index, program index]]}],
     "scopes": [...], "programs": ["jit_step(123)", ...],
     "host": [[span name, start, duration]]}

    `host` keeps the decoder's `aiko.decoder.*` spans and the
    benchmark's `bench.*`; `scopes` begins with UNSCOPED (an operation of
    the program outside its regions) and COMPILER (one with no path of
    its own, see `scope_of`)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = [value for field, value in _fields(buf, 0, len(buf))
              if field == 1]
    out = {"devices": [], "scopes": [UNSCOPED, COMPILER], "programs": [],
           "host": []}
    scope_index, program_index = {None: 0, COMPILER: 1}, {}
    for a, b in planes:
        plane = _plane(buf, a, b)
        if plane is None:
            continue
        metadata, lines = plane["metadata"], plane["lines"]
        if plane["name"].startswith(R.HOST_PREFIX):
            for rows in lines.values():
                for ident, start, duration in rows:
                    name = metadata.get(ident, {}).get("name", "")
                    if name.startswith((SPAN_PREFIX, "bench.")):
                        out["host"].append([name, start, duration])
            continue
        if not lines.get(R.OPS_LINE):
            continue
        by_fingerprint = {}
        modules = []
        for ident, start, duration in lines.get(R.MODULE_LINE, []):
            name = metadata.get(ident, {}).get("name", "")
            modules.append([name, start, duration])
            digits = name.rpartition("(")[2].rstrip(")")
            if digits.isdigit():
                by_fingerprint[int(digits)] = name
        ops = []
        for ident, start, duration in lines[R.OPS_LINE]:
            entry = metadata.get(ident, {})
            scope = entry.get("scope", COMPILER)
            if scope not in scope_index:
                scope_index[scope] = len(out["scopes"])
                out["scopes"].append(scope)
            program = by_fingerprint.get(entry.get("program"), "")
            if program not in program_index:
                program_index[program] = len(out["programs"])
                out["programs"].append(program)
            ops.append([start, duration, scope_index[scope],
                        program_index[program]])
        out["devices"].append({"name": plane["name"], "modules": modules,
                               "ops": ops})
    out["host"].sort(key=lambda row: row[1])
    return out


def newest_trace(root: str | None = None) -> str | None:
    """The newest `.xplane.pb` under <checkout>/.bench_out/."""
    root = root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    found = glob.glob(os.path.join(root, ".bench_out", "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def _loaded(path: str, stamp: float) -> dict:
    return load(path)


def of_run(run: dict) -> tuple:
    """(what `load` gives of the run's trace, the cell's directory under
    .bench_out), or (None, None) where the run was not traced: loaded
    once a process."""
    if not run.get("trace") or not run["trace"].get("devices"):
        return None, None
    path = newest_trace()
    if path is None:
        return None, None
    out_dir = path.split(os.sep + "trace" + os.sep + "plugins" + os.sep)[0]
    return _loaded(path, os.path.getmtime(path)), out_dir


# -- the arithmetic ---------------------------------------------------------------

def traced_span(trace: dict) -> tuple | None:
    for name, start, duration in trace["host"]:
        if name == R.SPAN_TRACED:
            return start, start + duration
    return None


def _clipped(start: float, duration: float, low: float, high: float):
    a, b = max(start, low), min(start + duration, high)
    return (a, b - a) if b > a else None


def region_seconds(trace: dict, programs: list, span: tuple | None = None
                   ) -> dict | None:
    """Device seconds by region, inside the programs named (`jit_step`,
    fingerprint stripped) and inside `span`, averaged over the chips:
    {"seconds": {region: s}, "adopted": {region: s of it}}.

    An operation is charged its own time, less that of the operations
    nested in it, under the scope it carries.  One that the compiler
    added (COMPILER: a copy, an async slice) and that holds no other is
    adopted by the region of the operation that ran before it in the
    same run of the program, or of the next one where none came before:
    the two copies of a pool leaf around the merge's scatter into it are
    the merge's.  `adopted` says how much of a region came to it so.
    Unscoped are the operations that the program left outside its
    regions, the own time of the compiler's that hold others (a
    `while`), and the compiler's in a run with no region at all.

    None where no operation of those programs carries a scope (a
    program from before the scopes)."""
    span = span or traced_span(trace) or (float("-inf"), float("inf"))
    wanted = {i for i, name in enumerate(trace["programs"])
              if R.program_name(name) in programs}
    seconds = [0.0] * len(trace["scopes"])
    adopted = [0.0] * len(trace["scopes"])
    # of the run being read: the scope of the last scoped operation, and
    # the ns of the compiler's operations that came before any
    run = {"last": 0, "waiting": 0.0}

    def close(stack: list) -> None:
        """The innermost open operation ends: charge its own time."""
        _, own, scope, holds_others = stack.pop()
        if not stack:                 # the run itself, not an operation
            seconds[0] += run["waiting"]
            run["last"], run["waiting"] = 0, 0.0
        elif scope > 1:
            seconds[scope] += own + run["waiting"]
            adopted[scope] += run["waiting"]
            run["last"], run["waiting"] = scope, 0.0
        elif scope == 0 or holds_others:
            seconds[0] += own
        elif run["last"]:
            seconds[run["last"]] += own
            adopted[run["last"]] += own
        else:
            run["waiting"] += own

    for device in trace["devices"]:
        runs = sorted((s, s + d) for name, s, d in device["modules"]
                      if R.program_name(name) in programs)
        starts = [a for a, _ in runs]
        ops = sorted((op for op in device["ops"] if op[3] in wanted),
                     key=lambda op: (op[0], -op[1]))
        stack: list = []      # frames: [end, own ns, scope, holds others]
        for start, duration, scope, _ in ops:
            while stack and stack[-1][0] <= start:
                close(stack)
            if not stack:
                at = bisect.bisect_right(starts, start) - 1
                end = runs[at][1] if at >= 0 and start < runs[at][1] \
                    else start + duration
                stack.append([end, 0.0, 0, True])
            clip = _clipped(start, duration, *span)
            inside = clip[1] if clip else 0.0
            if len(stack) > 1:
                stack[-1][1] -= inside
                stack[-1][3] = True
            stack.append([start + duration, inside, scope, False])
        while stack:
            close(stack)
    if not any(seconds[2:]):
        return None
    count = max(1, len(trace["devices"]))

    def named(column: list) -> dict:
        return {trace["scopes"][i]: max(ns, 0.0) / 1e9 / count
                for i, ns in enumerate(column) if ns}

    return {"seconds": named(seconds), "adopted": named(adopted)}


def idle_by_phase(trace: dict, span: tuple | None = None) -> dict | None:
    """The device's idle seconds inside `span` by what the decoder was
    doing, each gap named by the `aiko.decoder.*` phase over its middle
    (`plan`, `dispatch_step`, ...), `round` inside a round and outside
    every phase, `between_rounds` outside every round; with the number
    of rounds that began in the span.  `by_phase` has the gaps between
    operations (what `reduce.py` calls idle), `no_program` those of
    them in which the device ran no program at all: a bubble between
    two operations of one program is the device's own, whatever the
    host was doing.  None where the trace has no device or no span of
    the decoder's."""
    span = span or traced_span(trace)
    rounds = [(s, s + d) for name, s, d in trace["host"]
              if name == SPAN_ROUND]
    phases = [(s, s + d, name[len(SPAN_PREFIX):])
              for name, s, d in trace["host"]
              if name.startswith(SPAN_PREFIX) and name != SPAN_ROUND]
    if span is None or not trace["devices"] or not rounds:
        return None
    round_starts = [a for a, _ in rounds]
    phase_starts = [a for a, _, _ in phases]
    count = len(trace["devices"])

    def labelled(intervals_of) -> dict:
        table = {}
        for device in trace["devices"]:
            _, gaps = R.union_seconds(intervals_of(device), *span)
            for a, b in gaps:
                middle, label = (a + b) / 2, BETWEEN
                at = bisect.bisect_right(round_starts, middle) - 1
                if at >= 0 and middle < rounds[at][1]:
                    label = IN_ROUND
                    at = bisect.bisect_right(phase_starts, middle) - 1
                    if at >= 0 and middle < phases[at][1]:
                        label = phases[at][2]
                entry = table.setdefault(label, {"seconds": 0.0, "gaps": 0})
                entry["seconds"] += (b - a) / 1e9 / count
                entry["gaps"] += 1 / count
        return table

    by_phase = labelled(lambda d: [(s, s + t) for s, t, _, _ in d["ops"]])
    began = sum(span[0] <= a < span[1] for a in round_starts)
    return {"rounds": began, "window_s": (span[1] - span[0]) / 1e9,
            "idle_s": sum(e["seconds"] for e in by_phase.values()),
            "by_phase": by_phase,
            "no_program": labelled(
                lambda d: [(s, s + t) for _, s, t in d["modules"]])}


# -- what the metric files call ---------------------------------------------------

def _note(out_dir: str, key: str, value) -> None:
    """<cell>/program_spans.json keeps the tables behind the numbers."""
    path = os.path.join(out_dir, "program_spans.json")
    try:
        with open(path) as f:
            notes = json.load(f)
    except (OSError, ValueError):
        notes = {}
    notes[key] = value
    with open(path, "w") as f:
        json.dump(notes, f, indent=1)


def step_region_ms(run: dict, scope: str) -> float | None:
    """Device ms a decode step spends under `scope`: the region's
    seconds inside the decode-step programs over the steps run in the
    traced span, the denominator of `decode_step_device_ms`."""
    trace, out_dir = of_run(run)
    steps = (run.get("trace_counters") or {})
    if trace is None or "steps" not in steps.get("before", {}):
        return None
    steps = steps["after"]["steps"] - steps["before"]["steps"]
    if "decode_step_regions_ms" not in trace:     # once for the five regions
        found = region_seconds(
            trace, run["config"]["trace"]["programs"]["decode_step"])
        trace["decode_step_regions_ms"] = found and steps and {
            key: {name: 1e3 * s / steps for name, s in sorted(column.items())}
            for key, column in found.items()} | {"steps": steps}
        if trace["decode_step_regions_ms"]:
            _note(out_dir, "decode_step_regions_ms",
                  trace["decode_step_regions_ms"])
    per_step = trace["decode_step_regions_ms"]
    return per_step["seconds"].get(scope, 0.0) if per_step else None


def device_wait_on_host_ms(run: dict) -> float | None:
    """Device idle ms a round that the host answers for: inside the
    traced span, every gap whose middle does not lie in the decoder's
    `sync` (there the host waits for the device, not the device for
    the host), over the rounds begun in the span."""
    trace, out_dir = of_run(run)
    if trace is None:
        return None
    table = idle_by_phase(trace)
    if not table or not table["rounds"]:
        return None
    _note(out_dir, "device_idle_by_phase", table | {
        "idle_gaps_of_reduce": run["trace"].get("idle_gaps")})
    _note(out_dir, "rounds", program_rounds.table(run))
    waited = table["idle_s"] - table["by_phase"].get(
        SPAN_SYNC[len(SPAN_PREFIX):], {"seconds": 0.0})["seconds"]
    return 1e3 * waited / table["rounds"]


# -- by hand ----------------------------------------------------------------------

def cut(trace: dict, start_s: float, length_s: float) -> dict:
    """`length_s` seconds from `start_s` seconds into the traced span,
    times as whole ns from the cut's begin: a recording for the tests."""
    low = traced_span(trace)[0] + start_s * 1e9
    high = low + length_s * 1e9

    def rows(found, at):
        out = []
        for row in found:
            clip = _clipped(row[at], row[at + 1], low, high)
            if clip:
                row = list(row)
                row[at], row[at + 1] = round(clip[0] - low), round(clip[1])
                out.append(row)
        return out

    host = rows([r for r in trace["host"] if r[0] != R.SPAN_TRACED], 1)
    host.append([R.SPAN_TRACED, 0, round(high - low)])
    return {"scopes": trace["scopes"], "programs": trace["programs"],
            "host": host,
            "devices": [{"name": d["name"], "modules": rows(d["modules"], 1),
                         "ops": rows(d["ops"], 0)}
                        for d in trace["devices"]]}


def main(argv: list) -> int:
    path = argv[0]
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    trace = load(path)
    print(f"{path}: {os.path.getsize(path) / 1e6:.1f} MB, "
          f"{len(trace['devices'])} device(s), scopes {trace['scopes']}")
    names = sorted({R.program_name(p) for p in trace["programs"] if p})
    for program in names:
        found = region_seconds(trace, [program])
        if found:
            print(f"  {program}: " + ", ".join(
                f"{name} {s:.4f} s ({found['adopted'].get(name, 0.0):.4f} "
                f"adopted)" for name, s in sorted(found["seconds"].items())))
    print(f"  idle: {json.dumps(idle_by_phase(trace))}")
    if len(argv) > 1:
        start, length = (float(v) for v in (argv[2:4] + ["0.5", "0.12"][
            len(argv[2:4]):]))
        with open(argv[1], "w") as f:
            json.dump(cut(trace, start, length), f, separators=(",", ":"))
        print(f"wrote {argv[1]}: {os.path.getsize(argv[1]) / 1e3:.0f} kB")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
