#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, because one process owns the chip.  It builds the cell's
configuration, makes weights and inputs from --seed, warms up this cell's
shapes (all of that is the set-up time), measures for --seconds, checks a sample
of what the window served against the plain reference, and prints one
JSON object as the LAST line of its standard output.  Every name it uses
comes from BENCHMARK.json and the files that it points to: a new
configuration, traffic mix or metric is a new file, never an edit here
(see benchmark/README.md).

--rehearse (never passed by the driver) runs the same control flow at the
tiny presets each file carries, on whatever jax finds, for the tests; it
reports no device metric.  --control 1 also reads the lower-precision
control's gap (PERF.md, correctness); --lower-precision 1 serves float8
weights, which has to come out as not correct.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SECONDS = 4.0
DRAIN_SECONDS = 5.0


def say(message: str) -> None:
    print(f"[bench] {message}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(folder: str, name: str):
    """benchmark/<folder>/<name>.py, found by the name a data file gives
    (dots become `_`).  A metric `<family>.<suffix>` with no file of its
    own is read by its family's file, <family>.py."""
    family = name.rpartition(".")[0] or name
    paths = [os.path.join(HERE, folder, stem.replace(".", "_") + ".py")
             for stem in (name, family)]
    path = next((p for p in paths if os.path.exists(p)), None)
    if path is None:
        raise FileNotFoundError(f"{folder} {name!r}: no file {paths[0]}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def merged(base: dict, override: dict) -> dict:
    """`override` laid over `base`, dicts merged key by key."""
    out = dict(base)
    for key, value in override.items():
        out[key] = merged(out[key], value) \
            if isinstance(value, dict) and isinstance(out.get(key), dict) \
            else value
    return out


def resolve(workload: str, rehearse: bool) -> dict:
    """Everything the cell names, loaded: the cell, its configuration, its
    traffic and the metrics that it reports."""
    manifest = load_json("BENCHMARK.json")
    cells = {cell["name"]: cell for cell in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(entry["file"])
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    if rehearse:
        config = merged(config, config.get("rehearse", {}))
        traffic = merged(traffic, traffic.get("rehearse", {}))

    def reported(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in manifest["end_to_end"] if reported(m)],
            "per_layer": [m for m in manifest["per_layer"] if reported(m)]}


class CompileClock:
    """jax's own backend-compile durations and persistent-cache events
    (after chip_smoke.CompileClock): what compiled, and when."""

    def __init__(self):
        import jax.monitoring
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def device_report(jax) -> dict:
    """The device as jax reports it.  The peak is what the allocator had in
    use at most plus what it had reserved at most: on a TPU the programs'
    temporaries are reserved, and `peak_bytes_in_use` alone leaves them
    out (PERF.md, findings of PR 23)."""
    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def read_metrics(folder: str, metrics: list, run: dict,
                 rehearsal: bool = False) -> dict:
    """In a rehearsal only counts keep their value: a time, a rate or a
    share from a CPU run is not written under a metric's name."""
    out = {}
    for metric in metrics:
        value = load_module(folder, metric["name"]).read(run)
        if value is not None:
            keep = not rehearsal or metric["source"] == "program_counter"
            out[metric["name"]] = {"value": float(value) if keep else None,
                                   "unit": metric["unit"]}
    return out


def summary_lines(run: dict) -> list:
    """Medians, tails, lateness and the backlog's sign, for the log."""
    from benchmark import readers, stats
    records, lines = run["records"], []

    def halves(key, values) -> str:
        due = [r["due"] for r in records
               if r[key] is not None and not r["failed"]]
        try:
            return f"{stats.halves_ratio(due, values, run['seconds']):.2f}"
        except ValueError:
            return "not read (a half is empty)"

    for label, key in (("done", "done"), ("first token", "first")):
        values = [r[key] - r["due"] for r in records
                  if r[key] is not None and not r["failed"]]
        if len(values) >= 2 and run["traffic"]["parameters"].get(
                "max_outstanding") is None:
            lines.append(
                f"{label} - due: n={len(values)} p50="
                f"{stats.percentile(values, 50) * 1e3:.1f} ms p95="
                f"{stats.percentile(values, 95) * 1e3:.1f} ms; second half "
                f"over first {halves(key, values)}")
    gaps = [g for g in readers.per_token_seconds(records) if g is not None]
    if len(gaps) >= 2:
        lines.append(
            f"gap between tokens: over all tokens "
            f"{readers.mean_token_gap_ms(run):.3f} ms; per request mean "
            f"{sum(gaps) / len(gaps) * 1e3:.3f} p50 "
            f"{stats.percentile(gaps, 50) * 1e3:.3f} p90 "
            f"{stats.percentile(gaps, 90) * 1e3:.3f} p95 "
            f"{stats.percentile(gaps, 95) * 1e3:.3f} largest "
            f"{max(gaps) * 1e3:.3f} ms")
    # what users are promised, as a reading and never as a criterion
    for key, limits in run["traffic"].get("promised_ms", {}).items():
        values = readers.per_token_seconds(records) if key == "per_token" \
            else readers.latencies(records, "due", key)
        for limit in limits if values else ():
            lines.append(f"{key} within {limit} ms: "
                         f"{stats.share_within(values, limit / 1e3):.1%} of "
                         f"{len(values)} requests, failed ones counted")
    late = [r["sent"] - r["due"] for r in records if r["sent"] is not None]
    if late and run["traffic"]["parameters"].get("max_outstanding") is None:
        lines.append(f"generator lateness: p50="
                     f"{stats.percentile(late, 50) * 1e3:.2f} ms p95="
                     f"{stats.percentile(late, 95) * 1e3:.2f} ms")
    return lines


def main(argv=None, hook=None) -> int:
    """`hook(session)`, a test's seam, runs after set-up: it may break the
    timed path underneath to see `correct` come out false."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--set", action="append", default=[],
                        metavar="PATH=JSON", help="override a value of the "
                        "traffic file, for sweeps: parameters.rate_per_s=4")
    parser.add_argument("--control", type=int, choices=(0, 1), default=0)
    parser.add_argument("--lower-precision", type=int, choices=(0, 1),
                        default=0)
    args = parser.parse_args(argv)

    found = resolve(args.workload, args.rehearse)
    cell, config, traffic = found["cell"], found["config"], found["traffic"]
    for item in args.set:
        path, _, value = item.partition("=")
        *parents, leaf = path.split(".")
        target = traffic
        for key in parents:
            target = target[key]
        target[leaf] = json.loads(value)
        say(f"override: traffic {path} = {target[leaf]}")
    out_dir = os.path.join(ROOT, ".bench_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)

    import jax
    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"benchmark: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"jax found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 2
    peaks = load_json("benchmark", "peaks.json")["device_kinds"]
    if not args.rehearse and devices[0].device_kind not in peaks:
        print(f"benchmark: no peaks for device kind "
              f"{devices[0].device_kind!r} in benchmark/peaks.json",
              file=sys.stderr)
        return 2
    if not args.rehearse:
        # inside the checkout, at a fixed path, with no cap on its size:
        # the path is part of the cache's key, and a cap below the cell's
        # working set would evict every entry before its next use.  The
        # program takes the same directory where it reads the variable.
        cache_dir = os.path.join(ROOT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_compilation_cache_max_size", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()
    say(f"cell {args.workload} seed {args.seed} on {device_report(jax)} "
        f"jax {jax.__version__}" + (" REHEARSAL" if args.rehearse else ""))

    # -- set-up ---------------------------------------------------------------
    import numpy as np
    from benchmark.loop import Window
    from benchmark.trace import reduce as trace_reduce

    plan = load_module("generators", traffic["generator"]).generate(
        traffic["parameters"], args.seed, args.seconds)
    session = load_module("drivers", config["driver"]).Session(
        config, traffic, plan, args.seed, say,
        lower_precision=bool(args.lower_precision))
    session.warm_up()
    if hook is not None:
        hook(session)
    say(f"set-up compiled {clock.compiles} programs in {clock.seconds:.1f} s; "
        f"cache hits {clock.hits} misses {clock.misses}")

    trace_dir = os.path.join(out_dir, "trace")
    trace_counters, traced_span = {}, []

    def on_trace(begin: bool) -> None:
        if begin:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            traced_span.append(jax.profiler.TraceAnnotation(
                trace_reduce.SPAN_TRACED))
            traced_span[0].__enter__()
            trace_counters["before"] = session.counters()
        else:
            # the span ends with the window; the trace is written out
            # after the drain, so that no request waits on it
            trace_counters["after"] = session.counters()
            traced_span.pop().__exit__(None, None, None)

    trace_s = min(TRACE_SECONDS, args.seconds / 2) if args.trace else 0.0
    # how long after the window a request may still come back: the 5 s of
    # the issue, or what the traffic's longest request needs
    drain_s = float(traffic.get("drain_s", DRAIN_SECONDS))
    window = Window(plan, args.seconds, drain_s, trace_s, on_trace)

    # -- the window -------------------------------------------------------------
    gc.collect()
    gc.freeze()
    before, compiles_before = session.counters(), clock.compiles
    session.run(window)
    if args.trace:
        jax.profiler.stop_trace()
    set_up_seconds = window.origin - PROCESS_START
    compiles_in_window = clock.compiles - compiles_before
    after = session.counters()
    device = device_report(jax)
    say(f"memory at the close of the window: {devices[0].memory_stats()}")
    records = window.close()
    failed = sum(r["failed"] for r in records)
    say(f"window: {len(records)} requests counted, {failed} failed, "
        f"{window.tokens_in_window} tokens, set-up {set_up_seconds:.1f} s")

    run = {"records": records, "all_records": window.records,
           "requests": {r["id"]: r for r in plan["requests"]},
           "seconds": args.seconds, "miss_s": args.seconds + drain_s,
           "tokens_in_window": window.tokens_in_window,
           "set_up_seconds": set_up_seconds,
           "counters": {"before": before, "after": after},
           "trace_counters": trace_counters, "trace": None,
           "config": config, "traffic": traffic,
           "peaks": peaks.get(devices[0].device_kind)}
    if args.trace:
        run["trace"] = trace_reduce.reduce_directory(
            trace_dir, config.get("trace", {}).get("idle_labels"))
        with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
            json.dump(run["trace"], f, indent=1)

    # the newest run's stamps, request by request, for a look by hand
    with open(os.path.join(out_dir, "records.json"), "w") as f:
        json.dump(window.records, f, indent=1)
    for line in summary_lines(run):
        say(("rehearsal on the CPU, not a measurement: " if args.rehearse
             else "") + line)
    metrics = read_metrics(
        "layer_metrics" if args.trace else "end_to_end",
        found["per_layer"] if args.trace else found["end_to_end"], run,
        rehearsal=args.rehearse)
    for name, metric in metrics.items():
        if metric["value"] is not None:
            say(f"metric {name} = {metric['value']:.6g} {metric['unit']}")

    # -- correct ----------------------------------------------------------------
    rng = np.random.default_rng([int(args.seed), 13])
    samples = session.samples(window.records, int(traffic["reference_samples"]),
                              rng)
    sizes = session.reference_sizes()
    session.close()
    del session
    gc.collect()
    started = time.perf_counter()
    checked = load_module("reference", config["reference"]).check(
        samples, sizes, args.seed, config["dtype"],
        control=bool(args.control), say=say) if samples else None
    limits = config["correctness"]
    checks = [("requests_checked", len(samples), 1, len(samples) >= 1),
              ("compiles_in_window", compiles_in_window, 0,
               compiles_in_window == 0),
              ("backlog_ran_dry", int(window.exhausted), 0,
               not window.exhausted)]
    for name, limit in limits["limits"].items():
        values = checked["numbers"][name] if checked else [float("nan")]
        widest = max(values)
        checks.append((name + "_widest", widest, limit,
                       all(v == v for v in values) and widest <= limit))
        if checked and args.control:
            say(f"control (float8 weights in the reference) {name}: "
                f"{checked['control'][name]}; the sound path's {values}")
    for name, metric in metrics.items():
        if "roofline" in name and metric["value"] is not None:
            checks.append((name, metric["value"], 100.0,
                           metric["value"] <= 100.0))
    for name, value, limit, ok in checks:
        say(f"check {name} = {value} limit {limit} "
            f"{'ok' if ok else 'NOT CORRECT'}")
    say(f"reference took {time.perf_counter() - started:.1f} s over "
        f"{checked['positions'] if checked else 0} served tokens")
    correct = all(ok for *_, ok in checks)

    result = {"correct": bool(correct), "attempted": len(records),
              "failed": int(failed), "metrics": metrics, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    if args.trace and run["trace"]:
        if not args.rehearse:
            result["device"] |= {"busy_s": run["trace"]["busy_s"],
                                 "window_s": run["trace"]["window_s"]}
        result["breakdown"] = {
            "device_ops": [[name[:120], seconds] for name, seconds
                           in run["trace"]["device_ops"][:10]],
            "idle_gaps": run["trace"]["idle_gaps"][:10]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
