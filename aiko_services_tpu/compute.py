# ComputeRuntime: the TPU execution backend service.
#
# This is the north-star component (BASELINE.json): the piece that hosts
# compiled jax programs behind the control plane.  The reference has no
# equivalent — its elements call CUDA models inline on the event loop
# (reference: examples/speech/speech_elements.py:217-250), serializing
# every tensor through MQTT.  Here:
#   * a ComputeRuntime owns the device mesh and a table of compiled
#     functions ("programs"), placed with logical-axis shardings;
#   * pipeline elements submit work through a BatchingScheduler — frames
#     from many streams coalesce into MXU-sized batches with a bounded
#     wait (<150 ms p50 target);
#   * it is a Service: its mesh geometry, program table, and batch stats
#     are EC-shared, so dashboards and lifecycle managers see device
#     health (SURVEY.md §7 "two-plane consistency").

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Any, Callable

from .ops.batching import BatchingScheduler, ShapeBuckets
from .service import ServiceProtocol
from .actor import Actor
from .utils import get_logger

__all__ = ["ComputeRuntime", "CompiledProgram", "PROTOCOL_COMPUTE",
           "resolve_pipelined", "enable_compile_cache"]

# the persistent compile cache's home when the environment names none:
# fixed inside the checkout, because the directory is part of every
# cache key — a path that moves between runs never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache before the first
    compile; returns the directory in use.  JAX_COMPILATION_CACHE_DIR,
    when set, is honoured as jax itself reads it and no directory is
    set in code (so whoever runs the program can place the cache where
    it survives); otherwise the cache lives at COMPILE_CACHE_DIR.
    Entry points call this (chip_smoke.py, `aiko_tpu pipeline
    create`); the test suite does not."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def resolve_pipelined(pipelined, mode: str) -> bool:
    """Pipelined results complete on a LATER event-loop turn; a sync
    caller blocking on scheduler.drain(force=True) would hang forever.
    Every element that exposes both knobs must route them through here."""
    return bool(pipelined) and mode != "sync"

PROTOCOL_COMPUTE = ServiceProtocol("compute")


@dataclass
class CompiledProgram:
    name: str
    fn: Callable                  # jitted: fn(batch_payload) -> results
    buckets: ShapeBuckets | None
    scheduler: BatchingScheduler | None
    first_call_times: dict       # bucket -> first-call wall seconds
                                 # (compile + first execution, honestly
                                 # named: the two are not separable here)
    in_flight: dict | None = None   # pipelined: {"now": N, "peak": N}
    recent_service: Any = None   # deque[(bucket, seconds)] of recent
                                 # batch service times (post-compile)


class ComputeRuntime(Actor):
    """Owns the mesh; hosts compiled programs; schedules batches.

    mesh=None → single-device.  Programs are registered with a collate
    function (list of payloads → batch arrays) and a split function
    (batch results → per-item results); the runtime wires them to a
    BatchingScheduler driven off the EventEngine.
    """

    def __init__(self, runtime, name: str = "compute", mesh=None,
                 drive_period: float = 0.005):
        share = {"device_count": 0, "program_count": 0}
        super().__init__(runtime, name, PROTOCOL_COMPUTE, share=share)
        self.logger = get_logger(f"compute.{name}")
        self._mesh = mesh
        self.drive_period = drive_period
        self.programs: dict[str, CompiledProgram] = {}
        self._timers: list[int] = []
        # pipelined results: worker thread syncs device results (GIL
        # released during transfer) and deliveries cross back onto the
        # event loop through this queue
        self._results_queue = f"compute.results.{name}"
        self._worker = None
        self._worker_queue = None
        runtime.event.add_queue_handler(self._deliver_results,
                                        self._results_queue)
        import jax
        self._devices = list(mesh.devices.flat) if mesh is not None \
            else jax.devices()[:1]
        self.ec_producer.update("device_count", len(self._devices))
        self.ec_producer.update(
            "mesh", dict(mesh.shape) if mesh is not None else {})
        self.ec_producer.update("platform", self._devices[0].platform)
        self.ec_producer.update("device_kind", self._devices[0].device_kind)
        self.refresh_device_health()
        # keep device health LIVE: dashboards must see HBM pressure
        # building, not a boot-time snapshot
        self._timers.append(runtime.event.add_timer_handler(
            self.refresh_device_health, period=10.0))

    def refresh_device_health(self) -> None:
        """Publish per-device memory occupancy into the EC share so
        lifecycle managers / dashboards watch device health live
        (SURVEY.md §7 two-plane consistency).  TPU backends report
        bytes_in_use/bytes_limit; backends without memory_stats (CPU)
        just report presence."""
        for device in self._devices:
            stats = {}
            try:
                stats = device.memory_stats() or {}
            except Exception:
                pass
            in_use = stats.get("bytes_in_use")
            limit = stats.get("bytes_limit")
            value = round(100.0 * in_use / limit, 1) \
                if in_use is not None and limit else -1
            key = f"device.{device.id}.mem_pct"
            # dedup: EC updates fan out to every leaseholder — no-op
            # republishes every 10 s would spam each consumer forever
            if self.ec_producer.get(key) != value:
                self.ec_producer.update(key, value)

    @property
    def mesh(self):
        if self._mesh is None:
            from .parallel import single_device_mesh
            self._mesh = single_device_mesh()
        return self._mesh

    # -- direct (unbatched) programs ---------------------------------------
    def register_program(self, name: str, fn, donate_argnums=()) -> None:
        """Register a jittable fn for direct invocation via run()."""
        import jax
        compiled = jax.jit(fn, donate_argnums=donate_argnums)
        self.programs[name] = CompiledProgram(name, compiled, None, None,
                                              {})
        self.ec_producer.update("program_count", len(self.programs))

    def run(self, name: str, *args):
        program = self.programs[name]
        start = time.perf_counter()
        result = program.fn(*args)
        program.first_call_times.setdefault("direct",
                                         time.perf_counter() - start)
        return result

    # -- batched programs ---------------------------------------------------
    def register_batched(self, name: str, fn, buckets,
                         collate, split, max_batch: int = 32,
                         max_wait: float = 0.05,
                         pipelined: bool = False,
                         max_in_flight: int = 4) -> BatchingScheduler:
        """Register a batched program.

        fn(bucket, batch_arrays) -> batch_results (jit-compiled per
        bucket by the caller or internally static);
        collate(bucket, payloads) -> batch_arrays;
        split(batch_results, count) -> list of per-item results.

        pipelined=True moves split() — where the blocking device sync
        lives — onto a worker thread and delivers callbacks through the
        event queue: batch N+1's collate/upload overlaps batch N's device
        compute.  Callbacks then fire on a later event-loop turn, so
        callers must drive the engine (drain(force=True) alone does not
        complete items).  max_in_flight bounds how many dispatched
        batches may be awaiting their device sync at once (≥2 for any
        overlap; deeper keeps uploads of rounds k+1..k+d covering round
        k's compute+sync on thin links at the cost of per-batch latency
        and device queue memory).  Returns the scheduler."""
        program_holder = {}
        in_flight = {"now": 0, "peak": 0}

        def process_batch(bucket, items):
            payloads = [item.payload for item in items]
            batch = collate(bucket, payloads)
            start = time.perf_counter()
            results = fn(bucket, batch)       # async dispatch under jit
            if pipelined:
                in_flight["now"] += 1
                in_flight["peak"] = max(in_flight["peak"],
                                        in_flight["now"])
                self._worker_submit(program_holder["program"], bucket,
                                    items, results, split, start)
                return None                   # ownership transferred
            program = program_holder["program"]
            per_item = split(results, len(items))    # device sync
            elapsed = time.perf_counter() - start
            if bucket not in program.first_call_times:
                # first call = compile + run; do NOT feed it to the
                # service estimator or deadline admission would fire
                # spuriously for the whole warm period
                program.first_call_times[bucket] = elapsed
                self.ec_producer.update(
                    f"first_call.{name}.{bucket}", round(elapsed, 3))
            else:
                scheduler.observe_service_time(bucket, elapsed)
                program.recent_service.append((bucket, elapsed))
            self._publish_stats(name, scheduler)
            return per_item

        if not isinstance(buckets, ShapeBuckets):
            buckets = ShapeBuckets(buckets)
        gate = (lambda: in_flight["now"] < int(max_in_flight)) \
            if pipelined else None
        scheduler = BatchingScheduler(process_batch, buckets,
                                      max_batch=max_batch,
                                      max_wait=max_wait,
                                      clock=self.runtime.event.clock.now,
                                      dispatch_gate=gate,
                                      metrics_labels={"program": name})
        from collections import deque
        program = CompiledProgram(name, fn, buckets, scheduler, {})
        program.in_flight = in_flight
        program.recent_service = deque(maxlen=512)
        program_holder["program"] = program
        self.programs[name] = program
        self._timers.append(scheduler.attach(self.runtime.event,
                                             self.drive_period))
        self.ec_producer.update("program_count", len(self.programs))
        return scheduler

    def submit(self, name: str, stream_id: str, payload, length: int,
               callback, deadline: float | None = None) -> None:
        program = self.programs[name]
        if program.scheduler is None:
            raise ValueError(f"program {name} is not batched")
        program.scheduler.submit(stream_id, payload, length, callback,
                                 deadline=deadline)

    # -- pipelined results path ---------------------------------------------
    def _worker_submit(self, program, bucket, items, results, split,
                       start) -> None:
        import queue as _queue
        import threading
        if self._worker is None:
            self._worker_queue = _queue.Queue()
            self._worker = threading.Thread(
                target=self._worker_loop, name=f"compute.{self.name}",
                daemon=True)
            self._worker.start()
        self._worker_queue.put((program, bucket, items, results, split,
                                start))

    def _worker_loop(self) -> None:
        while True:
            job = self._worker_queue.get()
            if job is None:
                return
            program, bucket, items, results, split, start = job
            try:
                per_item = split(results, len(items))   # blocks on device
                if len(per_item) != len(items):
                    raise RuntimeError(
                        f"split returned {len(per_item)} results for "
                        f"{len(items)} items")
            except Exception as exc:
                per_item = [exc] * len(items)
            elapsed = time.perf_counter() - start
            self.runtime.event.queue_put(
                self._results_queue,
                (program, bucket, items, per_item, elapsed))

    def _deliver_results(self, _queue_name, job, _put_time) -> None:
        program, bucket, items, per_item, elapsed = job
        if program.in_flight is not None:
            program.in_flight["now"] = max(
                0, program.in_flight["now"] - 1)
        if bucket not in program.first_call_times:
            # keyed by the program's fixed bucket ladder — bounded:
            # graft: disable=lint-unbounded-cache
            program.first_call_times[bucket] = elapsed
            self.ec_producer.update(f"first_call.{program.name}.{bucket}",
                                    round(elapsed, 3))
        elif program.scheduler is not None:
            program.scheduler.observe_service_time(bucket, elapsed)
            if program.recent_service is not None:
                # audited: deque(maxlen=512)  # graft: disable=lint-unbounded-queue
                program.recent_service.append((bucket, elapsed))
        if program.scheduler is not None:
            self._publish_stats(program.name, program.scheduler)
        for item, result in zip(items, per_item):
            item.callback(item.stream_id, result)

    def _publish_stats(self, name: str, scheduler) -> None:
        self.ec_producer.update(f"batch.{name}.batches",
                                scheduler.stats["batches"])
        mean_size = round(scheduler.mean_batch_size(), 2)
        mean_wait_ms = round(scheduler.mean_wait() * 1000.0, 2)
        self.ec_producer.update(f"batch.{name}.mean_size", mean_size)
        self.ec_producer.update(f"batch.{name}.mean_wait_ms",
                                mean_wait_ms)
        # rolling levels beside the mirrored cumulative counters: the
        # dashboard metrics pane and a Prometheus scrape both see them
        from .observe.metrics import default_registry
        registry = default_registry()
        labels = {"program": name}
        registry.gauge("batch_mean_size",
                       "mean dispatched batch size", labels).set(mean_size)
        registry.gauge("batch_mean_wait_ms",
                       "mean batch-former queue wait",
                       labels).set(mean_wait_ms)

    # -- placement ----------------------------------------------------------
    def place_params(self, params, param_axes, rules=None):
        """Shard a parameter tree onto this runtime's mesh."""
        from .parallel import shard_pytree
        return shard_pytree(params, param_axes, self.mesh, rules)

    def stop(self) -> None:
        for timer in self._timers:
            self.runtime.event.remove_timer_handler(timer)
        for program in self.programs.values():
            if program.scheduler is not None:
                program.scheduler.drain(force=True)
        if self._worker is not None:
            self._worker_queue.put(None)
            self._worker.join(timeout=10.0)
            self._worker = None
        self.runtime.event.remove_queue_handler(self._results_queue)
        super().stop()
