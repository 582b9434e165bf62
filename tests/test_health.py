# Fleet health plane tests (ISSUE 11): the series store's windowed
# semantics, SLO burn-rate rules, the HealthAggregator's snapshot
# round-trip and alert lifecycle, the flight recorder's merged
# Perfetto dump (one trace id across >= 2 runtimes), the decode-round
# phase profiler's attribution, the metrics_dump scraper, and the
# lint-metric-label graft-check rule.

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "scripts"))

from aiko_services_tpu.observe import (
    DumpOnAlert, FlightRecorder, HealthAggregator, HistogramSeries,
    MetricsPublisher, PhaseProfiler, ScalarSeries, SeriesStore, SLORule,
    default_registry, parse_selector, tracing)
from aiko_services_tpu.observe import flight
from aiko_services_tpu.event import settle_virtual
from aiko_services_tpu.pipeline import (
    Frame, FrameOutput, Pipeline, PipelineElement,
    parse_pipeline_definition)
from aiko_services_tpu.registrar import Registrar
from aiko_services_tpu.share import ServicesCache


# ---------------------------------------------------------------------------
# selector grammar + ring semantics
# ---------------------------------------------------------------------------

class TestSelectors:
    def test_bare_family(self):
        assert parse_selector("hop_seconds") == ("hop_seconds", {}, None)

    def test_labels_and_quantile(self):
        name, labels, quantile = parse_selector(
            "pipeline_hop_seconds{pipeline=chaos_call,kind=x}:p95")
        assert name == "pipeline_hop_seconds"
        assert labels == {"pipeline": "chaos_call", "kind": "x"}
        assert quantile == pytest.approx(0.95)

    def test_quantile_only(self):
        assert parse_selector("h:p50")[2] == pytest.approx(0.5)


class TestScalarSeries:
    def test_latest_respects_window(self):
        ring = ScalarSeries("g", {}, "gauge")
        ring.append(0.0, 5.0)
        assert ring.latest(10.0, 30.0) == 5.0
        assert ring.latest(100.0, 30.0) is None     # aged out

    def test_single_sample_is_baseline_not_delta(self):
        ring = ScalarSeries("c", {}, "counter")
        ring.append(0.0, 1000.0)    # cumulative contamination
        assert ring.delta(1.0, 30.0) == 0.0
        ring.append(1.0, 1015.0)
        assert ring.delta(2.0, 30.0) == 15.0

    def test_trend_slope(self):
        ring = ScalarSeries("g", {}, "gauge")
        for t in range(5):
            ring.append(float(t), 10.0 * t)
        assert ring.trend(5.0, 30.0) == pytest.approx(10.0)
        assert ring.maximum(5.0, 30.0) == 40.0


class TestHistogramSeries:
    def make(self):
        ring = HistogramSeries("h", {}, bounds=(0.1, 1.0, 4.0))
        return ring

    def test_windowed_delta_quantile(self):
        ring = self.make()
        # contaminated cumulative start: 100 old fast observations
        ring.append(0.0, (100, 0, 0, 0))
        # this window's activity: 3 slow observations
        ring.append(1.0, (100, 0, 3, 0))
        assert ring.delta_quantile(0.95, 2.0, 30.0) == 4.0
        # the cumulative history alone (single sample) is NO evidence
        fresh = self.make()
        fresh.append(0.0, (100, 0, 0, 0))
        assert fresh.delta_quantile(0.95, 1.0, 30.0) is None
        # ... unless the reader opts into baseline_empty (autoscaler)
        assert fresh.delta_quantile(0.95, 1.0, 30.0,
                                    baseline_empty=True) == 0.1


class TestSeriesStore:
    def test_birth_seeding_counts_first_burst(self):
        """A counter series appearing MID-FLIGHT from a known source
        was provably zero at the source's previous snapshot — its
        birth value is a delta, not a baseline (without this, lazily
        created counters lose their entire first window of events)."""
        store = SeriesStore(window=30.0)
        store.append_snapshot("p1", {
            "other": {"type": "gauge",
                      "series": [{"labels": {}, "value": 1}]}}, t=0.0)
        store.append_snapshot("p1", {
            "shed_total": {"type": "counter",
                           "series": [{"labels": {}, "value": 15}]}},
            t=0.5)
        assert store.selector_delta("shed_total", 1.0, 30.0) == 15.0

    def test_first_snapshot_is_pure_baseline(self):
        """A source's FIRST-EVER snapshot may carry cumulative counts
        from before this store existed — no deltas from it."""
        store = SeriesStore(window=30.0)
        store.append_snapshot("p1", {
            "shed_total": {"type": "counter",
                           "series": [{"labels": {}, "value": 999}]}},
            t=0.0)
        assert store.selector_delta("shed_total", 1.0, 30.0) == 0.0

    def test_type_flip_replaces_ring_instead_of_crashing(self):
        """A publisher re-shipping a family under the OTHER metric
        type (upgrade reusing the retained topic_path) must not wedge
        the intake — the stale-kind ring is replaced."""
        store = SeriesStore(window=30.0)
        store.append_snapshot("p1", {
            "f": {"type": "histogram", "series": [{
                "labels": {}, "bounds": [1.0], "counts": [2, 0],
                "sum": 0.5, "count": 2}]}}, t=0.0)
        store.append_snapshot("p1", {
            "f": {"type": "gauge",
                  "series": [{"labels": {}, "value": 5.0}]}}, t=1.0)
        (_, ring), = store.rings("f")
        assert isinstance(ring, ScalarSeries)
        assert ring.latest(2.0, 30.0) == 5.0
        # and back the other way
        store.append_snapshot("p1", {
            "f": {"type": "histogram", "series": [{
                "labels": {}, "bounds": [1.0], "counts": [3, 0],
                "sum": 0.5, "count": 3}]}}, t=2.0)
        (_, ring), = store.rings("f")
        assert isinstance(ring, HistogramSeries)

    def test_prune_drops_silent_sources(self):
        store = SeriesStore(window=5.0)
        store.append_scalar("dead", "g", {}, 0.0, 1.0)
        store.append_scalar("live", "g", {}, 20.0, 2.0)
        dropped = store.prune(now=21.0)
        assert dropped == 1
        assert store.sources() == ["live"]

    def test_max_series_bound(self):
        store = SeriesStore(window=5.0, max_series=2)
        for index in range(5):
            store.append_scalar("p", "g", {"i": str(index)}, 0.0, 1.0)
        assert len(store) == 2


# ---------------------------------------------------------------------------
# SLO rules
# ---------------------------------------------------------------------------

def _feed_ratio(store, t, bad, good):
    store.append_snapshot("p1", {
        "bad_total": {"type": "counter",
                      "series": [{"labels": {}, "value": bad}]},
        "good_total": {"type": "counter",
                       "series": [{"labels": {}, "value": good}]},
    }, t=t)


class TestSLORules:
    def rule(self, **kwargs):
        defaults = dict(name="r", kind="ratio", bad="bad_total",
                        good="good_total", objective=0.99,
                        pairs=((30.0, 5.0, 2.0),))
        defaults.update(kwargs)
        return SLORule(**defaults)

    def test_multi_window_requires_both(self):
        store = SeriesStore(window=60.0)
        _feed_ratio(store, 0.0, 0, 0)
        _feed_ratio(store, 1.0, 10, 10)   # the burst
        rule = self.rule()
        # short + long both burning right after the burst
        assert rule.evaluate(store, 2.0)["breaching"]
        # keep reporting flat counters: the SHORT window dries up, the
        # long still remembers — multi-window stays quiet
        for t in (3.0, 5.0, 7.0, 9.0, 11.0):
            _feed_ratio(store, t, 10, 10)
        verdict = rule.evaluate(store, 11.0)
        assert not verdict["breaching"]
        window = verdict["windows"][0]
        assert window["burn_long"] >= 2.0       # long alone still hot
        assert window["burn_short"] == 0.0

    def test_no_events_no_burn(self):
        store = SeriesStore(window=60.0)
        _feed_ratio(store, 0.0, 0, 0)
        _feed_ratio(store, 1.0, 0, 0)
        assert not self.rule().evaluate(store, 2.0)["breaching"]

    def test_level_rule_histogram_quantile(self):
        store = SeriesStore(window=60.0)
        for t, counts in ((0.0, (5, 0, 0, 0)), (1.0, (5, 0, 2, 0))):
            store.append_snapshot("p1", {
                "lat": {"type": "histogram", "series": [{
                    "labels": {}, "bounds": [0.1, 1.0, 4.0],
                    "counts": list(counts), "sum": 0.0,
                    "count": sum(counts)}]}}, t=t)
        rule = SLORule(name="lat", kind="level", series="lat:p95",
                       threshold=2.0, window=30.0)
        assert rule.evaluate(store, 2.0)["breaching"]

    def test_validation(self):
        with pytest.raises(ValueError):
            SLORule(name="x", kind="nope")
        with pytest.raises(ValueError):
            SLORule(name="x", kind="ratio", bad="b")
        with pytest.raises(ValueError):
            SLORule(name="x", kind="level")


# ---------------------------------------------------------------------------
# HealthAggregator: snapshot round-trip + alert lifecycle
# ---------------------------------------------------------------------------

class TestHealthAggregator:
    def test_publisher_snapshot_roundtrip_into_store(self, make_runtime,
                                                     engine):
        """The ISSUE 11 schema round-trip: registry -> MetricsPublisher
        retained JSON -> HealthAggregator parse -> series append, for
        all three metric kinds, values intact."""
        registry = default_registry()
        publisher_rt = make_runtime("rt_pub").initialize()
        aggregator_rt = make_runtime("rt_agg").initialize()
        counter = registry.counter("rt_events_total",
                                   labels={"kind": "x"})
        gauge = registry.gauge("rt_depth")
        histogram = registry.histogram("rt_seconds",
                                       buckets=(0.1, 1.0, 4.0))
        counter.inc(7)
        gauge.set(3)
        histogram.observe(2.0)
        publisher = MetricsPublisher(publisher_rt, interval=0.5)
        aggregator = HealthAggregator(aggregator_rt, interval=0.5)
        settle_virtual(engine, 2.0)

        source = publisher_rt.topic_path
        assert source in aggregator.store.sources()
        (ring_source, counter_ring), = aggregator.store.rings(
            "rt_events_total", {"kind": "x"})
        assert ring_source == source
        assert counter_ring.points[-1][1] == 7
        (_, gauge_ring), = aggregator.store.rings("rt_depth")
        assert gauge_ring.latest(engine.clock.now(), 30.0) == 3
        (_, histogram_ring), = aggregator.store.rings("rt_seconds")
        assert histogram_ring.bounds == (0.1, 1.0, 4.0)
        # one more increment -> the windowed delta sees exactly it
        counter.inc(5)
        histogram.observe(0.05)
        settle_virtual(engine, 1.0)
        now = engine.clock.now()
        assert aggregator.store.selector_delta(
            "rt_events_total{kind=x}", now, 2.0) == 5.0
        aggregator.stop()
        publisher.stop()

    def test_alert_fires_resolves_and_publishes_retained(
            self, make_runtime, engine):
        registry = default_registry()
        publisher_rt = make_runtime("rt_pub2").initialize()
        aggregator_rt = make_runtime("rt_agg2").initialize()
        watcher_rt = make_runtime("rt_watch").initialize()
        bad = registry.counter("alert_bad_total")
        good = registry.counter("alert_good_total")
        good.inc()      # series exist before the aggregator starts
        bad.inc(0)
        publisher = MetricsPublisher(publisher_rt, interval=0.5)
        rule = SLORule(name="bad-burn", kind="ratio",
                       bad="alert_bad_total", good="alert_good_total",
                       objective=0.9, pairs=((8.0, 2.0, 1.0),))
        aggregator = HealthAggregator(aggregator_rt, rules=[rule],
                                      interval=0.5)
        fired = []
        aggregator.on_alert.append(lambda r, rec: fired.append(rec))
        retained = []
        watcher_rt.add_message_handler(
            lambda topic, payload: retained.append((topic, payload)),
            f"{watcher_rt.namespace}/alert/bad-burn")
        settle_virtual(engine, 2.0)
        assert aggregator.firing() == []

        bad.inc(50)
        good.inc(5)
        settle_virtual(engine, 2.0)
        assert aggregator.firing() == ["bad-burn"]
        assert len(fired) == 1                  # edge-triggered
        assert aggregator.fired["bad-burn"] == 1
        topic, payload = retained[-1]
        record = json.loads(payload)
        assert record["rule"] == "bad-burn"
        assert record["state"] == "firing"
        assert record["detail"]["windows"][0]["burn_short"] > 1.0

        # burn dries up in both windows -> resolved, published too
        settle_virtual(engine, 12.0)
        assert aggregator.firing() == []
        record = json.loads(retained[-1][1])
        assert record["state"] == "resolved"
        aggregator.stop()
        publisher.stop()

    def test_dashboard_metrics_pane_leads_with_firing_alerts(
            self, make_runtime, engine):
        from aiko_services_tpu.dashboard import DashboardState
        dashboard_rt = make_runtime("dash_alert").initialize()
        emitter_rt = make_runtime("dash_emit").initialize()
        state = DashboardState(dashboard_rt)
        emitter_rt.publish(
            f"{emitter_rt.namespace}/alert/hop-burn",
            json.dumps({"rule": "hop-burn", "state": "firing",
                        "since": 2.0, "description": "hops burning"}),
            retain=True)
        emitter_rt.publish(
            f"{emitter_rt.namespace}/alert/quiet-rule",
            json.dumps({"rule": "quiet-rule", "state": "resolved",
                        "time": 3.0}), retain=True)
        settle_virtual(engine, 0.5)
        lines = state.alert_lines()
        assert len(lines) == 1
        assert "ALERT hop-burn firing" in lines[0]
        assert "hops burning" in lines[0]
        state.terminate()

    def test_recorder_tails_alert_records(self, make_runtime, engine):
        from aiko_services_tpu.recorder import Recorder
        recorder_rt = make_runtime("rt_rec").initialize()
        emitter_rt = make_runtime("rt_emit").initialize()
        recorder = Recorder(recorder_rt)
        settle_virtual(engine, 0.5)
        emitter_rt.publish(
            f"{emitter_rt.namespace}/alert/my-rule",
            json.dumps({"rule": "my-rule", "state": "firing",
                        "time": 1.0}), retain=True)
        settle_virtual(engine, 0.5)
        assert recorder.alert_records()["my-rule"]["state"] == "firing"
        assert recorder.ec_producer.get("alerts_firing") in (1, "1")
        recorder.stop()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def element(name, inputs=(), outputs=(), deploy=None):
    return {"name": name,
            "input": [{"name": n} for n in inputs],
            "output": [{"name": n} for n in outputs],
            "deploy": deploy or {}}


class PE_FlightSource(PipelineElement):
    def process_frame(self, frame: Frame, **_) -> FrameOutput:
        return FrameOutput(True, {"value": 3})


class PE_FlightDouble(PipelineElement):
    def process_frame(self, frame: Frame, value=0, **_) -> FrameOutput:
        return FrameOutput(True, {"doubled": 2 * int(value)})


@pytest.fixture
def enabled_tracer():
    tracer = tracing.tracer
    was_enabled = tracer.enabled
    tracer.enable()
    tracer.clear()
    yield tracer
    tracer.clear()
    if not was_enabled:
        tracer.disable()


@pytest.fixture(autouse=True)
def _clean_flight_registry():
    yield
    for recorder in flight.recorders():
        flight.unregister(recorder)


class TestFlightRecorder:
    def test_dump_correlates_one_trace_across_two_runtimes(
            self, make_runtime, engine, enabled_tracer, tmp_path):
        """The ISSUE 11 correlation acceptance at unit scale: one
        remote frame, two runtimes, two flight recorders -> the merged
        timeline holds the caller's hop spans and the serving process
        span under ONE trace id, on different pids."""
        reg_rt = make_runtime("reg").initialize()
        Registrar(reg_rt)
        settle_virtual(engine, 2.5)
        serve_rt = make_runtime("serve").initialize()
        serving = Pipeline(
            serve_rt, parse_pipeline_definition({
                "version": 0, "name": "serve_flight",
                "runtime": "python", "graph": ["(PE_FlightDouble)"],
                "elements": [element("PE_FlightDouble", ["value"],
                                     ["doubled"])]}),
            element_classes={"PE_FlightDouble": PE_FlightDouble},
            auto_create_streams=True, stream_lease_time=0)
        call_rt = make_runtime("call").initialize()
        caller = Pipeline(
            call_rt, parse_pipeline_definition({
                "version": 0, "name": "call_flight",
                "runtime": "python",
                "graph": ["(PE_FlightSource (remote_double))"],
                "elements": [
                    element("PE_FlightSource", [], ["value"]),
                    element("remote_double", ["value"], ["doubled"],
                            deploy={"remote": {"service_filter":
                                    {"name": "serve_flight"}}})]}),
            element_classes={"PE_FlightSource": PE_FlightSource},
            services_cache=ServicesCache(call_rt),
            stream_lease_time=0, frame_deadline=30.0)
        settle_virtual(engine, 2.0)
        assert caller.remote_elements_ready()

        call_recorder = FlightRecorder(call_rt, sample_interval=0.5)
        serve_recorder = FlightRecorder(serve_rt, sample_interval=0.5)
        done = []
        caller.add_frame_handler(done.append)
        caller.create_stream("s1", lease_time=0)
        caller.post("process_frame", "s1", {})
        settle_virtual(engine, 2.0)
        assert done and int(done[0].swag["doubled"]) == 6
        trace_id = done[0].trace.trace_id

        pathname = flight.dump(tmp_path / "corr.json", reason="test")
        with open(pathname) as f:
            document = json.load(f)
        events = document["traceEvents"]
        pid_names = {e["pid"]: e["args"]["name"] for e in events
                     if e.get("ph") == "M"}
        ours = [e for e in events if e.get("ph") == "X"
                and e["args"].get("trace_id") == trace_id]
        procs = {pid_names[e["pid"]] for e in ours}
        assert {"call", "serve"} <= procs
        # metric samples rode along (sample timers ticked)
        assert any(e.get("ph") == "C" for e in events)
        caller.stop()
        serving.stop()
        call_recorder.close()
        serve_recorder.close()

    def test_fault_hook_and_dump_once_latch(self, tmp_path, engine):
        from aiko_services_tpu.transport.chaos import FaultPlan
        recorder = FlightRecorder(name="bare")
        plan = FaultPlan(seed=3)
        plan.drop(topic="t/#", probability=1.0, count=2)
        for _ in range(3):
            plan.decide("t/x", "a", "b", b"payload", 0.0)
        assert len(recorder.faults) == 2
        assert recorder.faults[0][1] == "drop"

        trigger = DumpOnAlert(str(tmp_path))
        rule = SLORule(name="r1", kind="level", series="s",
                       threshold=1.0)
        first = trigger(rule, {"state": "firing"})
        second = trigger(rule, {"state": "firing"})
        assert first is not None and second is None
        assert len(list(tmp_path.glob("*.json"))) == 1
        recorder.close()

    def test_rpc_dump(self, make_runtime, engine, tmp_path):
        runtime = make_runtime("rpc_rt").initialize()
        recorder = FlightRecorder(runtime)
        recorder.record_sample(0.0, "x", 1)
        replies = []
        runtime.add_message_handler(
            lambda topic, payload: replies.append(payload),
            f"{runtime.topic_path}/0/flight/out")
        target = tmp_path / "rpc.json"
        runtime.publish(f"{runtime.topic_path}/0/flight",
                        f"(dump {target})")
        settle_virtual(engine, 0.5)
        # the dump itself runs on a real-time worker thread (the RPC
        # handler must not block the event loop on file I/O) — join it,
        # then settle again so the queued reply drains through the loop
        assert recorder._dump_worker is not None
        recorder._dump_worker.join(timeout=10.0)
        settle_virtual(engine, 0.5)
        assert target.exists()
        assert replies and "dumped" in str(replies[0])
        recorder.close()

    def test_span_ownership_routing(self, make_runtime, engine,
                                    enabled_tracer):
        rt_a = make_runtime("owner_a").initialize()
        rt_b = make_runtime("owner_b").initialize()
        recorder_a = FlightRecorder(rt_a)
        recorder_b = FlightRecorder(rt_b)
        enabled_tracer.record("spanA", 0.0, 0.1, proc="owner_a")
        enabled_tracer.record("spanB", 0.0, 0.1, proc="owner_b")
        enabled_tracer.record("orphan", 0.0, 0.1, proc="nobody")
        names_a = {s.name for s in recorder_a.spans}
        names_b = {s.name for s in recorder_b.spans}
        assert "spanA" in names_a and "spanA" not in names_b
        assert "spanB" in names_b and "spanB" not in names_a
        # unclaimed spans land in the first-registered recorder
        assert "orphan" in names_a
        recorder_a.close()
        recorder_b.close()


# ---------------------------------------------------------------------------
# phase profiler
# ---------------------------------------------------------------------------

class TestPhaseProfiler:
    def test_mark_commit_attribution(self):
        profiler = PhaseProfiler("unit")
        profiler.begin_round()
        profiler.enter("host_sync")
        profiler.commit_round()
        stats = profiler.phase_stats()
        assert stats["rounds"] == 1
        assert "plan" in stats["phases"]
        assert "host_sync" in stats["phases"]
        total = sum(e["s"] for e in stats["phases"].values())
        assert total == pytest.approx(stats["wall_s"], rel=1e-6)

    def test_abandoned_rounds_do_not_dilute(self):
        profiler = PhaseProfiler("unit2")
        profiler.begin_round()
        profiler.enter("host_sync")
        profiler.abandon_round()
        assert profiler.rounds == 0
        assert profiler.phase_stats()["wall_s"] == 0.0

    def test_registry_counters_accumulate(self):
        registry = default_registry()
        profiler = PhaseProfiler("unit3")
        before = registry.value("serving_phase_seconds_total",
                                {"decoder": "unit3", "phase": "plan"})
        profiler.begin_round()
        profiler.commit_round()
        after = registry.value("serving_phase_seconds_total",
                               {"decoder": "unit3", "phase": "plan"})
        assert after > before

    def test_decoder_smoke_attributes_90_percent(self):
        """The acceptance number on the CPU llama smoke: >= 90% of
        measured decode-round wall time lands in NAMED phases."""
        import jax
        from aiko_services_tpu.models.llama import (LLAMA_PRESETS,
                                                    llama_init)
        from aiko_services_tpu.serving import ContinuousDecoder
        config = dataclasses.replace(LLAMA_PRESETS["tiny"],
                                     max_seq_len=96)
        params = llama_init(jax.random.PRNGKey(0), config)
        decoder = ContinuousDecoder(params, config, max_slots=4,
                                    prefill_buckets=(16,),
                                    steps_per_sync=4, name="smoke")
        done = {}
        rng = np.random.default_rng(7)
        for index in range(6):
            prompt = [int(x) for x in
                      rng.integers(1, config.vocab, size=5)]
            decoder.submit(f"r{index}", prompt, 8,
                           lambda rid, t: done.update({rid: t}))
        for _ in range(60):
            decoder.pump()
            if len(done) == 6:
                break
        assert len(done) == 6
        stats = decoder.profiler.phase_stats()
        assert stats["rounds"] >= 2
        assert stats["attributed_frac"] >= 0.9, stats
        # the load-bearing phases all appear
        for phase in ("plan", "scan_dispatch", "admit_dispatch",
                      "host_sync", "deliver"):
            assert phase in stats["phases"], stats["phases"].keys()


# ---------------------------------------------------------------------------
# metrics_dump scraper
# ---------------------------------------------------------------------------

class TestMetricsDump:
    def test_collect_and_render(self, make_runtime, engine):
        from metrics_dump import collect_snapshots, render
        registry = default_registry()
        registry.counter("dump_events_total",
                         labels={"kind": "t"}).inc(4)
        publisher_rt = make_runtime("dump_pub").initialize()
        publisher = MetricsPublisher(publisher_rt, interval=0.5)
        settle_virtual(engine, 1.0)

        scraper_rt = make_runtime("dump_scraper").initialize()
        documents = collect_snapshots(
            scraper_rt, wait=1.0,
            settle=lambda eng, wait: settle_virtual(eng, wait))
        assert publisher_rt.topic_path in documents

        text = render(documents, "prom", family="dump_events")
        assert "# TYPE dump_events_total counter" in text
        assert f'process="{publisher_rt.topic_path}"' in text
        assert 'kind="t"' in text

        blob = json.loads(render(documents, "json",
                                 family="dump_events"))
        snapshot = blob[publisher_rt.topic_path]["snapshot"]
        assert list(snapshot.keys()) == ["dump_events_total"]
        publisher.stop()


# ---------------------------------------------------------------------------
# lint-metric-label
# ---------------------------------------------------------------------------

class TestLintMetricLabel:
    def lint(self, source):
        from aiko_services_tpu.analysis.lint import lint_source
        return [f for f in lint_source(source, "pkg/mod.py")
                if f.rule == "lint-metric-label"]

    def test_topic_path_value_flagged(self):
        findings = self.lint(
            "registry.counter('x_total', 'help',\n"
            "                 labels={'src': self.topic_path})\n")
        assert len(findings) == 1

    def test_session_id_fstring_flagged(self):
        findings = self.lint(
            "registry.gauge('y', labels={'k': f'{session_id}'})\n")
        assert len(findings) == 1

    def test_suspicious_key_with_dynamic_value_flagged(self):
        findings = self.lint(
            "registry.counter('z_total', labels={'topic': value})\n")
        assert len(findings) == 1

    def test_bounded_labels_pass(self):
        findings = self.lint(
            "registry.counter('a_total', 'help',\n"
            "                 labels={'tenant': tenant,\n"
            "                         'kind': 'x',\n"
            "                         'pipeline': self.name})\n")
        assert findings == []

    def test_waiver_suppresses(self):
        findings = self.lint(
            "registry.counter(  # graft: disable=lint-metric-label\n"
            "    'x_total', labels={'src': self.topic_path})\n")
        assert findings == []

    def test_rule_registered(self):
        from aiko_services_tpu.analysis.lint import LINT_RULES
        assert "lint-metric-label" in LINT_RULES
