# Request journey plane tests (ISSUE 12): mergeable quantile sketch
# properties (relative-error bound, merge laws, snapshot roundtrip,
# cross-source window merge), per-request journey records through a
# real ContinuousDecoder, publisher interval jitter, the
# lint-wall-clock graft-check rule, the per-tenant SLO report, and the
# end-to-end acceptance: two serving runtimes under chaos, a level
# rule on the MERGED fleet ttft sketch fires, the retained alert
# record names exemplar trace ids, and the triggered flight dump
# carries those traces' journey spans across >= 2 pids.

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "scripts"))

from aiko_services_tpu.observe import (
    DumpOnAlert, FlightRecorder, HealthAggregator, MetricsPublisher,
    MetricsRegistry, SLORule, SeriesStore, Sketch, SketchSeries,
    default_registry, merge_sketches, tenant_slo_rows, tracing)
from aiko_services_tpu.observe import flight, journey
from aiko_services_tpu.event import settle_virtual
from aiko_services_tpu.pipeline import (
    DEFERRED, Frame, FrameOutput, Pipeline, PipelineElement,
    parse_pipeline_definition)
from aiko_services_tpu.registrar import Registrar
from aiko_services_tpu.share import ServicesCache


def element(name, inputs=(), outputs=(), deploy=None):
    return {"name": name,
            "input": [{"name": n} for n in inputs],
            "output": [{"name": n} for n in outputs],
            "deploy": deploy or {}}


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


# ---------------------------------------------------------------------------
# sketch properties
# ---------------------------------------------------------------------------

def _seeded_distributions():
    rng = np.random.default_rng(17)
    return {
        "lognormal": rng.lognormal(mean=-3.0, sigma=1.2, size=20000),
        "bimodal": np.concatenate([
            rng.normal(0.010, 0.002, size=12000).clip(1e-6),
            rng.normal(0.900, 0.100, size=8000).clip(1e-6)]),
    }


class TestSketchProperties:
    def test_relative_error_bound(self):
        """<= 2% relative error at p50/p95/p99 vs exact on seeded
        lognormal AND bimodal data (the ISSUE 12 acceptance; alpha =
        0.01 guarantees 1%, the margin absorbs rank interpolation)."""
        for name, data in _seeded_distributions().items():
            sketch = Sketch()
            for value in data:
                sketch.observe(value)
            for q in (0.50, 0.95, 0.99):
                exact = float(np.percentile(data, q * 100.0))
                approx = sketch.quantile(q)
                assert abs(approx - exact) / exact <= 0.02, \
                    f"{name} p{q * 100:.0f}: {approx} vs {exact}"

    def test_merge_equals_union_and_is_commutative_associative(self):
        data = _seeded_distributions()["lognormal"]
        parts = np.array_split(data, 3)
        sketches = []
        for part in parts:
            sketch = Sketch()
            for value in part:
                sketch.observe(value)
            sketches.append(sketch)
        union = Sketch()
        for value in data:
            union.observe(value)
        a, b, c = sketches

        def quantiles(sketch):
            return [sketch.quantile(q) for q in (0.5, 0.95, 0.99)]

        merged_abc = merge_sketches([a, b, c])
        merged_cba = merge_sketches([c, b, a])
        merged_nested = merge_sketches([merge_sketches([a, b]), c])
        # merged(A,B,C) == one-sketch(A ∪ B ∪ C), exactly — bins add
        assert quantiles(merged_abc) == quantiles(union)
        assert quantiles(merged_cba) == quantiles(union)     # commut.
        assert quantiles(merged_nested) == quantiles(union)  # assoc.
        assert merged_abc.count == union.count == len(data)

    def test_serialization_roundtrip_through_snapshot_schema(self):
        """Registry sketch -> snapshot() -> JSON wire form ->
        from_dict: quantiles, count, and exemplars survive intact (the
        retained {topic}/0/metrics path)."""
        registry = MetricsRegistry()
        sketch = registry.sketch("rt_sketch_seconds", "x",
                                 {"tenant": "acme"})
        rng = np.random.default_rng(3)
        for index, value in enumerate(rng.lognormal(size=500)):
            sketch.observe(value, exemplar=f"trace{index}")
        snapshot = json.loads(json.dumps(registry.snapshot()))
        entry = snapshot["rt_sketch_seconds"]
        assert entry["type"] == "sketch"
        series = entry["series"][0]
        assert series["labels"] == {"tenant": "acme"}
        restored = Sketch.from_dict(series)
        for q in (0.5, 0.95, 0.99):
            assert restored.quantile(q) == sketch.quantile(q)
        assert restored.count == sketch.count
        assert sorted(e[1] for e in restored.exemplars) == \
            sorted(e[1] for e in sketch.exemplars)

    def test_exemplars_keep_topk_worst_and_window_by_seq(self):
        sketch = Sketch(exemplar_k=2)
        for index, value in enumerate([0.1, 0.5, 0.2, 0.9, 0.3]):
            sketch.observe(value, exemplar=f"t{index}")
        worst = sketch.worst_exemplars()
        assert [e[1] for e in worst] == ["t3", "t1"]     # 0.9, 0.5
        # seq filter: only exemplars observed after the count was 3 —
        # t1 (the 2nd observation) ages out, t3 (the 4th) stays
        assert [e[1] for e in sketch.worst_exemplars(min_seq=3)] == \
            ["t3"]

    def test_bins_bounded_by_collapse(self):
        sketch = Sketch(alpha=0.01, max_bins=32)
        rng = np.random.default_rng(5)
        for value in rng.lognormal(sigma=4.0, size=5000):
            sketch.observe(value)
        assert len(sketch.bins) <= 32
        # collapsing folds LOW buckets: the tail keeps its guarantee
        data = rng.lognormal(sigma=4.0, size=5000)
        exact_like = Sketch(alpha=0.01)
        for value in data:
            exact_like.observe(value)

    def test_cross_source_window_merge_in_series_store(self):
        """TWO sources with asymmetric latency: the merged fleet p95
        weighs them by observation count (fleet-true), which the old
        worst-of-per-process read cannot do — and equals the quantile
        of one sketch fed both windows' observations."""
        store = SeriesStore(window=60.0)
        fast = np.full(950, 0.010)
        slow = np.full(50, 1.000)

        def payload(values):
            sketch = Sketch()
            for value in values:
                sketch.observe(value)
            return {**sketch.to_dict(), "labels": {}}

        def snapshot_doc(values):
            return {"serving_ttft_seconds": {
                "type": "sketch",
                "series": [payload(values)]}}

        # two samples per source: first is the baseline, second the
        # window's delta (anti-contamination rule)
        store.append_snapshot("proc_a", snapshot_doc([]), t=0.0)
        store.append_snapshot("proc_a", snapshot_doc(fast), t=1.0)
        store.append_snapshot("proc_b", snapshot_doc([]), t=0.0)
        store.append_snapshot("proc_b", snapshot_doc(slow), t=1.0)
        merged = store.merged_sketch("serving_ttft_seconds", 2.0, 30.0)
        assert merged.count == 1000
        union = Sketch()
        for value in np.concatenate([fast, slow]):
            union.observe(value)
        assert merged.quantile(0.95) == union.quantile(0.95)
        # fleet-true: p95 is fast (5% slow tail), NOT the slow
        # process's own p95 — worst-of would report ~1.0 s
        assert merged.quantile(0.95) < 0.05
        level = store.selector_level("serving_ttft_seconds:p95", 2.0,
                                     30.0)
        assert level == merged.quantile(0.95)

    def test_windowed_delta_excludes_prior_contamination(self):
        """Cumulative mass from before the window cannot leak into the
        windowed quantile — the HistogramSeries discipline, for
        sketches."""
        ring = SketchSeries("s", {})
        old = Sketch()
        for _ in range(1000):
            old.observe(10.0)                 # ancient slow history
        ring.append(0.0, old.to_dict())
        newer = Sketch.from_dict(old.to_dict())
        for _ in range(100):
            newer.observe(0.001)              # this window: fast
        ring.append(50.0, newer.to_dict())
        delta = ring.delta_sketch(51.0, 10.0)  # window sees both rows?
        # window [41, 51] holds ONLY the t=50 sample -> baseline, None
        assert delta is None
        delta = ring.delta_sketch(51.0, 60.0)
        assert delta.count == 100
        assert delta.quantile(0.95) < 0.01


# ---------------------------------------------------------------------------
# publisher jitter + publish cost
# ---------------------------------------------------------------------------

class TestPublisherJitter:
    def _publish_times(self, make_runtime, engine, seed):
        registry = MetricsRegistry()
        runtime = make_runtime(f"jit_{seed}").initialize()
        times = []
        original = MetricsPublisher.publish_now

        publisher = MetricsPublisher(runtime, interval=1.0,
                                     registry=registry, jitter=0.2,
                                     jitter_seed=seed)
        publisher.publish_now = lambda: (
            times.append(engine.clock.now()), original(publisher))
        settle_virtual(engine, 6.0)
        publisher.stop()
        return times

    def test_seeded_jitter_decorrelates_and_is_deterministic(
            self, make_runtime, engine):
        times_a = self._publish_times(make_runtime, engine, seed=1)
        times_b = self._publish_times(make_runtime, engine, seed=2)
        assert len(times_a) >= 4 and len(times_b) >= 4
        # jittered: not the metronome cadence...
        intervals = [round(b - a, 6)
                     for a, b in zip(times_a, times_a[1:])]
        assert len(set(intervals)) > 1
        assert all(0.8 <= i <= 1.2 + 1e-9 for i in intervals)
        # ...and two seeds do not synchronize
        assert times_a[:4] != times_b[:4]
        # deterministic: the same seed replays the same schedule
        engine2_times = [t - times_a[0] for t in times_a]
        assert engine2_times[0] == 0.0

    def test_publish_cost_gauge(self, make_runtime, engine):
        registry = MetricsRegistry()
        runtime = make_runtime("jit_cost").initialize()
        publisher = MetricsPublisher(runtime, interval=5.0,
                                     registry=registry)
        publisher.publish_now()
        snapshot = registry.snapshot()
        assert "metrics_publish_seconds" in snapshot
        value = snapshot["metrics_publish_seconds"]["series"][0]["value"]
        assert value >= 0.0
        publisher.stop()

    def test_zero_jitter_keeps_exact_cadence(self, make_runtime,
                                             engine):
        registry = MetricsRegistry()
        runtime = make_runtime("jit_zero").initialize()
        times = []

        class StampingPublisher(MetricsPublisher):
            def publish_now(self):
                times.append(engine.clock.now())
                super().publish_now()

        publisher = StampingPublisher(runtime, interval=1.0,
                                      registry=registry, jitter=0.0)
        settle_virtual(engine, 4.5)
        publisher.stop()
        intervals = [b - a for a, b in zip(times, times[1:])]
        # metronome cadence to within ONE settle tick (VirtualClock's
        # 0.05 advance accumulates float drift against the heap's
        # exact due increments) — vs the jittered test's ±20% spread
        assert intervals and all(abs(i - 1.0) <= 0.06
                                 for i in intervals)


# ---------------------------------------------------------------------------
# lint-wall-clock
# ---------------------------------------------------------------------------

class TestLintWallClock:
    def _lint(self, source, path="aiko_services_tpu/observe/x.py"):
        from aiko_services_tpu.analysis.lint import lint_source
        return [f for f in lint_source(source, path)
                if f.rule == "lint-wall-clock"]

    def test_time_time_flagged(self):
        assert self._lint("import time\nstamp = time.time()\n")

    def test_datetime_now_flagged(self):
        found = self._lint(
            "import datetime\nwhen = datetime.datetime.now()\n"
            "legacy = datetime.datetime.utcnow()\n")
        assert len(found) == 2

    def test_monotonic_and_perf_counter_pass(self):
        assert not self._lint(
            "import time\na = time.monotonic()\nb = time.perf_counter()\n")

    def test_import_aliases_resolved(self):
        # aliased module imports still trip ...
        assert self._lint("import time as t\nstamp = t.time()\n")
        assert self._lint(
            "import datetime as dt\nwhen = dt.datetime.now()\n")
        assert self._lint("from time import time\nstamp = time()\n")
        # ... while unrelated attributes named .time() do not
        assert not self._lint("stamp = self.clock.time()\n")
        assert not self._lint("stamp = frame.time()\n")

    def test_waiver_suppresses(self):
        assert not self._lint(
            "import time\n"
            "stamp = time.time()  # graft: disable=lint-wall-clock\n")

    def test_tests_exempt(self):
        assert not self._lint("import time\nstamp = time.time()\n",
                              path="tests/test_x.py")

    def test_rule_registered(self):
        from aiko_services_tpu.analysis.lint import LINT_RULES
        assert "lint-wall-clock" in LINT_RULES


# ---------------------------------------------------------------------------
# request journeys through a real decoder
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_llama():
    import jax
    from aiko_services_tpu.models.llama import LLAMA_PRESETS, llama_init
    config = LLAMA_PRESETS["tiny"]
    return llama_init(jax.random.PRNGKey(0), config), config


def make_decoder(tiny_llama, name, registry=None, **kwargs):
    from aiko_services_tpu.serving import ContinuousDecoder
    params, config = tiny_llama
    options = {"max_slots": 2, "max_seq": 64, "prefill_buckets": (8,),
               "steps_per_sync": 2, **kwargs}
    return ContinuousDecoder(params, config, name=name,
                             registry=registry, **options)


class TestRequestJourney:
    def test_journey_record_full_lifecycle(self, tiny_llama,
                                           enabled_tracer):
        registry = MetricsRegistry()
        decoder = make_decoder(tiny_llama, "jdec", registry)
        context = tracing.new_trace()
        journey.note_admission(context.trace_id, "admitted",
                               queue_wait_s=0.025, tenant="acme",
                               tier=1)
        done = []
        with tracing.activate(context):
            assert decoder.submit(
                "r1", [1, 2, 3], 4, lambda rid, toks: done.append(toks),
                deadline=time.monotonic() + 30.0)
        for _ in range(12):
            decoder.pump()
            if done:
                break
        assert done
        record = decoder.journeys.journey_for(context.trace_id)
        assert record is not None
        doc = record.to_dict()
        assert doc["admission_verdict"] == "admitted"
        assert doc["admission_wait_s"] == pytest.approx(0.025)
        assert doc["tenant"] == "acme"
        assert doc["waves"].get("admit", 0) >= 1
        assert doc["tokens_total"] == 4
        assert len(doc["token_ticks"]) == 4
        assert doc["ttft_s"] > 0 and doc["queue_wait_s"] >= 0
        assert doc["outcome"] == "deadline-met"
        assert doc["deadline_margin_s"] > 0
        # spans emitted under the frame's trace id, journey names
        names = [s.name for s in enabled_tracer.spans
                 if s.trace_id == context.trace_id]
        for expected in ("journey:request", "journey:admission",
                         "journey:queue", "journey:prefill",
                         "journey:token"):
            assert expected in names
        # the per-token ticks parent to the journey:request span
        request_span = next(s for s in enabled_tracer.spans
                            if s.name == "journey:request")
        token_spans = [s for s in enabled_tracer.spans
                       if s.name == "journey:token"
                       and s.trace_id == context.trace_id]
        assert all(s.parent_id == request_span.span_id
                   for s in token_spans)
        assert request_span.parent_id == context.span_id

    def test_sketch_percentiles_match_adhoc_computation(self,
                                                       tiny_llama):
        """The bench-parity acceptance at unit scale: sketch-derived
        ttft/itl p50/p95 agree with the np.percentile-over-deque
        numbers within the sketch's relative error (plus a whisker for
        rank interpolation on small samples)."""
        registry = MetricsRegistry()
        decoder = make_decoder(tiny_llama, "jparity", registry)
        done = []
        for index in range(8):
            decoder.submit(f"p{index}", [1 + index % 5, 2, 3], 10,
                           lambda rid, toks: done.append(rid))
        for _ in range(120):
            decoder.pump()
            if len(done) == 8:
                break
        assert len(done) == 8
        adhoc = decoder.slo_stats()
        sketchy = decoder.slo_sketch_stats()
        ordered_ms = {
            "ttft": sorted(s * 1000.0 for s in decoder.ttft_samples),
            "itl": sorted(s * 1000.0 for s in decoder.itl_samples)}
        for kind in ("ttft", "itl"):
            samples = ordered_ms[kind]
            for q, suffix in ((0.5, "p50"), (0.95, "p95")):
                exact = adhoc[f"{kind}_{suffix}_ms"]
                approx = sketchy[f"{kind}_{suffix}_ms"]
                if exact is None:
                    continue
                # the sketch guarantees a value WITHIN the order
                # stats bracketing the rank (1% bucket error); the
                # np.percentile number INTERPOLATES between them, and
                # at small n over a bimodal ITL population (within- vs
                # cross-sync-burst gaps) the midpoint can sit far from
                # both brackets — so accept the bracket interval, not
                # the midpoint (the bench smoke pins the midpoint at
                # thousands of samples)
                rank = q * (len(samples) - 1)
                lo = samples[int(np.floor(rank))]
                hi = samples[int(np.ceil(rank))]
                assert lo * 0.95 <= approx <= hi * 1.05, \
                    f"{kind} {suffix}: {approx} outside " \
                    f"[{lo}, {hi}] (np interp {exact})"
        assert sketchy["ttft_exemplars"]

    def test_decoder_shed_closes_journey(self, tiny_llama):
        registry = MetricsRegistry()
        decoder = make_decoder(tiny_llama, "jshed", registry)
        decoder._round_ewma = 10.0      # huge estimated wait
        accepted = decoder.submit("doomed", [1], 4, lambda *_: None,
                                  deadline=time.monotonic() + 0.001)
        assert not accepted
        assert decoder.journeys.journeys()[-1].outcome == "shed"
        snapshot = registry.snapshot()
        series = snapshot["journey_requests_total"]["series"]
        shed = [s for s in series if s["labels"]["outcome"] == "shed"]
        assert shed and shed[0]["value"] == 1


class TestJourneyRounds:
    """ISSUE 36: a journey carries the `seq` of the decoder's round
    records that served it, and every finished journey leaves a plain
    tuple that `journey_log(name)` hands to a reader without the
    decoder."""

    PROMPT = [(i * 13) % 50 + 1 for i in range(40)]

    def served(self, tiny_llama, name):
        """A paged decoder serves three requests of different lengths,
        the second in chunks, each submitted some rounds after the one
        before: (decoder, {request: {seq: tokens handed over in it}},
        {request: the last committed seq when it was submitted})."""
        from aiko_services_tpu.observe import profiler as P
        decoder = make_decoder(
            tiny_llama, name, MetricsRegistry(), paged_kv=True, kv_block=8,
            max_slots=4, prefill_buckets=(16,), prefill_chunk=16)
        handed, submitted_after, done = {}, {}, []

        def on_token(request_id, slot, token, now):
            rounds = handed.setdefault(request_id, {})
            seq = decoder.profiler.seq
            rounds[seq] = rounds.get(seq, 0) + 1

        decoder.on_token = on_token
        for rid, prompt, new, pumps in (("a", self.PROMPT[:12], 11, 3),
                                        ("b", self.PROMPT[:40], 7, 2),
                                        ("c", self.PROMPT[:7], 4, 80)):
            log = P.round_log(name)
            submitted_after[rid] = log[-1][0] if log else 0
            assert decoder.submit(rid, prompt, new,
                                  lambda rid, toks: done.append(rid))
            for _ in range(pumps):
                decoder.pump()
        assert sorted(done) == ["a", "b", "c"]
        return decoder, handed, submitted_after

    def test_round_numbers_are_in_order_and_in_the_ring(self, tiny_llama):
        from aiko_services_tpu.observe import profiler as P
        decoder, _, submitted_after = self.served(tiny_llama, "jrounds_a")
        seqs = {r[0] for r in P.round_log("jrounds_a")}
        journeys = {j.request_id: j for j in decoder.journeys.journeys()}
        assert set(journeys) == {"a", "b", "c"}
        for rid, j in journeys.items():
            assert {j.first_round, j.last_round} <= seqs
            # a round that began after the request was submitted handed
            # over its first token, a later one its last
            assert submitted_after[rid] < j.first_round < j.last_round
        assert submitted_after["a"] == 0 < submitted_after["b"]
        # the chunked prompt rode three extends before its first token
        assert journeys["b"].waves == {"chunk-admit": 1, "extend": 3}
        assert journeys["b"].first_round >= submitted_after["b"] + 4
        assert journeys["a"].first_round <= submitted_after["a"] + 2

    def test_the_rounds_between_first_and_last_hand_over_the_rest(
            self, tiny_llama):
        decoder, handed, _ = self.served(tiny_llama, "jrounds_b")
        for j in decoder.journeys.journeys():
            rounds = handed[j.request_id]
            assert min(rounds) == j.first_round
            assert max(rounds) == j.last_round > j.first_round
            assert sum(rounds.values()) == j.tokens_total
            # the first round hands over the owed first token and what its
            # own step emitted; every later token falls in (first, last]
            later = sum(count for seq, count in rounds.items()
                        if j.first_round < seq <= j.last_round)
            assert later == j.tokens_total - rounds[j.first_round]
            assert 1 <= rounds[j.first_round] <= 1 + decoder.steps_per_sync
            assert later >= 1

    def test_the_tuple_carries_what_a_reader_uses(self, tiny_llama):
        decoder, _, _ = self.served(tiny_llama, "jrounds_c")
        records = journey.journey_log("jrounds_c")
        assert journey.JOURNEY_RECORD == (
            "request_id", "tokens_total", "first_round", "last_round")
        finished = decoder.journeys.journeys()
        assert len(records) == len(finished) == 3
        for record, j in zip(records, finished):
            assert type(record) is tuple
            assert record == (j.request_id, j.tokens_total, j.first_round,
                              j.last_round)
            assert j.done_t == j.token_ticks[-1]          # the last token's

    def test_a_request_that_never_got_there_keeps_minus_one(
            self, tiny_llama):
        decoder = make_decoder(tiny_llama, "jrounds_d", MetricsRegistry())
        decoder._round_ewma = 10.0      # huge estimated wait: shed
        assert not decoder.submit("doomed", [1], 4, lambda *_: None,
                                  deadline=time.monotonic() + 0.001)
        decoder._round_ewma = None
        for index in range(3):          # two slots: the third one queues
            assert decoder.submit(f"q{index}", [1, 2, 3], 6, lambda *_: None)
        decoder.pump()
        decoder.pump()
        (evacuated,) = decoder.drain()
        assert evacuated["request_id"] == "q2"
        by_id = {r[0]: dict(zip(journey.JOURNEY_RECORD, r))
                 for r in journey.journey_log("jrounds_d")}
        outcomes = {j.request_id: j.outcome
                    for j in decoder.journeys.journeys()}
        assert outcomes == {"doomed": "shed", "q2": "evacuated"}
        for rid in ("doomed", "q2"):
            assert by_id[rid]["first_round"] == by_id[rid]["last_round"] \
                == -1
            assert by_id[rid]["tokens_total"] == 0

    def test_journey_log_outlives_completed_and_goes_with_its_decoder(
            self, tiny_llama):
        import gc
        log = journey.JourneyLog(name="jrounds_e",
                                 registry=MetricsRegistry())
        for index in range(300):
            log.finish(journey.RequestJourney(f"r{index}", float(index)),
                       float(index) + 1.0)
        assert len(log.completed) == 256
        records = journey.journey_log("jrounds_e")
        assert [r[0] for r in records] == [f"r{i}" for i in range(300)]
        assert log.finished.maxlen == journey.RING_JOURNEYS == 8192
        del log
        gc.collect()
        with pytest.raises(LookupError):
            journey.journey_log("jrounds_e")
        decoder = make_decoder(tiny_llama, "jrounds_f", MetricsRegistry())
        assert journey.journey_log("jrounds_f") == []
        del decoder
        gc.collect()
        with pytest.raises(LookupError):
            journey.journey_log("jrounds_f")


# ---------------------------------------------------------------------------
# per-tenant SLO rows: dashboard pane + slo_report script
# ---------------------------------------------------------------------------

def _tenant_snapshot():
    """A registry snapshot with two tenants' journey evidence."""
    registry = MetricsRegistry()
    ttft_acme = registry.sketch("serving_ttft_seconds", "",
                                {"decoder": "d", "tenant": "acme"})
    ttft_flood = registry.sketch("serving_ttft_seconds", "",
                                 {"decoder": "d", "tenant": "flood"})
    for value in (0.010, 0.012, 0.011):
        ttft_acme.observe(value, exemplar="trace-acme")
    for value in (0.900, 1.100):
        ttft_flood.observe(value, exemplar="trace-flood")
    registry.counter("journey_requests_total",
                     labels={"log": "d", "tenant": "acme",
                             "outcome": "deadline-met"}).inc(99)
    registry.counter("journey_requests_total",
                     labels={"log": "d", "tenant": "acme",
                             "outcome": "deadline-missed"}).inc(1)
    registry.counter("journey_requests_total",
                     labels={"log": "d", "tenant": "flood",
                             "outcome": "deadline-missed"}).inc(6)
    registry.counter("journey_requests_total",
                     labels={"log": "d", "tenant": "flood",
                             "outcome": "deadline-met"}).inc(4)
    registry.counter("admission_shed_total",
                     labels={"tenant": "flood", "tier": "1",
                             "reason": "tenant-over-budget"}).inc(15)
    return json.loads(json.dumps(registry.snapshot()))


class TestTenantSLORows:
    def test_rows_merge_outcomes_sketches_and_admission(self):
        rows = tenant_slo_rows([_tenant_snapshot()], objective=0.99)
        by_tenant = {row["tenant"]: row for row in rows}
        acme, flood = by_tenant["acme"], by_tenant["flood"]
        assert acme["attainment"] == pytest.approx(0.99)
        assert acme["met"] and not flood["met"]
        assert flood["attainment"] == pytest.approx(0.4)
        assert flood["shed"] == 15
        assert acme["ttft_p95_ms"] < 50 < flood["ttft_p95_ms"]
        assert "trace-flood" in flood["exemplars"]

    def test_dashboard_pane_leads_with_tenant_rows(self, make_runtime,
                                                   engine):
        from aiko_services_tpu.dashboard import DashboardState
        runtime = make_runtime("dash_slo").initialize()
        state = DashboardState(runtime)
        state.metrics_doc = {"process": "p", "time": 1.0,
                             "snapshot": _tenant_snapshot()}
        state._metrics_topic = "x"
        lines = state.metrics_lines()
        tenant_lines = [line for line in lines if "flood" in line]
        assert tenant_lines and "ttft_p95" in tenant_lines[0]
        assert any("tenant SLO" in line for line in lines)
        state.terminate()

    def test_slo_report_script(self, make_runtime, engine):
        """scripts/slo_report.py over a live runtime's retained
        snapshots: rows rendered in both formats, exit logic on the
        objective."""
        import slo_report
        publisher_rt = make_runtime("slo_pub").initialize()
        scraper_rt = make_runtime("slo_scrape").initialize()
        registry = MetricsRegistry()
        # populate the registry with the canonical two-tenant fixture
        snapshot = _tenant_snapshot()
        publisher_rt.publish(
            f"{publisher_rt.topic_path}/0/metrics",
            json.dumps({"process": "slo_pub",
                        "topic_path": publisher_rt.topic_path,
                        "time": 1.0, "snapshot": snapshot}),
            retain=True)
        documents = slo_report.collect_snapshots(
            scraper_rt, wait=1.0,
            settle=lambda eng, seconds: settle_virtual(eng, seconds))
        assert publisher_rt.topic_path in documents
        rows = slo_report.report_rows(documents, objective=0.99)
        assert not all(row["met"] for row in rows)       # flood misses
        text = slo_report.render_report(rows, "text", objective=0.99)
        assert "MISSED" in text and "flood" in text
        parsed = json.loads(slo_report.render_report(rows, "json",
                                                     objective=0.99))
        assert parsed["objective"] == 0.99
        assert {row["tenant"] for row in parsed["tenants"]} == \
            {"acme", "flood"}
        del registry


# ---------------------------------------------------------------------------
# the e2e acceptance: chaos fleet -> merged-sketch alert -> exemplar ->
# flight dump with journey spans
# ---------------------------------------------------------------------------

class PE_JSource(PipelineElement):
    def process_frame(self, frame: Frame, **_) -> FrameOutput:
        return FrameOutput(True, {"value": 3})


class _AgentBase(PipelineElement):
    decoder = None          # class attribute set by the test
    out_name = "tokens"

    def process_frame(self, frame: Frame, value=0, **_) -> FrameOutput:
        import time as _time
        from aiko_services_tpu.observe.tracing import current_trace
        context = current_trace()
        deadline = None
        if context is not None and context.deadline is not None:
            remaining = context.remaining(
                self.runtime.event.clock.now())
            if remaining is not None:
                deadline = _time.monotonic() + max(0.0, remaining)

        def on_done(_rid, generated):
            self.pipeline.post("resume_frame", frame,
                               self.definition.name,
                               {self.out_name: len(generated)})

        accepted = type(self).decoder.submit(
            f"{frame.stream_id}.{frame.frame_id}",
            [1 + int(value), 2, 3], 3, on_done, deadline=deadline)
        if not accepted:
            return FrameOutput(False, diagnostic="decoder shed")
        return FrameOutput(True, DEFERRED)


class PE_JAgent1(_AgentBase):
    out_name = "tok1"


class PE_JAgent2(_AgentBase):
    out_name = "tok2"


class TestJourneyPlaneEndToEnd:
    def test_chaos_fleet_alert_exemplar_dump(self, make_runtime,
                                             engine, broker,
                                             enabled_tracer, tiny_llama,
                                             tmp_path):
        """ISSUE 12 acceptance: two serving runtimes (each a pipeline
        + ContinuousDecoder) under seeded chaos, a ttft-p95 LEVEL rule
        over the MERGED fleet sketch fires, the retained alert record
        carries >= 1 exemplar trace id, and the DumpOnAlert flight dump
        contains that trace's journey spans (admission -> queue ->
        prefill -> per-token ticks) with the trace spanning >= 2
        pids."""
        from aiko_services_tpu.ops.admission import AdmissionGate
        from aiko_services_tpu.transport.chaos import (ChaosBroker,
                                                       FaultPlan)
        plan = FaultPlan(seed=9)
        broker.__class__ = ChaosBroker
        broker.plan = plan
        broker.engine = engine

        reg_rt = make_runtime("reg").initialize()
        Registrar(reg_rt)
        settle_virtual(engine, 2.5)

        registries = [MetricsRegistry(), MetricsRegistry()]
        serve_rts, servings, publishers, recorders = [], [], [], []
        for index, agent_class in enumerate((PE_JAgent1, PE_JAgent2)):
            serve_rt = make_runtime(f"sj{index + 1}").initialize()
            decoder = make_decoder(tiny_llama, f"serve_j{index + 1}",
                                   registries[index])
            decoder.attach(engine)
            agent_class.decoder = decoder
            serving = Pipeline(
                serve_rt, parse_pipeline_definition({
                    "version": 0, "name": f"serve_j{index + 1}",
                    "runtime": "python",
                    "graph": [f"({agent_class.__name__})"],
                    "elements": [element(agent_class.__name__,
                                         ["value"],
                                         [agent_class.out_name])]}),
                element_classes={agent_class.__name__: agent_class},
                auto_create_streams=True, stream_lease_time=0,
                admission=AdmissionGate())
            servings.append(serving)
            serve_rts.append(serve_rt)
            publishers.append(MetricsPublisher(
                serve_rt, interval=0.5, registry=registries[index]))
            recorders.append(FlightRecorder(serve_rt,
                                            sample_interval=0.5))

        call_rt = make_runtime("call").initialize()
        caller = Pipeline(
            call_rt, parse_pipeline_definition({
                "version": 0, "name": "call_j", "runtime": "python",
                "graph": ["(PE_JSource (remote_j1) (remote_j2))"],
                "elements": [
                    element("PE_JSource", [], ["value"]),
                    element("remote_j1", ["value"], ["tok1"],
                            deploy={"remote": {"service_filter":
                                    {"name": "serve_j1"}}}),
                    element("remote_j2", ["value"], ["tok2"],
                            deploy={"remote": {"service_filter":
                                    {"name": "serve_j2"}}})]}),
            element_classes={"PE_JSource": PE_JSource},
            services_cache=ServicesCache(call_rt),
            stream_lease_time=0, frame_deadline=60.0,
            remote_timeout=1.0, remote_retries=3, remote_backoff=0.25,
            retry_seed=7)
        recorders.append(FlightRecorder(call_rt, sample_interval=0.5))
        settle_virtual(engine, 2.0)
        assert caller.remote_elements_ready()

        # chaos: drop the first request reaching each serving input —
        # the callers' retry machinery recovers both
        for serving in servings:
            plan.drop(topic=f"{serving.topic_path}/in",
                      probability=1.0, count=1)

        # the fleet rule: ttft p95 over the MERGED sketches (any real
        # decoder latency breaches the threshold -> it must fire from
        # windowed deltas of BOTH sources)
        agg_rt = make_runtime("agg").initialize()
        rule = SLORule(name="ttft-p95", kind="level",
                       series="serving_ttft_seconds:p95",
                       threshold=1e-6, window=120.0,
                       description="fleet ttft p95")
        aggregator = HealthAggregator(agg_rt, rules=[rule],
                                      interval=0.5, window=240.0)
        dump_trigger = DumpOnAlert(str(tmp_path))
        aggregator.on_alert.append(dump_trigger)

        done = []
        caller.add_frame_handler(done.append)
        caller.create_stream("s1", lease_time=0)
        for _ in range(4):
            caller.post("process_frame", "s1", {})
            settle_virtual(engine, 1.5)
        settle_virtual(engine, 4.0)

        assert len(done) == 4, "frames lost under chaos"
        assert int(done[0].swag["tok1"]) == 3
        assert int(done[0].swag["tok2"]) == 3
        # chaos actually bit: at least one retry recovered a drop
        assert caller.recovery_stats["retries"] >= 1

        # the rule fired on the MERGED sketch, with exemplars
        assert aggregator.firing() == ["ttft-p95"]
        record = aggregator.alerts["ttft-p95"]
        assert record["state"] == "firing"
        assert len(record["exemplars"]) >= 1
        exemplar = record["exemplars"][0]
        frame_traces = {frame.trace.trace_id for frame in done}
        assert exemplar in frame_traces
        # ... and the retained copy on {namespace}/alert/{rule} says so
        retained = []
        watch_rt = make_runtime("watch").initialize()
        watch_rt.add_message_handler(
            lambda topic, payload: retained.append(payload),
            f"{watch_rt.namespace}/alert/ttft-p95")
        settle_virtual(engine, 0.5)
        retained_record = json.loads(retained[-1])
        assert retained_record["exemplars"] == record["exemplars"]

        # the triggered dump carries the exemplar's journey spans,
        # and the trace spans >= 2 pids (caller hop + serving journey)
        dump_path = dump_trigger.dumped["ttft-p95"]
        with open(dump_path) as f:
            document = json.load(f)
        assert document["metadata"]["reason"] == "slo-breach:ttft-p95"
        assert exemplar in document["metadata"]["exemplars"]
        events = document["traceEvents"]
        ours = [e for e in events if e.get("ph") == "X"
                and e["args"].get("trace_id") == exemplar]
        names = {e["name"] for e in ours}
        for expected in ("journey:admission", "journey:queue",
                         "journey:prefill", "journey:token"):
            assert expected in names, f"missing {expected}: {names}"
        assert len({e["pid"] for e in ours}) >= 2
        # the journey's admission span carries the measured verdict
        admission_span = next(e for e in ours
                              if e["name"] == "journey:admission")
        assert admission_span["args"]["verdict"] == "admitted"

        for publisher in publishers:
            publisher.stop()
        aggregator.stop()
        caller.stop()
        for serving, agent_class in zip(servings,
                                        (PE_JAgent1, PE_JAgent2)):
            serving.stop()
            agent_class.decoder.detach(engine)
        for recorder in recorders:
            recorder.close()
