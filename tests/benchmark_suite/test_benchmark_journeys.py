"""The judged gap between tokens split from inside (ISSUE 36):
`benchmark/program_journeys.py` and the six metric files over it, on
hand-built rings and journey tuples, on a decoder served on the CPU, and
their entries in the manifest."""

import dataclasses
import gc
import os
import types

import pytest

from aiko_services_tpu.observe import journey as J
from aiko_services_tpu.observe import profiler as P
from benchmark import program_journeys, program_rounds, run

MS = 1e-3
NAMES = ("tpot_mid_ms", "tpot_mid_host_ms", "tpot_mid_sync_clean_ms",
         "tpot_mid_sync_behind_ms", "tpot_mid_rounds_behind",
         "prefill_prefix_depth")
CELLS = ["chat_open_loop", "doc_qa_open_loop", "long_doc_open_loop"]
# between two rounds' stamps, everything but the later round's sync:
# wave_resolve 0.05 + deliver 0.4 of the earlier; gap 0.1, plan 0.2,
# scan_dispatch 0.3, admit_dispatch 0.1, extend_dispatch 0.1 of the later
HOST_MS = 0.05 + 0.4 + 0.1 + 0.2 + 0.3 + 0.1 + 0.1


def round_record(seq, sync_ms, ahead=0, pieces=0, depth=0, num_steps=4,
                 fields=None):
    fields = fields or P.ROUND_RECORD
    values = dict.fromkeys(fields, 0.0) | {
        "seq": seq, "rounds": 100 + seq, "idle_before": False,
        "gap_s": 0.1 * MS, "plan": 0.2 * MS, "scan_dispatch": 0.3 * MS,
        "admit_dispatch": 0.1 * MS, "extend_dispatch": 0.1 * MS,
        "host_sync": sync_ms * MS, "wave_resolve": 0.05 * MS,
        "deliver": 0.4 * MS, "num_steps": num_steps, "slots": 3,
        "prefill_tokens": 512 if pieces else 0, "pending": 0,
        "attend_width": 1024, "prefill_ahead": ahead,
        "prefill_pieces": pieces, "prefill_prefix_tokens": depth}
    values["wall_s"] = sum(values[phase] for phase in P.PHASES)
    return tuple(values[name] for name in fields)


def journey_record(rid, first, last, tokens):
    values = {"request_id": rid, "tokens_total": tokens,
              "first_round": first, "last_round": last}
    return tuple(values[name] for name in J.JOURNEY_RECORD)


# rounds 1 to 20: clean ones wait 10 ms, the two behind a piece 30 ms
BEHIND = {5: (512, 1, 1024), 9: (1024, 2, 3072)}
RING = [round_record(seq, 30.0 if seq in (6, 10) else 10.0,
                     ahead=BEHIND[seq - 1][0] if seq - 1 in BEHIND else 0,
                     pieces=BEHIND.get(seq, (0, 0, 0))[1],
                     depth=BEHIND.get(seq, (0, 0, 0))[2])
        for seq in range(1, 21)]


@pytest.fixture
def program():
    """A profiler and a journey log under the driver's name: the test
    fills `program.ring` and `program.finished`."""
    profiler = P.PhaseProfiler(program_rounds.DECODER)
    log = J.JourneyLog(name=program_rounds.DECODER)
    profiler.ring.extend(RING)
    # both register weakly by name: the namespace keeps them alive
    yield types.SimpleNamespace(ring=profiler.ring, finished=log.finished,
                                registered=(profiler, log))


def a_run(requests, traced_from=None, seconds=40.0):
    """`requests`: {id: due}, every one finished and not failed."""
    run_ = {"all_records": {rid: {"due": due, "failed": False, "done": 1.0}
                            for rid, due in requests.items()},
            "seconds": seconds, "counters": {}}
    if traced_from is not None:
        run_["trace_counters"] = {"before": {"rounds": 100 + traced_from},
                                  "after": {"rounds": 100 + len(RING)}}
    return run_


def read(name, of):
    return run.load_module("layer_metrics", name).read(of)


def test_the_three_parts_sum_to_the_requests_own_gap(program):
    # rounds 4..8 after the first token in round 3: one of the five behind
    program.finished.append(journey_record("r0", 3, 8, 21))
    ring = program_journeys.program()[1]
    split = program_journeys.parts(
        dict(zip(J.JOURNEY_RECORD, program.finished[0])), ring)
    assert split["rounds"] == 5 and split["rounds_behind"] == 1
    assert split["host"] == pytest.approx(5 * HOST_MS * MS / 20)
    assert split["clean"] == pytest.approx(4 * 10.0 * MS / 20)
    assert split["behind"] == pytest.approx(30.0 * MS / 20)
    # the stamps of rounds 3 and 8 on the ring's clock are that far apart
    of = {name: i for i, name in enumerate(P.ROUND_RECORD)}

    def stamp(record, t0):
        return t0 + sum(record[of[phase]] for phase in (
            "plan", "scan_dispatch", "spec_verify", "admit_dispatch",
            "extend_dispatch", "host_sync"))

    t0, stamps = 0.0, {}
    for record in RING:
        t0 += record[of["gap_s"]]
        stamps[record[0]] = stamp(record, t0)
        t0 += record[of["wall_s"]]
    assert sum(split[key] for key in program_journeys.PARTS) == \
        pytest.approx((stamps[8] - stamps[3]) / 20)
    # and the metric files hand out the same, in ms
    only = a_run({"r0": 1.0})
    assert read("tpot_mid_ms", only) == pytest.approx(
        (5 * HOST_MS + 40.0 + 30.0) / 20)
    assert read("tpot_mid_host_ms", only) + \
        read("tpot_mid_sync_clean_ms", only) + \
        read("tpot_mid_sync_behind_ms", only) == \
        pytest.approx(read("tpot_mid_ms", only), abs=1e-9)
    assert read("tpot_mid_sync_behind_ms", only) == pytest.approx(1.5)
    assert read("tpot_mid_rounds_behind", only) == pytest.approx(20.0)


def test_a_request_with_no_gap_or_no_rounds_left_is_not_split(program):
    ring = program_journeys.program()[1]

    def parts(first, last, tokens):
        return program_journeys.parts(dict(zip(
            J.JOURNEY_RECORD, journey_record("x", first, last, tokens))),
            ring)

    assert parts(3, 8, 1) is None            # one token: no gap
    assert parts(-1, -1, 0) is None          # shed before a slot
    assert parts(3, -1, 4) is None           # evacuated half way
    assert parts(18, 22, 9) is None          # past the ring's newest
    whole = parts(4, 4, 5)                   # every token in one round
    assert whole["rounds"] == 0 and whole["clean"] == whole["host"] == 0.0
    program.ring.popleft()                   # the ring let round 1 go
    assert program_journeys.parts(dict(zip(
        J.JOURNEY_RECORD, journey_record("x", 1, 3, 9))),
        program_journeys.program()[1]) is None


@pytest.mark.parametrize("count, expected", [
    (1, [0]), (4, [0, 1, 2, 3]), (5, [0, 1, 2, 3, 4]),
    (9, [2, 3, 4, 5, 6]), (25, list(range(10, 15))),
    (50, list(range(20, 30))), (101, list(range(40, 61)))])
def test_the_median_band(count, expected):
    assert program_journeys.middle(list(range(count))) == expected


def test_the_band_is_ranked_by_gap_over_the_counted_requests(program):
    # nine requests over the same rounds: the more tokens, the smaller gap
    for i in range(9):
        program.finished.append(journey_record(f"r{i}", 3, 8, 11 + 10 * i))
    program.finished.append(journey_record("early", 3, 8, 3))
    program.finished.append(journey_record("single", 3, 8, 1))
    requests = {f"r{i}": float(i) for i in range(9)} | {
        "early": -2.0, "single": 3.0, "never": 4.0}
    of = a_run(requests)
    of["all_records"]["never"]["done"] = None
    found = program_journeys.band(of)
    gaps = [sum(s[key] for key in program_journeys.PARTS) for s in found]
    interval = (5 * HOST_MS + 70.0) * MS
    # r2 .. r6, the five nearest the median of nine, smallest gap first
    assert gaps == pytest.approx([interval / (10 + 10 * i)
                                  for i in (6, 5, 4, 3, 2)])
    assert read("tpot_mid_ms", of) == pytest.approx(
        1e3 * sum(gaps) / 5)


def test_a_request_that_reaches_into_the_traced_span_is_left_out(program):
    program.finished.append(journey_record("before", 3, 8, 21))
    program.finished.append(journey_record("into", 9, 14, 11))
    requests = {"before": 1.0, "into": 2.0}
    # the span begins behind round 12: `into` ends in it
    of = a_run(requests, traced_from=12)
    assert len(program_journeys.band(of)) == 1
    assert read("tpot_mid_ms", of) == pytest.approx(
        (5 * HOST_MS + 70.0) / 20)
    assert len(program_journeys.band(a_run(requests))) == 2   # no span
    # where that leaves none the metrics read 0.0, and never the requests
    # that reach into the span or the pre-roll's and the drain's
    for of in (a_run(requests, traced_from=2),
               a_run({"before": -3.0, "into": 41.0}, traced_from=12)):
        assert program_journeys.band(of) == []
        for name in NAMES[:5]:
            assert read(name, of) == 0.0, name


def test_a_run_that_finished_no_request_of_two_tokens_reads_zero(program):
    program.finished.append(journey_record("single", 3, 3, 1))
    of = a_run({"single": 1.0, "unknown": 2.0}, traced_from=12)
    assert program_journeys.band(of) == []
    for name in NAMES[:5]:
        assert read(name, of) == 0.0, name


def test_the_newest_journey_of_an_id_is_the_one(program):
    """The warm-up reuses its ids batch after batch."""
    program.finished.append(journey_record("r0", 3, 5, 9))
    program.finished.append(journey_record("r0", 11, 16, 21))
    assert read("tpot_mid_ms", a_run({"r0": 1.0})) == pytest.approx(
        (5 * HOST_MS + 50.0) / 20)


def test_prefix_depth_over_the_traced_spans_pieces(program):
    # the span of rounds 5..20 dispatched 1 + 2 pieces over 1024 + 3072
    of = a_run({}, traced_from=4)
    assert read("prefill_prefix_depth", of) == pytest.approx(4096 / 3)
    assert read("prefill_prefix_depth", a_run({}, traced_from=8)) == \
        pytest.approx(3072 / 2)
    # a span without a piece
    assert read("prefill_prefix_depth", a_run({}, traced_from=9)) == 0.0
    assert read("prefill_prefix_depth", a_run({}, traced_from=20)) == 0.0
    # an untraced run has no such span
    assert read("prefill_prefix_depth", a_run({})) is None


@pytest.mark.parametrize("gone", ["journey_log", "JOURNEY_RECORD"])
def test_a_parent_without_journey_log_reads_none(program, monkeypatch, gone):
    program.finished.append(journey_record("r0", 3, 8, 21))
    monkeypatch.delattr(J, gone)
    of = a_run({"r0": 1.0}, traced_from=12)
    for name in NAMES:
        assert read(name, of) is None, name


def test_a_parent_without_the_rings_fields_reads_none(program, monkeypatch):
    program.finished.append(journey_record("r0", 3, 8, 21))
    of = a_run({"r0": 1.0}, traced_from=12)
    assert read("tpot_mid_ms", of) is not None
    monkeypatch.setattr(P, "ROUND_RECORD", P.ROUND_FIELDS + ("attend_width",))
    for name in NAMES:
        assert read(name, of) is None, name
    monkeypatch.undo()
    # a ring whose records end where PR 28's did
    program.ring.clear()
    program.ring.extend(record[:len(P.ROUND_FIELDS) + 1] for record in RING)
    for name in NAMES:
        assert read(name, of) is None, name
    # and the readers of the parent's day read that ring as before
    assert program_rounds.rounds(
        {"counters": {"before": {"rounds": 100}, "after": {"rounds": 120}}})


def test_no_decoder_of_that_name_reads_none():
    gc.collect()
    if program_rounds.DECODER in P._profilers:
        pytest.skip("an earlier test of this worker left a decoder alive")
    for name in NAMES:
        assert read(name, a_run({"r0": 1.0}, traced_from=12)) is None


def test_a_served_decoders_parts_sum_to_each_journeys_own_gap():
    """The ring's clock against the journey's: every request's host +
    clean + behind is its (last - first stamp) / (tokens - 1)."""
    import jax
    from aiko_services_tpu.models.llama import LLAMA_PRESETS, llama_init
    from aiko_services_tpu.serving import ContinuousDecoder
    config = dataclasses.replace(LLAMA_PRESETS["tiny"], max_seq_len=96)
    decoder = ContinuousDecoder(
        llama_init(jax.random.PRNGKey(0), config), config, paged_kv=True,
        kv_block=8, max_slots=4, prefill_buckets=(16,), prefill_chunk=16,
        steps_per_sync=2, name=program_rounds.DECODER)
    prompt = [(i * 13) % 50 + 1 for i in range(60)]
    done = []
    for index, (length, new, pumps) in enumerate((
            (12, 14, 2), (40, 9, 3), (7, 12, 1), (55, 16, 4), (20, 6, 2),
            (33, 10, 200))):
        assert decoder.submit(f"r{index}", prompt[:length], new,
                              lambda rid, tokens: done.append(rid))
        for _ in range(pumps):
            decoder.pump()
            if len(done) == 6:
                break
    assert len(done) == 6
    journeys, ring = program_journeys.program()
    # the journeys' own stamps, on the decoder's clock
    own = {j.request_id: (j.done_t - j.first_token_t) / (j.tokens_total - 1)
           for j in decoder.journeys.journeys()}
    behind = 0
    for rid in done:
        j = journeys[rid]
        split = program_journeys.parts(j, ring)
        assert sum(split[key] for key in program_journeys.PARTS) == \
            pytest.approx(own[rid], rel=0.02)
        assert split["rounds"] == j["last_round"] - j["first_round"] >= 2
        behind += split["rounds_behind"]
    assert behind >= 1                  # somebody waited behind a chunk
    of = a_run({rid: float(i) for i, rid in enumerate(done)})
    middle = read("tpot_mid_ms", of)
    assert read("tpot_mid_host_ms", of) + \
        read("tpot_mid_sync_clean_ms", of) + \
        read("tpot_mid_sync_behind_ms", of) == pytest.approx(middle, abs=1e-6)
    assert 0.0 < read("tpot_mid_rounds_behind", of) <= 100.0
    # the window's per-request median, from the journeys' own stamps
    gaps = sorted(own[rid] for rid in done)
    assert gaps[0] * 1e3 <= middle * 1.02 and middle <= gaps[-1] * 1e3 * 1.02


def test_the_manifest_entries():
    manifest = run.load_json("BENCHMARK.json")
    tpot = next(m for m in manifest["end_to_end"]
                if m["name"] == "llm_tpot_p50_ms")
    assert tpot["workloads"] == CELLS
    # ONE entry a metric for the three cells, found by its name: where it
    # stands in the list is not this test's to say
    per_layer = manifest["per_layer"]
    assert len({m["name"] for m in per_layer}) == len(per_layer)
    mine = {m["name"]: m for m in per_layer if m["name"] in NAMES}
    assert sorted(mine) == sorted(NAMES)
    for name, (unit, source) in zip(NAMES, [("ms", "program_span")] * 4 + [
            ("%", "program_counter"), ("positions", "program_counter")]):
        assert mine[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "scheduling", "moves": "llm_tpot_p50_ms",
            "workloads": CELLS}
        assert not name.startswith("step_")
        assert os.path.exists(os.path.join(
            run.ROOT, "benchmark", "layer_metrics", name + ".py"))
        assert callable(run.load_module("layer_metrics", name).read)
    # the entries that were there are there: each cell's own, by count
    # (`test_benchmark_hybrid_sparse.py` holds their fields)
    own = {cell: [m for m in per_layer if m.get("workloads") == [cell]]
           for cell in CELLS}
    assert [len(own[cell]) for cell in CELLS] == [12, 25, 26]
    for cell in CELLS:
        names = {m["name"] for m in run.resolve(cell, False)["per_layer"]}
        assert names == set(NAMES) | {m["name"] for m in own[cell]}
    saturated = run.resolve("decode_saturated", False)["per_layer"]
    assert not set(NAMES) & {m["name"] for m in saturated}
