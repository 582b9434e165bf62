"""`attend_width.decode` / `.chat` (PR 28): the positions a round's
decode step attended at, the mean over the window's rounds that ran a step,
read from the decoder's ring behind the fields that
`program_rounds.rounds` hands out.  On a recorded ring (the widths of a
`chat_open_loop` window as the decoder's ladder would choose them), on a
ring whose records end where PR 24's did, on a program without the field,
and its entries in the manifest."""

import json
import os

import pytest

from aiko_services_tpu.observe import profiler as P
from benchmark import program_rounds, run

NAMES = ("attend_width.decode", "attend_width.chat")


def record(seq, rounds, width, num_steps=4, fields=P.ROUND_RECORD):
    values = dict.fromkeys(fields, 0.0) | {
        "seq": seq, "rounds": rounds, "idle_before": False, "wall_s": 0.08,
        "num_steps": num_steps, "slots": 7 if num_steps else 0,
        "prefill_tokens": 0, "pending": 0, "attend_width": width}
    return tuple(values[name] for name in fields)


@pytest.fixture
def ring():
    profiler = P.PhaseProfiler(program_rounds.DECODER)
    yield profiler.ring


def a_run(before=10, after=18, traced_from=None):
    run_ = {"counters": {"before": {"rounds": before},
                         "after": {"rounds": after}}}
    if traced_from is not None:
        run_["trace_counters"] = {"before": {"rounds": traced_from},
                                  "after": {"rounds": after}}
    return run_


def read(name, of):
    return run.load_module("layer_metrics", name).read(of)


RECORDED = [
    record(1, 10, 512),                     # before the window
    record(2, 11, 1024), record(3, 12, 1024), record(4, 13, 2048),
    record(5, 13, 0, num_steps=0),          # prefilled only: no step, no width
    record(6, 14, 2048), record(7, 15, 2048), record(8, 16, 512),
    record(9, 17, 1024),                    # the traced span, when there is one
    record(10, 18, 1024),
    record(11, 19, 512),                    # after the window
]


@pytest.mark.parametrize("name", NAMES)
def test_mean_width_of_the_windows_stepped_rounds(ring, name):
    ring.extend(RECORDED)
    widths = [1024, 1024, 2048, 2048, 2048, 512, 1024, 1024]
    assert read(name, a_run()) == pytest.approx(sum(widths) / 8)
    # in a traced run the window's rounds end where the profiler starts
    assert read(name, a_run(traced_from=16)) == pytest.approx(
        sum(widths[:6]) / 6)
    assert read(name, a_run(19, 19)) is None            # an empty window
    assert read(name, a_run(12, 13)) == 2048.0          # round 5 ran no step


@pytest.mark.parametrize("name", NAMES)
def test_a_ring_without_the_field_reads_none(ring, name):
    ring.extend(record(seq, 10 + seq, 1024, fields=P.ROUND_FIELDS)
                for seq in range(1, 9))
    assert len(ring[0]) == len(P.ROUND_FIELDS)
    assert program_rounds.rounds(a_run())               # the ring is read ...
    assert read(name, a_run()) is None                  # ... the width is not


@pytest.mark.parametrize("gone", ["ROUND_RECORD", "round_log"])
def test_a_program_without_the_field_reads_none(ring, monkeypatch, gone):
    """The parent of this PR (no ROUND_RECORD) and of PR 24 (no ring)."""
    ring.extend(RECORDED)
    monkeypatch.delattr(P, gone)
    for name in NAMES:
        assert read(name, a_run()) is None


def test_no_decoder_of_that_name_reads_none():
    import gc
    gc.collect()
    if program_rounds.DECODER in P._profilers:
        pytest.skip("an earlier test of this worker left a decoder alive")
    assert read(NAMES[0], a_run()) is None


def test_the_manifest_lists_it_once_a_cell():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, moves, cell in zip(
            NAMES, ("llm_tokens_per_s", "llm_tpot_p50_ms"),
            ("decode_saturated", "chat_open_loop")):
        assert entries[name] == {
            "name": name, "unit": "positions", "better": "lower",
            "source": "program_span", "layer": "model program",
            "moves": moves, "workloads": [cell]}
    # one reader for the family
    folder = os.path.join(run.ROOT, "benchmark", "layer_metrics")
    assert [f for f in os.listdir(folder) if "attend_width" in f] == \
        ["attend_width.py"]
