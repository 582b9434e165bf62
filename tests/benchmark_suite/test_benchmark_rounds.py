"""The readers of the decoder's round records (benchmark/program_rounds.py
and the metric files over it) on a synthetic `run` and ring."""

import pytest

from aiko_services_tpu.observe import profiler as P
from benchmark import program_rounds, run

MS = 1e-3


def record(seq, rounds, wall_ms, host_ms, gap_ms=0.1, idle_before=False,
           num_steps=4, prefill_tokens=0, t0=0.0):
    phases = dict.fromkeys(P.PHASES, 0.0)
    phases["host_sync"] = (wall_ms - host_ms) * MS
    phases["deliver"] = host_ms * MS
    return (seq, rounds, t0, gap_ms * MS, idle_before, wall_ms * MS,
            *phases.values(), num_steps, 3 if num_steps else 0,
            prefill_tokens, 0)


@pytest.fixture
def ring(monkeypatch):
    """A profiler under the driver's name whose ring the test fills."""
    profiler = P.PhaseProfiler(program_rounds.DECODER)
    records = [
        record(1, 10, 90.0, 2.0),                        # before the window
        record(2, 11, 100.0, 3.0),
        record(3, 12, 101.0, 4.0, prefill_tokens=512),   # its own step: on time
        record(4, 13, 147.0, 1.0),                       # pays for 3's chunk
        record(5, 13, 30.0, 3.0, num_steps=0, prefill_tokens=64),
        record(6, 14, 110.0, 3.0, prefill_tokens=512),   # pays for 5's admit
        record(7, 15, 149.0, 1.0, prefill_tokens=256),   # pays for 6's chunk
        record(8, 16, 60.0, 3.0, num_steps=2),
        record(9, 17, 104.0, 2.0, gap_ms=1900.0),        # the loop stood still
        record(10, 18, 100.0, 2.0, gap_ms=5000.0, idle_before=True),
        record(11, 19, 95.0, 1.0),                       # after the window
    ]
    profiler.ring.extend(records)
    yield profiler


def a_run(before=10, after=18, span="counters"):
    return {span: {"before": {"rounds": before}, "after": {"rounds": after}}}


def test_the_ring_is_cut_by_the_rounds_counter(ring):
    found = program_rounds.rounds(a_run())
    assert [r["seq"] for r in found] == [2, 3, 4, 5, 6, 7, 8, 9, 10]
    assert set(found[0]) == set(P.ROUND_FIELDS)
    traced = program_rounds.rounds(a_run(16, 18, "trace_counters")
                                   | {"counters": {}}, "trace_counters")
    assert [r["seq"] for r in traced] == [9, 10]
    assert program_rounds.rounds({"counters": {}}) is None
    assert program_rounds.rounds(a_run(18, 18)) == []
    # in a traced run the window's rounds end where the profiler starts
    both = a_run() | a_run(16, 18, "trace_counters")
    assert [r["seq"] for r in program_rounds.rounds(both)] == \
        [2, 3, 4, 5, 6, 7, 8]
    assert [r["seq"] for r in program_rounds.rounds(both, "trace_counters")] \
        == [9, 10]
    table = program_rounds.table(both)
    assert table["fields"] == list(P.ROUND_FIELDS)
    assert len(table["window"]["rounds"]) == 7
    assert table["traced"]["mean_wall_ms"] == pytest.approx(102.0)
    assert table["traced"]["mean_gap_ms"] == pytest.approx(3450.0)
    assert program_rounds.table({"counters": {}}) is None


def test_each_reader_on_the_synthetic_ring(ring):
    r = a_run()
    # wall less sync over the nine rounds of the window
    assert program_rounds.host_ms(r) == pytest.approx(
        (3 + 4 + 1 + 3 + 3 + 1 + 3 + 2 + 2) / 9)
    # the stall counts with its gap; the round after an idle decoder does not
    assert program_rounds.longest_ms(r) == pytest.approx(1900.0 + 104.0)
    # commonest num_steps is 4; the rounds AFTER one that prefilled pay
    pays, free = program_rounds.prefill_classes(r)
    assert sorted(pays) == pytest.approx([0.110, 0.147, 0.149])
    assert sorted(free) == pytest.approx([0.100, 0.101, 0.104])
    assert program_rounds.prefill_penalty_ms(r) == pytest.approx(147.0 - 101.0)
    assert program_rounds.prefill_share(r) == pytest.approx(100.0 * 4 / 9)
    for name, expected in (("round_host_ms.chat", 22 / 9),
                           ("round_host_ms.decode", 22 / 9),
                           ("round_longest_ms.chat", 2004.0),
                           ("round_longest_ms.decode", 2004.0),
                           ("prefill_round_penalty_ms.chat", 46.0),
                           ("rounds_with_prefill.chat", 400 / 9)):
        assert run.load_module("layer_metrics", name).read(r) == \
            pytest.approx(expected), name


def test_an_empty_class_reads_zero_and_an_empty_window_nothing(ring):
    no_prefill = a_run(16, 19)            # records 9, 10 and 11: none prefills
    assert program_rounds.prefill_penalty_ms(no_prefill) == 0.0
    assert program_rounds.prefill_share(no_prefill) == 0.0
    empty = a_run(19, 19)
    for read in (program_rounds.host_ms, program_rounds.longest_ms,
                 program_rounds.prefill_penalty_ms,
                 program_rounds.prefill_share):
        assert read(empty) is None
    only_after_idle = a_run(17, 18)
    assert program_rounds.longest_ms(only_after_idle) is None


def test_a_program_without_the_ring_reads_nothing(monkeypatch):
    """The parent of the PR that added the ring: no `round_log`."""
    monkeypatch.delattr(P, "round_log")
    for name in ("round_host_ms.decode", "round_longest_ms.chat",
                 "prefill_round_penalty_ms.chat", "rounds_with_prefill.chat"):
        assert run.load_module("layer_metrics", name).read(a_run()) is None


def test_no_decoder_of_that_name_reads_nothing():
    import gc
    gc.collect()
    if program_rounds.DECODER in P._profilers:
        pytest.skip("an earlier test of this worker left a decoder alive")
    assert program_rounds.rounds(a_run()) is None
