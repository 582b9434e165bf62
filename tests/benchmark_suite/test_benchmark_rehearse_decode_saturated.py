"""`--rehearse` end to end for decode_saturated at its tiny preset (one file a cell,
so that the cells rehearse side by side under the test workers)."""

import pytest

from rehearsal import rehearse

CELL = "decode_saturated"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace, capsys):
    rehearse(CELL, trace, capsys)


def test_a_compile_inside_the_window_is_not_correct(capsys):
    def forget_the_programs(session):
        # as if warm-up had left every shape out
        import jax
        session.decoder._prefill_fns.clear()
        jax.clear_caches()

    rehearse(CELL, 0, capsys, hook=forget_the_programs, expect_correct=False)


def test_the_reference_sample_takes_a_request_from_every_slot():
    """A fault confined to one slot has to be in the sample: the longest
    request, then slot after slot, drawn from the seed."""
    import numpy as np
    from benchmark import run

    session = object.__new__(run.load_module(
        "drivers", "continuous_decoder").Session)
    session.say = lambda message: None
    names = [f"r{i}" for i in range(40)]
    session.prompts = {rid: [1] * (10 + i) for i, rid in enumerate(names)}
    session.served = {rid: [2, 3] for rid in names}
    session.slot_of = {rid: i % 8 for i, rid in enumerate(names)}
    records = {rid: {"done": 1.0} for rid in names}
    records["r3"]["done"] = None                    # never finished
    picked = [session.samples(records, 9, np.random.default_rng(seed))
              for seed in (1, 2)]
    for sample in picked:
        ids = [entry["id"] for entry in sample]
        assert ids[0] == "r39" and "r3" not in ids and len(set(ids)) == 9
        assert {session.slot_of[rid] for rid in ids[1:]} == set(range(8))
    assert picked[0] != picked[1]
    assert len(session.samples(records, 100, np.random.default_rng(1))) == 39
