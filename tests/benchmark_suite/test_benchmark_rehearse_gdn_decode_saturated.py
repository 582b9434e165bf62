"""`--rehearse` end to end for gdn_decode_saturated at its tiny preset (one
file a cell, so that the cells rehearse side by side under the test
workers): the Gated-DeltaNet hybrid decoder (6 recurrent heads of [8, 16]
whose state is a slot's, beside a K/V pool in one layer of five) through its
own driver, weights and reference, a backlog that keeps every slot live."""

import pytest

from rehearsal import rehearse

CELL = "gdn_decode_saturated"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace, capsys):
    result = rehearse(CELL, trace, capsys)
    if trace:
        metrics = result["metrics"]
        # counts keep their values in a rehearsal: outputs of 8 tokens, so
        # a slot waits for its next request a good part of the time, and
        # the states moved are the slots that decoded, layer for layer
        occupied = metrics["slot_occupancy.gdn"]["value"]
        assert 20.0 < occupied <= 100.0
        assert metrics["gdn_state_moved_share"]["value"] == \
            pytest.approx(occupied)
        assert "gdn_step_state_ms" not in metrics      # a device's time
        assert "gdn_state_roofline" not in metrics


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys):
    """The rest of a run with the timed path broken underneath: every
    token comes out one higher than the step chose it."""
    def alter(session):
        session.break_token = lambda request_id, token: (token + 1) % 256

    assert rehearse(CELL, 0, capsys, hook=alter,
                    expect_correct=False)["attempted"] > 0


def test_lower_precision_serves_other_weights(capsys):
    """`--lower-precision 1` at the tiny preset: the driver serves
    float8-rounded weights, which the reference (sound weights) sees."""
    import json
    from benchmark import run
    code = run.main(["--workload", CELL, "--seed", str(2**31 + 17),
                     "--seconds", "3", "--trace", "0", "--rehearse",
                     "--lower-precision", "1"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    assert json.loads(lines[-1])["correct"] is False, lines[-8:]
