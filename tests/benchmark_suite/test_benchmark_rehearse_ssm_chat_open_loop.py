"""`--rehearse` end to end for ssm_chat_open_loop at its tiny preset (one
file a cell, so that the cells rehearse side by side under the test
workers): the Mamba-2 hybrid decoder (6 state-space heads of [16, 8] that
share B and C, whose state is a slot's, beside a K/V pool in two layers of
eight) through its own driver, weights and reference, Poisson arrivals of
short chat lengths."""

import pytest

from rehearsal import rehearse

CELL = "ssm_chat_open_loop"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace, capsys):
    result = rehearse(CELL, trace, capsys)
    if trace:
        metrics = result["metrics"]
        # counts keep their values in a rehearsal; a device's time or
        # share is not written under a metric's name
        assert metrics["prefill_prefix_depth"]["value"] >= 0.0
        assert "decode_step_device_ms.ssmchat" not in metrics
        assert "ssm_state_roofline" not in metrics


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
