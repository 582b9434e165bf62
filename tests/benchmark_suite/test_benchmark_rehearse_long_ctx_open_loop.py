"""`--rehearse` end to end for long_ctx_open_loop at its tiny preset (one file
a cell, so that the cells rehearse side by side under the test workers): the
sparse grouped-query decoder (K, V and an indexer key a token, the exact top
16 positions chosen a query) through its own driver, weights and reference,
holding 4 of 8 experts behind a plain router."""

import pytest

from rehearsal import rehearse

CELL = "long_ctx_open_loop"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace, capsys):
    result = rehearse(CELL, trace, capsys)
    if trace:
        metrics = result["metrics"]
        # the counters are counts, so a rehearsal keeps their values: half
        # the experts are held behind a plain router, so about half of the
        # pairs land here; prompts of 8-100 positions against 16 attended
        # at most
        assert 35.0 < metrics["moe_pairs_here_share.longctx"]["value"] < 65.0
        assert 0.0 < metrics["moe_experts_hit_share.longctx"]["value"] <= 100
        assert 10.0 < metrics["dsa_attended_share.longctx"]["value"] < 90.0
        # single rows are fetched: never more than were attended
        assert 0.5 < metrics["dsa_fetch_amplification"]["value"] <= 1.0
        assert "dsa_step_select_ms" not in metrics     # a device's time


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
