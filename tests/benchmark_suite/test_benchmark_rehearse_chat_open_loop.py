"""`--rehearse` end to end for chat_open_loop at its tiny preset (one file a cell,
so that the cells rehearse side by side under the test workers)."""

import pytest

from rehearsal import rehearse

CELL = "chat_open_loop"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace, capsys):
    rehearse(CELL, trace, capsys)


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys):
    """The rest of a run with the timed path broken underneath: every
    token comes out one higher than the step chose it."""
    def alter(session):
        session.break_token = lambda request_id, token: (token + 1) % 256

    assert rehearse(CELL, 0, capsys, hook=alter,
                    expect_correct=False)["attempted"] > 0
