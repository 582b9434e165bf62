# The hybrid decoder (tests/test_hybrid_sparse_layers.py has the suite's
# sizes and reference) SERVED at the geometries that a case's own check
# needs beside the suite's (test_0_served_hybrid_sparse.py): a step a round,
# where the sparse step's counts are exact; one slot, which the second
# request reuses with the first's blocks; and buckets and chunks of other
# sizes, after which a slot's state is the same.

import jax
import numpy as np
import pytest

from test_hybrid_sparse_layers import CASES, LOGIT_TOLERANCE

serve, served_gaps = CASES.serve, CASES.served_gaps
KDA_LAYERS = 3


@pytest.fixture(scope="module")
def step_a_round():
    return CASES.decoder_for("hybrid-counted", steps_per_sync=1)


@pytest.fixture(scope="module")
def one_slot():
    return CASES.decoder_for("hybrid-one-slot", slots=1)


@pytest.mark.parametrize("length, new", [(40, 9), (6, 7)],
                         ids=["three-groups-a-step", "one-then-two-groups"])
def test_the_step_attends_the_chosen_groups_and_the_open_one(
        step_a_round, length, new):
    """One request decoding a step a round: at position p the sparse layer
    takes min(3, p // 4) groups from the pool, attends their 4 rows each
    and its open group's p % 4 + 1 positions, of p + 1 live, and fetches a
    whole tile of 8 rows a group taken (ISSUE 37)."""
    prompt = np.random.default_rng(9).integers(1, 256, size=length).tolist()
    _, counted = serve(step_a_round, {"a": (prompt, new)})
    positions = range(length, length + new - 1)   # the admit gives the first
    taken = [min(3, p // 4) for p in positions]
    assert counted["dsa_positions_live"] == sum(p + 1 for p in positions)
    assert counted["dsa_positions_attended"] == sum(
        4 * groups + p % 4 + 1 for groups, p in zip(taken, positions))
    assert counted["dsa_rows_fetched"] == 8 * sum(taken)


def test_the_step_counts_the_states_it_must_move_and_those_it_holds(
        step_a_round):
    """Four slots, two requests that decode 9 and 4 tokens, a step a
    round: a KDA layer moves the state of the slots that decode in a
    step, and holds every slot's."""
    rng = np.random.default_rng(22)
    _, counted = serve(step_a_round, {
        rid: (rng.integers(1, 256, size=n).tolist(), new)
        for rid, (n, new) in {"a": (12, 10), "b": (20, 5)}.items()})
    assert {"kda_states_moved", "kda_states_held"} <= set(counted)
    # the first token of each comes from its admit: 9 + 4 slot-steps
    assert counted["useful_steps"] == 13
    assert counted["kda_states_moved"] == KDA_LAYERS * 13
    assert counted["kda_states_held"] == KDA_LAYERS * 4 * counted["steps"]
    assert 9 <= counted["steps"] <= 13



def test_a_reused_slots_step_reads_nothing_the_longer_request_left(one_slot):
    """One slot: a request of 115 + 6 positions, which fills every block
    of the pool, then one of 21 + 11 in blocks the first gave back.  The
    tiles the second fetches hold the first's rows in their other halves
    and past its length; its tokens are the reference's to within the
    tolerance."""
    rng = np.random.default_rng(31)
    first = (rng.integers(1, 256, size=115).tolist(), 6)
    second = (rng.integers(1, 256, size=21).tolist(), 11)
    both, counted = serve(one_slot, {"a": first, "b": second})
    assert counted["slot_states_zeroed"] == 2
    leaf = np.asarray(one_slot.pool.k_pools[3])
    # whichever blocks the second took, the first had written them
    assert (np.abs(leaf[1:]).max(axis=(1, 2, 3)) > 0.05).all()
    for rid, gap in served_gaps({"a": first, "b": second}, both).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)


def test_a_slot_reused_after_retirement_starts_from_zero(one_slot):
    """One slot: the second request is served in the slot the first left,
    by a chunked prefill (whose first chunk starts from zeros) and by an
    admit; its tokens are those it gets alone."""
    rng = np.random.default_rng(12)
    first = (rng.integers(1, 256, size=50).tolist(), 6)
    for n in (41, 9):
        second = (rng.integers(1, 256, size=n).tolist(), 9)
        alone, _ = serve(one_slot, {"b": second})
        both, counted = serve(one_slot, {"a": first, "b": second})
        assert counted["slot_states_zeroed"] == 2
        assert both["b"] == alone["b"]
        assert max(served_gaps({"b": second}, both).values()) < \
            LOGIT_TOLERANCE


# -- the state a slot holds after a prefill --------------------------------------

def _state_after_prefill(prompt, name, **kwargs):
    """The slot state and the first token after `prompt` alone has been
    prefilled (no decode step yet: it asks for one token)."""
    decoder = CASES.decoder_for(name, **kwargs)
    served, _ = serve(decoder, {"a": (prompt, 1)})
    return jax.tree.map(lambda leaf: np.asarray(leaf[0]),
                        decoder.slot_state.arrays), served["a"], decoder


def _assert_states_agree(one, other):
    # S of spread ~0.5, tails of spread ~1, key sums ~1: float32 sums in
    # another order (chunks of other sizes)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(other)):
        assert a.shape == b.shape and np.abs(a - b).max() < 2e-5


def test_chunked_extend_equals_one_shot():
    """A prompt of 62 by ONE admit (a bucket of 64) and by four chunks of
    16 (the last padded): the state a slot holds after it, the rows the
    pool holds, the first token."""
    prompt = np.random.default_rng(10).integers(1, 256, size=62).tolist()
    whole, first, one = _state_after_prefill(prompt, "oneshot",
                                             buckets=(8, 64), chunk=64)
    assert one.stats["prefills"] == 1 and not one.stats["prefill_chunks"]
    pieces, again, many = _state_after_prefill(prompt, "chunked",
                                               buckets=(8, 16), chunk=16)
    assert many.stats["prefill_chunks"] == 4 and not many.stats["prefills"]
    assert list(first) == list(again)
    _assert_states_agree(whole, pieces)
    assert np.abs(whole[0][0]).max() > 0.05       # a state that is not zero
    assert np.abs(whole[3][0]).max() > 0.05       # 62 % 4 = 2 keys summed
    for side in ("k_pools", "v_pools"):
        a = np.asarray(getattr(one.pool, side)[3])
        b = np.asarray(getattr(many.pool, side)[3])
        count = 62 * a.shape[2] // 8              # whole rows of the leaf
        flat_a = a[one._tables_np[0]].reshape(-1, a.shape[-1])[:count]
        flat_b = b[many._tables_np[0]].reshape(-1, b.shape[-1])[:count]
        assert np.abs(flat_a - flat_b).max() < 1e-5, side


def test_a_padded_admit_bucket_leaves_state_as_the_unpadded_run_does():
    """20 tokens in a bucket of 32 (12 positions of padding that the scan
    and the convolution tail must pass over) and in a bucket of 20."""
    prompt = np.random.default_rng(11).integers(1, 256, size=20).tolist()
    padded, first, _ = _state_after_prefill(prompt, "padded",
                                            buckets=(8, 32))
    exact, again, _ = _state_after_prefill(prompt, "exact",
                                           buckets=(8, 20), chunk=32)
    assert list(first) == list(again)
    _assert_states_agree(padded, exact)
    # and it is the state of 20 tokens, not of 32: the same prompt with 12
    # more tokens behind it leaves another
    longer, _, _ = _state_after_prefill(prompt + [7] * 12, "longer",
                                        buckets=(8, 32))
    assert np.abs(longer[0][0] - padded[0][0]).max() > 1e-3
