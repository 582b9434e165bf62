# The sparse grouped-query decoder (tests/test_sparse_gqa_layers.py has the
# suite's sizes and reference) SERVED: prefill through admit and chunked
# extend then decode through the pool on both sides of `topk` and across
# it, slots beyond one group, a reused slot, and the serving paths that
# refuse.  The geometries that compile here (tests/paged_model_cases.py):
# the suite's four slots with the plain step and with the kernel's, six
# slots, one slot, and chunks of 16.

import numpy as np
import pytest

import aiko_services_tpu.serving as serving
from aiko_services_tpu.ops.paged_attention import walk_positions
from paged_model_cases import NOT_CARRIED
from test_sparse_gqa_layers import CASES, LOGIT_TOLERANCE, M

serve, served_gaps = CASES.serve, CASES.served_gaps


def decoder_for(name, kernel=False, **kwargs):
    """`kernel`: the step as a chip's decoder builds it (the walk with the
    chosen positions as its mask, here in the interpreter), asked for by
    name as off a chip it must be; else the plain form."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(serving, "ATTENTION_IMPL",
                      "paged_kernel" if kernel else None)
        decoder = CASES.decoder_for(name, **kwargs)
    assert decoder._walks_live and decoder.step_kernel is kernel
    return decoder


@pytest.fixture(scope="module")
def decoder():
    """The suite's geometry, the plain step."""
    return decoder_for("sparse-gqa")


def _pool_reads(prompt: int, new: int, kernel: bool) -> int:
    """What one layer's steps read of the pool for a request of `prompt`
    tokens that decodes `new` - 1 times in rounds of four steps: a round's
    steps walk the blocks of 8 that were live as the round began (the
    kernel), or gather the one piece that a table of 128 positions is."""
    steps = new - 1
    return sum(
        min(4, steps - first) *
        (int(walk_positions(np.int32(prompt + first), 8)) if kernel else 128)
        for first in range(0, steps, 4))


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_prefill_then_decode_through_the_pool_agrees_with_one_forward(
        decoder, kernel):
    """Seven requests over four slots: prompts of 10 and 30 go in by one
    padded admit, 5 and 3 by a narrow one, 45 and 77 by chains of 32-token
    extends whose last chunk is padded, 64 by two whole chunks; three wait
    for a slot that another request leaves.  All decode 11 tokens: 3 stays
    under topk 16 (every step attends everything), 5 ends AT it, 10
    crosses it while decoding, the others are past it from the start;
    each served token is the reference's best at its position to within
    the tolerance."""
    rng = np.random.default_rng(7)
    requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), 11)
                for n in (10, 45, 77, 5, 30, 64, 3)}
    served, stats = serve(
        decoder_for("sparse-gqa-kernel", True) if kernel else decoder,
        requests)
    assert stats["prefill_chunks"] == 7 and stats["prefills"] == 4
    for rid, gap in served_gaps(requests, served).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)
    # every pair of the whole model lands on a held expert
    assert stats["moe_pairs_here"] == stats["moe_pairs_routed"] > 0
    assert 0 < stats["moe_layer_steps"] <= 2 * stats["steps"]
    # what was attended: everything up to 16 positions, 16 past them
    assert 0 < stats["dsa_positions_attended"] < \
        0.6 * stats["dsa_positions_live"]
    # what the steps READ of the pool to attend that: every live block of
    # a slot that decodes (the kernel), the table's one piece (plain);
    # the round's own rows come from nowhere
    assert stats["dsa_rows_fetched"] == 2 * sum(
        _pool_reads(len(prompt), new, kernel)
        for prompt, new in requests.values())
    assert stats["dsa_rows_fetched"] > stats["dsa_positions_attended"]
    # r3's ten steps, r5's ten and r10's six (positions 10 to 15), in
    # each of two layers (a prompt's first token comes from its prefill)
    assert stats["dsa_slot_steps_dense"] == 2 * (10 + 10 + 6)


def test_slots_beyond_one_group_are_served_group_by_group():
    """Six slots are two groups of `_SLOT_GROUP` (the second padded with
    rows that drop): the slots that decode are taken first, so a round
    with five live computes both groups, one with two live the first
    alone; every token is the reference's best either way."""
    assert M._SLOT_GROUP == 4
    rng = np.random.default_rng(12)
    requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), 4 + n % 5)
                for n in (9, 21, 33, 50, 62, 18, 40)}
    served, stats = serve(decoder_for("two-groups", slots=6), requests)
    for rid, gap in served_gaps(requests, served).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)
    # a step a generated token after the first, in each of two layers
    assert stats["dsa_slot_steps_dense"] == 2 * 7        # r9: positions 9-15
    assert stats["dsa_positions_live"] == 2 * sum(
        n + j for n in (9, 21, 33, 50, 62, 18, 40)
        for j in range(1, 4 + n % 5))


def test_the_counters_of_long_contexts_alone_say_nothing_was_dense(decoder):
    rng = np.random.default_rng(9)
    requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), 6)
                for n in (40, 70)}
    _, stats = serve(decoder, requests)
    assert stats["dsa_slot_steps_dense"] == 0
    # five steps a request in two layers, 16 positions each
    assert stats["dsa_positions_attended"] == 2 * 2 * 5 * 16


def test_a_served_token_altered_is_seen(decoder):
    assert CASES.altered_token_gap(decoder) > 100 * LOGIT_TOLERANCE


def test_chunked_extend_equals_one_shot(decoder):
    """77 tokens through chunks of 32 and of 16: the same logits' choice,
    whatever the pieces the prefix was read in."""
    rng = np.random.default_rng(10)
    requests = {"c": (rng.integers(1, 256, size=77).tolist(), 5)}
    wide, _ = serve(decoder, requests)
    narrow, _ = serve(decoder_for("chunks-16", chunk=16, buckets=(8, 16)),
                      requests)
    assert wide == narrow


def test_a_slot_reused_reads_nothing_the_longer_request_left():
    """One slot: a request of 90 positions, then one of 20 in the same
    blocks; the second's indexer sees stale keys past its length and
    must choose none of them."""
    rng = np.random.default_rng(11)
    requests = {"long": (rng.integers(1, 256, size=90).tolist(), 4),
                "short": (rng.integers(1, 256, size=20).tolist(), 8)}
    served, _ = serve(decoder_for("reused", slots=1), requests)
    for rid, gap in served_gaps(requests, served).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)


# -- the paths three leaves are not carried through refuse, by name --------------

@pytest.mark.parametrize("path", NOT_CARRIED)
def test_paths_not_carried_refuse_at_construction(path):
    CASES.refuses_to_build(*NOT_CARRIED[path])


def test_tensor_parallel_weights_refuse_at_construction():
    CASES.refuses_tensor_parallel_weights()


@pytest.mark.parametrize("path", ["drain", "wire-layout", "install",
                                  "disagg-client"])
def test_drain_and_the_kv_wire_refuse_by_name(decoder, path):
    CASES.refuses(decoder, path)
