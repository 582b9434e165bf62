# The hybrid decoder (tests/test_hybrid_sparse_layers.py has the suite's
# sizes and reference) SERVED: prefill through admit and chunked extend
# then decode through the pool AND the slot state, the kernel asked for
# against the plain recurrence, and the serving paths that refuse.  ONE
# geometry compiles here (tests/paged_model_cases.py: the suite's four
# slots, four steps a round), with the plain step and with the kernel's;
# the case that traces the chunk kernel, emptying the builders' caches,
# comes last.  The cases that need other geometries are in
# test_0_slots_hybrid_sparse.py.

import numpy as np
import pytest

import aiko_services_tpu.serving as serving
from paged_model_cases import NOT_CARRIED, scan_kernel_interpreted
from test_hybrid_sparse_layers import CASES, LOGIT_TOLERANCE, M

serve, served_gaps = CASES.serve, CASES.served_gaps


@pytest.fixture(scope="module")
def decoder():
    """The suite's geometry; `kda_recurrent` in the step, as every
    decoder off a chip that is not asked for the kernel."""
    decoder = CASES.decoder_for("hybrid")
    assert decoder._walks_live and decoder.step_kernel is False
    return decoder


def test_a_served_token_altered_is_seen(decoder):
    assert CASES.altered_token_gap(decoder) > 100 * LOGIT_TOLERANCE


def test_the_kernel_asked_for_serves_what_the_plain_recurrence_serves(
        decoder, monkeypatch):
    """The `tiny` decoder as every test builds it (`kda_recurrent`), and
    with the kernel asked for (the interpreter, a head of 16): requests
    that decode side by side and leave at different steps, a chunked
    prompt among them.  Both within the tolerance of the reference, the
    same counts."""
    rng = np.random.default_rng(21)
    requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), new)
                for n, new in ((10, 11), (45, 6), (5, 9))}
    monkeypatch.setattr(serving, "ATTENTION_IMPL", "paged_kernel")
    other = CASES.decoder_for("kernel-form")
    assert other._walks_live and other.step_kernel is True
    (plain, counted), (asked, again) = (serve(each, requests)
                                        for each in (decoder, other))
    for served in (plain, asked):
        for rid, gap in served_gaps(requests, served).items():
            assert gap < LOGIT_TOLERANCE, (rid, gap)
    assert plain == asked
    for name in M.HYBRID_COUNTERS:
        assert counted[name] == again[name], name


# -- the paths slot state is not carried through refuse, by name -----------------

NOT_CARRIED = NOT_CARRIED | {
    "no-chunk": (dict(prefill_chunk=None), "prefill_chunk must be set"),
    "chunk-not-dividing": (dict(prefill_chunk=24), "divide max_seq"),
    "block-of-half-a-tile": (dict(kv_block=4),
                             "kv_block must be a multiple of 8")}

@pytest.mark.parametrize("path", NOT_CARRIED)
def test_paths_not_carried_refuse_at_construction(path):
    CASES.refuses_to_build(*NOT_CARRIED[path])


def test_tensor_parallel_weights_refuse_at_construction():
    CASES.refuses_tensor_parallel_weights()


@pytest.mark.parametrize("path", ["drain", "wire-layout", "install",
                                  "disagg-client"])
def test_drain_and_the_kv_wire_refuse_by_name(decoder, path):
    CASES.refuses(decoder, path)


# -- the whole of it, last: the second case empties the builders' caches ---------

@pytest.mark.parametrize("scan", [False, True],
                         ids=["chunked-by-xla", "chunk-kernel-interpreted"])
def test_prefill_then_decode_through_pool_and_state_agrees_with_one_forward(
        decoder, scan):
    """Six requests over four slots: prompts of 10 and 30 go in by one
    padded admit, 5 by a narrow one, 45 and 77 by chains of 32-token
    extends whose last chunk is padded, 64 by two whole chunks; two wait
    for a slot that another request leaves.  All decode 11 tokens, past
    16 positions, so every step chooses groups; each served token is the
    reference's best at its position to within the tolerance.  `scan`:
    the admits' and the extends' KDA layers through ops/delta_chunk's
    kernel (ISSUE 41), as a chip runs them (through a decoder of its own:
    one that has traced its admits keeps them)."""
    rng = np.random.default_rng(7)
    requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), 11)
                for n in (10, 45, 77, 5, 30, 64)}
    with scan_kernel_interpreted(M, scan):
        served, stats = serve(
            CASES.decoder_for("hybrid-scan") if scan else decoder, requests)
    assert stats["prefill_chunks"] == 7 and stats["prefills"] == 3
    assert stats["slot_states_zeroed"] == 6
    for rid, gap in served_gaps(requests, served).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)
    # every pair of the whole model lands on a held expert
    assert stats["moe_pairs_here"] == stats["moe_pairs_routed"] > 0
    assert 0 < stats["moe_layer_steps"] <= 3 * stats["steps"]
    # the sparse layer attended a part of what was live: at most 3 groups,
    # the open group and the round's rows a slot and step
    assert 0 < stats["dsa_positions_attended"] < \
        0.6 * stats["dsa_positions_live"]
