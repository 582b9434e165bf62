# The Gated-DeltaNet hybrid decoder (tests/test_gated_delta_layers.py has
# the suite's sizes and reference) through a DECODER: prefill through admit
# and chunked extend then decode through the pool AND the slot state,
# gathered views and the kernels in the interpreter, a reused slot, the
# pool's geometry, which kernels a decoder takes, and the serving paths
# that refuse.  The case that traces the chunk kernel, emptying the
# builders' caches, comes last.

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import aiko_services_tpu.serving as serving
from aiko_services_tpu import serving_paged
from aiko_services_tpu.serving import ContinuousDecoder
from paged_model_cases import NOT_CARRIED, scan_kernel_interpreted
from test_gated_delta_layers import (CASES, LOGIT_TOLERANCE, SIZES, M,
                                     model_config)

serve, served_gaps = CASES.serve, CASES.served_gaps


def decoder_for(name, kernel=False, **kwargs):
    """`kernel`: the decoder is ASKED for its kernels off the chip
    (`serving.ATTENTION_IMPL`), so both run in the interpreter: the walk of
    the full layers' pool and the recurrence over the live slots' state."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(serving, "ATTENTION_IMPL",
                      "paged_kernel" if kernel else None)
        decoder = CASES.decoder_for(name, prefill_budget=64, **kwargs)
    assert decoder.step_kernel is kernel and decoder._walks_live is kernel
    return decoder


@pytest.fixture(scope="module")
def decoder():
    """The suite's geometry: gathered views and the plain recurrence."""
    return decoder_for("gated-delta")


def test_a_served_token_altered_is_seen(decoder):
    assert CASES.altered_token_gap(decoder) > 100 * LOGIT_TOLERANCE


def test_a_fresh_request_starts_from_zeros_in_a_reused_slot():
    """One slot: a request of 100 + 6 positions, then one of 21 + 11 in the
    slot and the blocks the first gave back.  The second's tokens are the
    reference's to within the tolerance: nothing of the first's state, its
    convolution tail or its rows reaches them."""
    rng = np.random.default_rng(31)
    first = (rng.integers(1, 256, size=100).tolist(), 6)
    second = (rng.integers(1, 256, size=21).tolist(), 11)
    both, stats = serve(decoder_for("reused", slots=1),
                        {"a": first, "b": second})
    assert stats["slot_states_zeroed"] == 2
    for rid, gap in served_gaps({"a": first, "b": second}, both).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)


def test_the_pool_holds_rows_for_the_full_layers_alone(decoder):
    config = model_config()
    pool = decoder.pool
    assert [leaf is not None for leaf in pool.k_pools] == \
        [kind == "full" for kind in config.layer_types]
    assert pool.k_pools[3].shape[1:] == (4, 8, 16)
    assert pool.block_nbytes == 8 * 2 * 4 * 16 * 4        # K and V, float32
    state = decoder.slot_state.arrays
    assert [len(layer) for layer in state] == [2, 2, 2, 0, 2]
    # S lies with its heads side by side: [slots, key side, heads x value]
    assert state[0][0].shape == (4, 8, 6 * 16)
    assert state[0][0].dtype == jnp.float32
    assert state[0][1].shape == (4, 3 * 6 * (8 + 8 + 16))
    assert serving_paged.layer_leaves(config)[3] == ((4, 16, 1), (4, 16, 1))



@pytest.mark.parametrize("impl, sizes, backend, step_kernel, state_kernel", [
    # the interpreter would take any geometry, and nobody asked for it
    (None, SIZES, "cpu", False, True),
    # a chip, heads of 16 and 6 x 16 lanes: no walk, no state kernel
    (None, SIZES, "tpu", False, False),
    ("paged_kernel", SIZES, "cpu", True, True),
    # the published head sizes on a chip: BOTH reasons at once
    (None, SIZES | dict(
        hidden_size=512, num_attention_heads=4, num_key_value_heads=4,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192), "tpu", True,
     True),
    # a head of whole lanes beside a state that is not: the walk alone
    (None, SIZES | dict(hidden_size=512, num_attention_heads=4,
                        num_key_value_heads=4), "tpu", True, False),
], ids=["cpu", "small-heads-on-tpu", "asked", "published-heads-on-tpu",
        "walk-alone"])
def test_the_decoder_takes_the_kernels_for_both_reasons_at_once(
        monkeypatch, impl, sizes, backend, step_kernel, state_kernel):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(serving, "ATTENTION_IMPL", impl)
    config = model_config(sizes)
    weights = jax.eval_shape(
        lambda: M.gated_delta_init(jax.random.PRNGKey(0), config))
    weights = jax.tree.map(lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
                           weights)
    decoder = ContinuousDecoder(
        weights, config, paged_kv=True, kv_block=8, max_slots=2, max_seq=128,
        prefill_buckets=(8, 32), prefill_chunk=32, steps_per_sync=2,
        name=f"which-{backend}-{impl}-{step_kernel}-{state_kernel}")
    assert decoder.step_kernel is step_kernel
    assert decoder._walks_live is step_kernel
    assert bool(decoder._model_kernel) is state_kernel
    assert decoder._attend_widths == (128,)



NOT_CARRIED = NOT_CARRIED | {
    "no-chunk": (dict(prefill_chunk=None), "prefill_chunk must be set"),
    "chunk-not-dividing": (dict(prefill_chunk=24), "divide max_seq")}

@pytest.mark.parametrize("path", NOT_CARRIED)
def test_paths_not_carried_refuse_at_construction(path):
    """By name, as for the other model with slot state: nothing here runs
    another model's code on this one's cache."""
    CASES.refuses_to_build(*NOT_CARRIED[path])


def test_tensor_parallel_weights_refuse_at_construction():
    CASES.refuses_tensor_parallel_weights()


@pytest.mark.parametrize("path", ["drain", "wire-layout", "install",
                                  "disagg-client"])
def test_drain_and_the_kv_wire_refuse_by_name(decoder, path):
    CASES.refuses(decoder, path)


# -- the whole of it, last: the second case empties the builders' caches ---------

@pytest.mark.parametrize("kernel", [False, True],
                         ids=["views-and-recurrence", "kernels-interpreted"])
def test_prefill_then_decode_through_pool_and_state_agrees_with_one_forward(
        decoder, kernel):
    """Six requests over four slots: prompts of 10 and 30 go in by one
    padded admit, 5 by a narrow one, 45 and 77 by chains of 32-token
    extends whose last chunk is padded (each chunk from the state the last
    one left, the full layer reading the chunks before it from the pool),
    64 by two whole chunks; two wait for a slot that another request
    leaves.  All decode 11 tokens; each served token is the reference's
    best at its position to within the tolerance.  `kernel`: the step's
    kernels asked for, and a prompt's pieces through ops/delta_chunk's
    (a decoder of its own: one that has traced its admits keeps them)."""
    rng = np.random.default_rng(7)
    requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), 11)
                for n in (10, 45, 77, 5, 30, 64)}
    with scan_kernel_interpreted(M, kernel):
        served, stats = serve(
            decoder_for("gated-delta-kernels", True) if kernel else decoder,
            requests)
    assert stats["prefill_chunks"] == 7 and stats["prefills"] == 3
    assert stats["slot_states_zeroed"] == 6
    for rid, gap in served_gaps(requests, served).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)
    # four recurrent layers: a state moved for every token a step decoded,
    # every slot's held at every step that ran
    assert stats["gdn_states_moved"] == 4 * stats["tokens_decode"]
    assert stats["gdn_states_held"] % (4 * 4) == 0
    assert 0 < stats["gdn_states_moved"] < stats["gdn_states_held"]
