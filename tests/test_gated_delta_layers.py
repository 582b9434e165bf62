# The Gated-DeltaNet hybrid decoder (ISSUE 40: recurrent layers with one gate
# a head, 6 heads of [8, 16] here, whose state is a SLOT's, beside full
# layers whose K and V the shared paged kernel walks) at a small size on the
# CPU in float32, the layer's own functions, no decoder: the model against
# the benchmark's plain reference (benchmark/reference/gated_delta_lm.py:
# the recurrence token by token, plain softmax, precision "highest"), the
# published switch, a slot that decodes nothing.  This file holds the
# suite's SIZES and its `CASES` (tests/paged_model_cases.py); the cases
# that build or serve through a decoder are in
# test_0_served_gated_delta.py.
#
# Comparisons are of LOGITS or states, never of sampled tokens.  Each
# tolerance states its reason.

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_model_cases import PagedModelCases

from aiko_services_tpu.models import gated_delta as M
from benchmark import weights_gated_delta as W
from benchmark.reference import gated_delta_lm as R

SEED = 2**31 + 41
LIN, FULL = "linear_attention", "full_attention"
# the published keys at a size a test holds: a period and one more layer,
# 6 recurrent heads (no multiple of 8) of [8, 16] (unequal sides), 4 full
# heads of 16
SIZES = dict(
    hidden_size=64, vocab_size=256, intermediate_size=128,
    num_hidden_layers=5, layer_types=[LIN, LIN, LIN, FULL, LIN],
    num_attention_heads=4, num_key_value_heads=4,
    linear_num_key_heads=6, linear_num_value_heads=6,
    linear_key_head_dim=8, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
    rope_parameters={"rope_theta": None}, rms_norm_eps=1e-6)
# float32 against float32 at "highest": what is left is the order of the
# sums (chunked against one-token recurrence, a walk against one softmax),
# a few float32 ulps of logits whose spread is 1: measured 4e-5 at most.
# bfloat16 anywhere reads 1e-2 and more.
LOGIT_TOLERANCE = 2e-4


CASES = PagedModelCases(
    "gated_delta_decoder", W,
    lambda tokens, sizes, seed: R.forward_logits(tokens[None], sizes, seed,
                                                 jnp.float32)[0],
    SIZES, SEED)
model_config, reference_logits = CASES.model_config, CASES.reference_logits
TOKENS = np.random.default_rng(0).integers(1, 256, size=90)


def test_seeded_weights_have_the_programs_layout():
    assert model_config() == M.GATED_DELTA_PRESETS["tiny"]
    CASES.has_the_layout_of(M.gated_delta_init)


def test_full_forward_agrees_with_the_reference():
    """90 tokens: a chunk of 64 and a padded one through every recurrent
    layer, beta up to 2, one full layer between them."""
    with jax.default_matmul_precision("highest"):
        gap, spread = CASES.forward_gap(M.gated_delta_forward, TOKENS)
    assert spread > 0.5                         # logits of spread ~1
    assert gap < LOGIT_TOLERANCE


def test_bfloat16_would_fail():
    gap, _ = CASES.forward_gap(M.gated_delta_forward, TOKENS, jnp.bfloat16)
    assert gap > 10 * LOGIT_TOLERANCE


def test_the_reference_reads_the_published_switches():
    """`linear_allow_neg_eigval` false is another function (beta a plain
    sigmoid), in the reference as in the program."""
    tokens = np.random.default_rng(1).integers(1, 256, size=40)
    plain = PagedModelCases(
        CASES.driver, W, CASES.reference_forward,
        SIZES | {"linear_allow_neg_eigval": False}, SEED)
    assert np.abs(plain.reference_logits(tokens) -
                  reference_logits(tokens)).max() > 0.05
    with jax.default_matmul_precision("highest"):
        gap, _ = plain.forward_gap(M.gated_delta_forward, tokens)
    assert gap < LOGIT_TOLERANCE


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["recurrence", "kernel-interpreted"])
def test_a_slot_that_does_not_decode_keeps_its_states_bits(kernel):
    """One layer's token mixing in the step over three slots of which the
    middle one decodes nothing: its state S and its convolution tail come
    back bit for bit, the others' change."""
    config = model_config()
    layer = CASES.params["layers"][1]
    key = jax.random.PRNGKey(5)
    state = tuple(jax.random.normal(jax.random.fold_in(key, n),
                                    (3,) + shape).astype(dtype)
                  for n, (shape, dtype) in enumerate(config.slot_state[1]))
    active = jnp.asarray([True, False, True])
    lengths = jnp.zeros((3,), jnp.int32)
    x = jax.random.normal(jax.random.fold_in(key, 9), (3, 1, 64))
    attend = M._step_attention(kernel)
    out, _, after, counted = attend(
        None, layer, config, x, None, None, [], None, [], lengths, lengths,
        0, active, state, active)
    for before, left in zip(state, after):
        assert np.array_equal(np.asarray(left)[1], np.asarray(before)[1])
        assert not np.array_equal(np.asarray(left)[0], np.asarray(before)[0])
    assert np.asarray(counted).tolist() == [2, 3]
    # the live slots' outputs are the other form's
    other, _, left, _ = M._step_attention(not kernel)(
        None, layer, config, x, None, None, [], None, [], lengths, lengths,
        0, active, state, active)
    live = np.asarray(active)
    assert np.abs(np.asarray(out - other)[live]).max() < 1e-5
    assert np.abs(np.asarray(after[0] - left[0])[live]).max() < 1e-5

