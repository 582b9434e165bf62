# The hybrid decoder (ISSUE 33: KDA slot state beside a sparse-selected latent
# pool, hyper-connected streams, held experts with a correction bias) at a
# small size on the CPU in float32, the layer's own functions, no decoder:
# the model against the benchmark's plain reference
# (benchmark/reference/hybrid_sparse_lm.py: the one-token recurrence,
# expanded keys and values, experts as a loop, precision "highest"), the
# chunked scan against the recurrence, Sinkhorn, the choice of groups.
# This file holds the suite's SIZES and its `CASES`
# (tests/paged_model_cases.py); the sparse step over a pool, the experts'
# share and the geometry are in test_hybrid_sparse_step.py, the cases that
# serve through a decoder in test_0_served_hybrid_sparse.py, the step's
# kernels in test_0_kernels_hybrid_sparse.py.
#
# Comparisons are of LOGITS, states or attention outputs, never of sampled
# tokens.  Each tolerance states its reason.

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_model_cases import PagedModelCases

from aiko_services_tpu.models import hybrid_sparse as M
from benchmark import weights_hybrid_sparse as W
from benchmark.reference import hybrid_sparse_lm as R

SEED = 2**31 + 29
LIN, DSA = "linear_attention", "deepseek_sparse_attention"
# every mechanism of the published file at a size a test holds: KDA + dense
# MLP, two KDA layers and one sparse-attention layer with 8 experts top 2
# (all held), 2 KDA heads of 16, 4 MLA heads of 16 over a latent of 32, 8
# indexer heads of 16 (rotary on 8 lanes), groups of 4 and 16 positions
# attended at most, 4 streams
SIZES = dict(
    hidden_size=64, vocab_size=256, num_hidden_layers=4,
    first_k_dense_replace=1, layer_types=[LIN, LIN, LIN, DSA],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
    linear_attn_config=dict(num_heads=2, head_dim=16,
                            short_conv_kernel_size=4, gate_lower_bound=-5),
    assumed_sizes=dict(kda_gate_rank=8, index_rope_head_dim=8,
                       index_rope_theta=10000),
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_head_dim=16, qk_rope_head_dim=0, v_head_dim=16,
    index_n_heads=8, index_head_dim=16, index_topk=16, index_kpool=4,
    index_kpool_compress=True, index_kpool_always_select_tail=True,
    indexer_rope_interleave=True, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=2, routed_scaling_factor=2.5, swiglu_limit=10,
    scoring_func="sigmoid", norm_topk_prob=True, topk_method="noaux_tc",
    n_group=1, mhc=True, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    rms_norm_eps=1e-5)
# float32 against float32 at "highest": what is left is the order of the
# sums (chunked against one-token recurrence, absorbed against expanded,
# online against one softmax, tiles against a loop over experts), a few
# float32 ulps of logits whose spread is 1: measured 2e-5 at most.
# bfloat16 anywhere reads 1e-2 and more.
LOGIT_TOLERANCE = 2e-4


CASES = PagedModelCases(
    "hybrid_sparse_decoder", W,
    lambda tokens, sizes, seed: R.forward_logits(tokens, sizes, seed,
                                                 jnp.float32), SIZES, SEED)
model_config, reference_logits = CASES.model_config, CASES.reference_logits
TOKENS = np.random.default_rng(0).integers(1, 256, size=90)


def test_seeded_weights_have_the_programs_layout():
    assert model_config() == M.HYBRID_SPARSE_PRESETS["tiny"]
    CASES.has_the_layout_of(M.hybrid_sparse_init)


def test_full_forward_agrees_with_the_reference():
    """90 tokens: 22 groups where a query attends 3 and its own, so the
    choice of groups is in every later logit."""
    gap, spread = CASES.forward_gap(M.hybrid_sparse_forward, TOKENS)
    assert spread > 0.5                         # logits of spread ~1
    assert gap < LOGIT_TOLERANCE


def test_bfloat16_would_fail():
    gap, _ = CASES.forward_gap(M.hybrid_sparse_forward, TOKENS, jnp.bfloat16)
    assert gap > 10 * LOGIT_TOLERANCE


# -- the kernels -----------------------------------------------------------------

def _kda_inputs(key, a, t, h, d, strong):
    ks = jax.random.split(key, 6)
    unit = lambda z: z / jnp.linalg.norm(z, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (a, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (a, t, h, d)))
    v = jax.random.normal(ks[2], (a, t, h, d))
    g = -5.0 * jax.nn.sigmoid(
        jax.random.normal(ks[3], (a, t, h, d)) * 3 + (4.0 if strong else -3.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (a, t, h)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (a, h, d, d))


def _recurrence(q, k, v, g, beta, state):
    def one(state, xs):
        out, state = M.kda_recurrent(*xs, state)
        return state, out
    state, out = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


@pytest.mark.parametrize("tokens, strong", [
    (200, False), (200, True), (64, True), (40, False), (7, True)],
    ids=["slow-decay", "decay-to-e-5-a-token", "one-chunk", "padded",
         "shorter-than-a-sub-block"])
def test_the_chunked_scan_equals_the_recurrence(tokens, strong):
    """The WY form over chunks of 64 (sub-blocks of 16) against one token
    at a time, from a state that is not zero.  With decays near e^-5 a
    token the cumulative decay of a chunk is e^-320: every exponent the
    chunked form takes is a difference that is <= 0, so nothing overflows
    and what underflows is the limit.  Outputs of spread ~0.3: float32
    sums in another order."""
    q, k, v, g, beta, state = _kda_inputs(jax.random.PRNGKey(tokens), 2,
                                          tokens, 2, 16, strong)
    want, want_state = _recurrence(q, k, v, g, beta, state)
    got, got_state = M.kda_chunked(q, k, v, g, beta, state)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    assert np.abs(np.asarray(got_state - want_state)).max() < 2e-5


def test_a_position_that_is_not_live_leaves_the_state_as_it_was():
    """beta = 0 and g = 0 past a true length: the state after 37 live
    tokens of a block of 64 is the state after a block of 37."""
    q, k, v, g, beta, state = _kda_inputs(jax.random.PRNGKey(1), 1, 64, 2,
                                          16, False)
    live = (jnp.arange(64) < 37)[None]
    _, padded = M.kda_chunked(q, k, v, g * live[..., None, None],
                              beta * live[..., None], state)
    _, short = M.kda_chunked(q[:, :37], k[:, :37], v[:, :37], g[:, :37],
                             beta[:, :37], state)
    assert np.abs(np.asarray(padded - short)).max() < 1e-5


def test_sinkhorn_gives_unit_row_and_column_sums():
    config = model_config()
    streams = jax.random.normal(jax.random.PRNGKey(2), (50, 4, 64)) * 3
    hc = CASES.params["layers"][1]["hc_attn"]
    pre, post, res = M.mhc_maps(hc, config, streams)
    # twenty rounds end on the columns: those are exact, the rows settled
    assert np.abs(np.asarray(res.sum(axis=-1)) - 1).max() < 1e-3
    assert np.abs(np.asarray(res.sum(axis=-2)) - 1).max() < 1e-6
    assert (np.asarray(res) > 0).all()
    assert (0 < np.asarray(pre)).all() and (np.asarray(pre) < 1).all()
    assert (0 < np.asarray(post)).all() and (np.asarray(post) < 2).all()
    # the mappings differ a token and a stream: they are not a constant
    assert np.asarray(res).std(axis=0).min() > 1e-3
    theirs = R.stream_maps(hc, streams, SIZES)
    for ours, other in zip((pre, post, res), theirs):
        assert np.abs(np.asarray(ours) - np.asarray(other)).max() < 1e-5


def test_program_and_reference_choose_the_same_groups():
    """One sparse layer over 96 positions (24 groups, 3 chosen and the open
    one): the program's masked absorbed attention against the reference's
    expanded softmax over ITS chosen positions.  A group chosen otherwise
    moves the output by its whole weight, far beyond the tolerance; with
    the choice widened to every group the output is another."""
    config = model_config()
    layer = CASES.params["layers"][3]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 96, 64))
    cos, sin = M.rope_tables(config)
    live = jnp.ones((1, 96), bool)
    ours, rows, pooled, left = M._dsa_block(layer, config, x, cos, sin,
                                            jnp.int32(0), live)
    with jax.default_matmul_precision("highest"):
        theirs, share = R.sparse_attention(layer, x[0], SIZES)
    assert 0.2 < float(share) < 0.5          # most positions are NOT attended
    assert np.abs(np.asarray(ours[0]) - np.asarray(theirs)).max() < 1e-5
    wide = dataclasses.replace(config, index_topk=4096)
    dense, *_ = M._dsa_block(layer, wide, x, cos, sin, jnp.int32(0), live)
    assert np.abs(np.asarray(dense[0]) - np.asarray(theirs)).max() > 1e-2
    assert rows.shape == (1, 1, 96, 32) and pooled.shape == (1, 1, 24, 16)
    assert not np.asarray(left).any()        # 96 positions: no group is open
    # the reference's rule on scores whose order is plain: query 13 (group
    # 3) of groups 0..2 takes all three; query 23 (group 5) the best three
    scores = jnp.tile(jnp.asarray([[5., 1., 4., 2., 3., 9., 9., 9.]]), (2, 1))
    picked = np.asarray(R.chosen_groups(scores[:1], 13, SIZES))[0]
    assert picked.nonzero()[0].tolist() == [0, 1, 2]
    picked = np.asarray(R.chosen_groups(scores[:1], 23, SIZES))[0]
    assert picked.nonzero()[0].tolist() == [0, 2, 4]
