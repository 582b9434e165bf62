# The latent-attention (MLA), routed-expert decoder (ISSUE 31) at a small
# size on the CPU in float32, the layer's own functions, no decoder: the
# model against the benchmark's plain reference
# (benchmark/reference/latent_moe_lm.py: expanded attention only, experts as
# a loop, precision "highest"), absorbed against expanded attention on one
# cache, the YaRN table, the sixteen-way share tied to the uncut layer, a
# token no held expert takes, the pool's geometry.  This file holds the
# suite's SIZES and its `CASES` (tests/paged_model_cases.py); the cases
# that serve through a decoder are in test_0_served_latent_moe.py.
#
# Comparisons are of LOGITS (or of attention outputs), never of sampled
# tokens.  Each tolerance states its reason; `test_bfloat16_would_fail`
# shows that the same computation in bfloat16 breaks them.

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_model_cases import PagedModelCases, share_layer

from aiko_services_tpu.models import latent_moe as M
from benchmark import weights_latent_moe as W
from benchmark.reference import latent_moe_lm as R

SEED = 2**31 + 29
# every mechanism of the published file at a size a test holds: 1 dense +
# 2 sparse layers, 8 experts top 2 (all held), 4 heads of 16 + 8, rows of
# 32 + 8 padded to 128 lanes, YaRN factor 4 over 32 positions
SIZES = dict(
    hidden_size=64, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_attention_heads=4,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
    n_shared_experts=1, first_k_dense_replace=1, vocab_size=256,
    num_hidden_layers=3, num_experts_per_tok=2, routed_scaling_factor=2.5,
    rms_norm_eps=1e-6, rope_theta=10000, scoring_func="sigmoid",
    norm_topk_prob=True, moe_layer_freq=1,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=4, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=32,
                      type="yarn"))
# float32 against float32 at "highest": what is left is the order of the
# sums (absorbed against expanded, online against two-pass softmax, tiles
# against a loop over experts), a few float32 ulps of logits whose spread
# is 1: measured 9e-6 at most.  bfloat16 anywhere reads 1e-2 and more.
LOGIT_TOLERANCE = 2e-4


CASES = PagedModelCases(
    "latent_moe_decoder", W,
    lambda tokens, sizes, seed: R.forward_logits(tokens, sizes, seed,
                                                 jnp.float32), SIZES, SEED)
model_config, reference_logits = CASES.model_config, CASES.reference_logits
TOKENS = np.random.default_rng(0).integers(1, 256, size=48)


def test_seeded_weights_have_the_programs_layout():
    CASES.has_the_layout_of(M.latent_moe_init)


def test_full_forward_agrees_with_the_reference():
    gap, spread = CASES.forward_gap(M.latent_moe_forward, TOKENS)
    assert spread > 0.5                         # logits of spread ~1
    assert gap < LOGIT_TOLERANCE


def test_bfloat16_would_fail():
    """The tolerance is tight enough: the same forward with bfloat16
    weights and activations, where float32 is stated, breaks it."""
    gap, _ = CASES.forward_gap(M.latent_moe_forward, TOKENS, jnp.bfloat16)
    assert gap > 10 * LOGIT_TOLERANCE


def test_yarn_table_and_softmax_scale_agree_with_the_reference():
    config = model_config()
    cos, sin = M.yarn_rope_tables(config)
    angles = np.arange(128)[:, None] * R.yarn_inverse_frequencies(SIZES)
    # float32 cos/sin of angles up to 128: an ulp or two
    assert np.abs(np.asarray(cos) - np.cos(angles)).max() < 1e-5
    assert np.abs(np.asarray(sin) - np.sin(angles)).max() < 1e-5
    assert config.softmax_scale == pytest.approx(R.softmax_scale(SIZES))
    # the published keys: 192^-0.5 x (0.1 ln 32 + 1)^2, a ramp from
    # dimension 10 to 23 of 32
    published = M.LatentMoeConfig()
    assert published.softmax_scale == pytest.approx(
        192 ** -0.5 * 1.3465735902799727 ** 2)
    assert published.row_lanes == 640
    assert published.cache_leaves == ((1, 640),)


def test_absorbed_and_expanded_attention_agree_on_one_cache():
    """The same numbers two ways: the last token's attention over a cache
    of 37 rows, W_kvb applied to every row (expanded) or folded into the
    query and the output (absorbed)."""
    config = model_config()
    layer = CASES.params["layers"][1]
    cos, sin = M.yarn_rope_tables(config)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 38, 64))
    expanded, rows = M.expanded_attention(layer, config, x, cos, sin,
                                          jnp.int32(0))
    q_nope, q_rope, row = M.project_block(layer, config, x[:, 37:], cos, sin,
                                          jnp.asarray([37]))
    assert np.abs(np.asarray(row - rows[:, :, 37:])).max() < 1e-6
    assert not np.asarray(rows[..., 40:]).any()         # the pad lanes
    side = jnp.zeros((1, 1, 4, config.row_lanes)).at[:, :, :1].set(row)
    o_lat = M.absorbed_attention(
        config, M.absorb_queries(layer["attn"], config, q_nope, q_rope),
        rows[:, :, :37], side, jnp.ones((1, 1, 1, 37), bool),
        (jnp.arange(4) < 1)[None, None, None])
    absorbed = M.absorb_output(layer["attn"], config, o_lat, 1)
    # attention outputs of spread ~0.3; float32 association only
    assert np.abs(np.asarray(absorbed[0, 0] - expanded[0, 37])).max() < 1e-5


# -- the expert layer and its share ---------------------------------------------

SIXTEEN = SIZES | {"n_routed_experts": 16}     # one expert a chip, 16 chips


def share_config(first, held, sizes=SIXTEEN):
    return dataclasses.replace(model_config(sizes), experts_first=first,
                               experts_held=held)


@pytest.mark.parametrize("tokens", [24, 200], ids=["decode-block", "tiles"])
def test_the_sixteen_shares_routed_parts_add_up_to_the_uncut_layer(tokens):
    """The cut ties to the model: sixteen chips hold one expert each of a
    layer of sixteen (top 2); what each gives beyond the shared expert,
    added up with the shared expert counted once, is the reference's whole
    layer.  Both bodies: a decode block (every row through a hit expert)
    and a prefill block over _EXPERT_TILE rows (compacted tiles)."""
    layer = W.decoder_layer(W.key_for(SEED), 2, SIXTEEN, jnp.float32, True)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 64))
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(R._swiglu(layer["shared"], x))
        whole = shared + np.asarray(R.experts_part(layer, x, SIXTEEN))
    total, pairs, hit = shared.copy(), 0, 0
    for first in range(16):
        y, counts = M.moe_ffn(share_layer(layer, first, 1),
                              share_config(first, 1), x)
        total += np.asarray(y) - shared
        pairs, hit = pairs + int(counts[2]), hit + int(counts[1])
        assert int(counts[3]) == tokens * 2 and int(counts[0]) == 1
    assert pairs == tokens * 2          # every pair landed on one share
    assert 2 <= hit <= 16
    # outputs of spread ~1; float32 sums in another order.  (A bfloat16
    # layer reads 1e-2 here.)
    assert np.abs(total - whole).max() < 5e-5
    # and a share of four as the reference computes it
    sizes = SIXTEEN | {"n_routed_experts": 4, "published": {
        "n_routed_experts": 16}, "deployment": {"experts_first": 8}}
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(R.experts_part(share_layer(layer, 8, 4), x, sizes))
    ours, _ = M.moe_ffn(share_layer(layer, 8, 4), share_config(8, 4), x)
    assert np.abs(np.asarray(ours) - shared - theirs).max() < 5e-5


def test_a_token_no_held_expert_takes_gets_its_shared_expert_only():
    layer = CASES.params["layers"][1]
    config = share_config(6, 2, SIZES)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    scores = jax.nn.sigmoid(x @ layer["router"]["w"])
    ids, _ = M.select_experts(config, scores)
    untaken = np.asarray((ids < 6).all(axis=1))
    assert untaken.any() and not untaken.all()
    y, counts = M.moe_ffn(share_layer(layer, 6, 2), config, x)
    shared = np.asarray(M._swiglu(layer["shared"], x))
    assert np.array_equal(np.asarray(y)[untaken], shared[untaken])
    assert np.abs(np.asarray(y)[~untaken] - shared[~untaken]).max() > 1e-3
    assert int(counts[2]) == int((np.asarray(ids) >= 6).sum())
    # a token that is not live costs no expert its weights
    live = jnp.zeros((40,), bool)
    y, counts = M.moe_ffn(share_layer(layer, 6, 2), config, x, live)
    assert np.array_equal(np.asarray(y), shared)
    assert [int(c) for c in counts] == [1, 0, 0, 0]


def test_select_experts_is_the_plain_rule():
    config = model_config()
    scores = jnp.asarray([[.1, .9, .2, .8, .3, .4, .5, .6]])
    ids, weights = M.select_experts(config, scores)
    assert sorted(np.asarray(ids)[0].tolist()) == [1, 3]
    assert np.asarray(weights).sum() == pytest.approx(2.5)
    chosen, theirs = R.select(scores, 2, 2.5)
    assert np.asarray(chosen)[0].nonzero()[0].tolist() == [1, 3]
    assert float(theirs[0, 1]) == pytest.approx(2.5 * .9 / 1.7)


def test_the_grouped_query_model_is_still_carried_everywhere():
    from aiko_services_tpu.models.llama import LLAMA_PRESETS
    model = LLAMA_PRESETS["tiny"].paged_model()
    assert {"dense_cache", "int8_kv", "speculation", "prefix_cache",
            "weight_quant", "tensor_parallel", "kv_wire",
            "drain"} <= model.supports and model.counters == ()
    assert LLAMA_PRESETS["tiny"].cache_leaves == ((2, 16), (2, 16))


def test_the_pool_takes_its_geometry_from_the_model():
    from aiko_services_tpu.serving_paged import BlockPool
    pool = BlockPool(model_config(), 8, False, initial_blocks=4, name="geo")
    assert pool.v_pools == [] and len(pool.k_pools) == 3
    assert pool.k_pools[0].shape == (5, 1, 8, 128)
    assert pool.block_nbytes == 3 * 8 * 128 * 4
    assert pool.nbytes() == 3 * 5 * 8 * 128 * 4
    ids = pool.alloc_blocks(2)
    assert pool.copy_blocks(ids[:1], ids[1:]) == pool.block_nbytes
    pool.reserve(12)
    assert pool.k_pools[0].shape[0] >= 13 and pool.v_pools == []

