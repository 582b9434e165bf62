# The sparse grouped-query decoder (ISSUE 38: K, V and an indexer key a token
# in three pool leaves, the exact top `topk` positions chosen a query by a
# lightning indexer, softmax-routed held experts and no shared expert) at a
# small size on the CPU in float32, the layer's own functions, no decoder:
# the model against the benchmark's plain reference
# (benchmark/reference/sparse_gqa_lm.py: sectioned rotary, its own
# sort-based top-k, one masked softmax, experts as a loop, precision
# "highest"), the selection's ties, the rotary's two forms, the step's
# attention as a mask, the eight-way share, the router's plain columns, the
# parameter count, the pool's geometry.  This file holds the suite's SIZES
# and its `CASES` (tests/paged_model_cases.py); the cases that serve
# through a decoder are in test_0_served_sparse_gqa.py.
#
# Comparisons are of LOGITS or masks, never of sampled tokens.  Each
# tolerance states its reason.

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_model_cases import ROOT, PagedModelCases, share_layer

from aiko_services_tpu import serving_paged
from aiko_services_tpu.models import latent_moe
from aiko_services_tpu.models import sparse_gqa as M
from aiko_services_tpu.ops.paged_attention import walk_positions
from aiko_services_tpu.serving_paged import BlockPool
from benchmark import ops_bytes_sparse_gqa as ops
from benchmark import weights_sparse_gqa as W
from benchmark.reference import sparse_gqa_lm as R

SEED = 2**31 + 29
# every mechanism of the published file at a size a test holds: two layers,
# 8 query heads over 2 K/V heads of 16, 4 indexer heads of 8 (rotary on 4
# lanes), 16 positions attended at most, 8 experts top 2 (all held)
SIZES = dict(
    hidden_size=64, vocab_size=256, num_hidden_layers=2, head_dim=16,
    num_attention_heads=8, num_key_value_heads=2, rope_theta=10000000,
    rope_scaling=dict(mrope_section=[2, 3, 3], rope_type="default",
                      type="default"),
    sa_config=dict(indexer_head_dim=8, indexer_num_heads=4,
                   indexer_num_kv_heads=1, kv_chunk_size=512,
                   q_chunk_size=512, topk=16),
    assumed_sizes=dict(index_rope_head_dim=4, index_rope_theta=10000000),
    moe_intermediate_size=32, num_experts=8, num_local_experts=8,
    num_experts_per_tok=2, norm_topk_prob=True, mlp_only_layers=[],
    decoder_sparse_step=1, attention_bias=False, hidden_act="silu",
    use_sliding_window=False, tie_word_embeddings=False, rms_norm_eps=1e-6)
# float32 against float32 at "highest": what is left is the order of the
# sums (online against one softmax, grouped against per-head einsums, tiles
# against a loop over experts), a few float32 ulps of logits whose spread
# is 1: measured 1e-5 at most.  bfloat16 anywhere reads 1e-2 and more.
LOGIT_TOLERANCE = 2e-4


CASES = PagedModelCases(
    "sparse_gqa_decoder", W,
    lambda tokens, sizes, seed, streams=None: R.forward_logits(
        tokens, sizes, seed, jnp.float32, streams), SIZES, SEED)
model_config = CASES.model_config
TOKENS = np.random.default_rng(0).integers(1, 256, size=90)


def reference_logits(tokens, sizes=SIZES, streams=None):
    if streams is None:
        return CASES.reference_logits(tokens, sizes)
    return np.asarray(CASES.reference_forward(tokens, sizes, SEED, streams))


def test_seeded_weights_have_the_programs_layout():
    assert model_config() == M.SPARSE_GQA_PRESETS["tiny"]
    CASES.has_the_layout_of(M.sparse_gqa_init)
    assert "shared" not in CASES.params["layers"][0]


def test_full_forward_agrees_with_the_reference():
    """90 tokens where a query attends 16: the choice of positions is in
    every later logit, and the reference found it by its own sort."""
    gap, spread = CASES.forward_gap(M.sparse_gqa_forward, TOKENS)
    assert spread > 0.5                         # logits of spread ~1
    assert gap < LOGIT_TOLERANCE


def test_the_selection_is_in_the_numbers():
    """Attending everything (topk past the sequence) is another model."""
    dense = reference_logits(
        TOKENS, SIZES | {"sa_config": SIZES["sa_config"] | {"topk": 128}})
    sparse = reference_logits(TOKENS)
    assert np.abs(dense - sparse).max() > 100 * LOGIT_TOLERANCE
    # and up to topk positions it is the same model: nothing is left out
    assert np.abs(dense[:16] - sparse[:16]).max() < LOGIT_TOLERANCE


def test_bfloat16_would_fail():
    gap, _ = CASES.forward_gap(M.sparse_gqa_forward, TOKENS, jnp.bfloat16)
    assert gap > 10 * LOGIT_TOLERANCE


# -- rotary ----------------------------------------------------------------------

def test_plain_rotary_is_the_sectioned_one_on_equal_streams():
    """The program's rotary (half-split pairs, one position a token)
    against the reference's sectioned form fed the token's index in all
    three streams, at the published head and sections."""
    config = M.SparseGqaConfig(max_seq_len=64)
    cos, sin = M.rope_tables(config)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 40, 128))
    positions = jnp.arange(7, 47)[None]
    ours = M._rotate(x, cos[0], sin[0], positions)[0]        # [H, T, D]
    theirs = R.sectioned_rotary(
        jnp.swapaxes(x[0], 0, 1), jnp.broadcast_to(positions, (3, 40)),
        (16, 24, 24), 1e7)
    assert np.abs(np.asarray(jnp.swapaxes(ours, 0, 1)) -
                  np.asarray(theirs)).max() < 1e-5


def test_the_reference_keeps_to_its_definition_on_unequal_streams():
    """A token at (t, h, w) = (5, 2, 9): pair i < 16 turns by 5 x its
    frequency, 16 <= i < 40 by 2 x, 40 <= i < 64 by 9 x, lanes (i, i +
    64)."""
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, 1, 128)))
    streams = jnp.asarray([[5], [2], [9]])
    out = np.asarray(R.sectioned_rotary(jnp.asarray(x), streams,
                                        (16, 24, 24), 1e7))[0, 0]
    for i in (0, 15, 16, 39, 40, 63):
        at = 5 if i < 16 else 2 if i < 40 else 9
        angle = at * 1e7 ** (-i / 64)
        low, high = x[0, 0, i], x[0, 0, i + 64]
        assert out[i] == pytest.approx(
            low * np.cos(angle) - high * np.sin(angle), abs=1e-5)
        assert out[i + 64] == pytest.approx(
            high * np.cos(angle) + low * np.sin(angle), abs=1e-5)
    # and an image-like sequence is another result than the text's
    tokens = np.random.default_rng(1).integers(1, 256, size=24)
    grid = np.stack([np.arange(24), np.arange(24) // 6, np.arange(24) % 6])
    assert np.abs(reference_logits(tokens, streams=grid) -
                  reference_logits(tokens)).max() > 100 * LOGIT_TOLERANCE


# -- the selection ---------------------------------------------------------------

def _stable_top(scores, limit):
    """Rows' `limit` largest by a stable sort: ties to the lower index."""
    order = np.argsort(-scores, axis=-1, kind="stable")[..., :limit]
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, order, True, axis=-1)
    return mask


@pytest.mark.parametrize("case", ["random", "ties", "zeros-of-both-signs",
                                  "fewer-than-the-limit", "with-minus-inf"])
def test_top_positions_is_exact_with_ties_to_the_lower_index(case):
    rng = np.random.default_rng(4)
    scores = rng.standard_normal((3, 5, 40)).astype(np.float32)
    limit = 16
    if case == "ties":
        scores = np.round(scores * 2) / 2          # many equal values
    elif case == "zeros-of-both-signs":
        scores = np.where(scores < 0.3, 0.0, scores).astype(np.float32)
    elif case == "fewer-than-the-limit":
        limit = 64
    elif case == "with-minus-inf":
        scores[..., 10:] = -np.inf                 # ten candidates a row
    ours = np.asarray(M.top_positions(jnp.asarray(scores), limit))
    assert (ours == _stable_top(scores, limit)).all()
    assert (ours.sum(-1) == min(limit, 40)).all()


def test_program_and_reference_choose_the_same_positions():
    """The program's choice (a bit-by-bit threshold, in a block and in the
    step alike) and the reference's (a sort's) over the same scores, ties
    made on purpose: one set, and a stable sort's."""
    rng = np.random.default_rng(5)
    scores = (np.round(rng.standard_normal((40, 40)) * 3) / 3 + 0.0).astype(
        np.float32)                     # + 0.0: one zero, as `_scores` makes
    theirs = np.asarray(R.chosen_positions(jnp.asarray(scores), 0, 16))
    causal = np.tril(np.ones((40, 40), bool))
    masked = np.where(causal, scores, -np.inf).astype(np.float32)
    ours = np.asarray(M.top_positions(jnp.asarray(masked), 16)) & causal
    assert (ours == theirs).all()
    assert (theirs.sum(-1) == np.minimum(np.arange(40) + 1, 16)).all()
    assert ((_stable_top(masked, 16) & causal) == theirs).all()


# -- the step's attention alone: a mask, then the walk or the plain form ----------

STEP_SLOTS = {      # slot -> (entry length, the round's tokens so far, decodes)
    "idle": (31, 0, False), "fresh": (0, 2, True), "under": (9, 1, True),
    "at-the-limit": (14, 1, True), "one-over": (14, 2, True),
    "long": (45, 2, True)}


@pytest.fixture(scope="module")
def step_inputs():
    """One layer's leaves, tables, sides and queries for STEP_SLOTS, at
    the tiny preset: blocks of 8, a table of 6, a round of 4 steps, the
    step at its third token (step_index 2); `live` says which cells of the
    pool hold a position of a slot that decodes."""
    config = model_config()
    slots, block, nb, steps = len(STEP_SLOTS), 8, 6, 4
    keys = jax.random.split(jax.random.PRNGKey(39), 8)
    entry = jnp.asarray([case[0] for case in STEP_SLOTS.values()], jnp.int32)
    taken = jnp.asarray([case[1] for case in STEP_SLOTS.values()], jnp.int32)
    active = jnp.asarray([case[2] for case in STEP_SLOTS.values()])
    tables = 1 + jax.random.permutation(
        keys[0], slots * nb).astype(jnp.int32).reshape(slots, nb)
    live = jnp.zeros((slots * nb + 1, block), bool).at[tables].set(
        (jnp.arange(nb * block)[None] <
         jnp.where(active, entry, 0)[:, None]).reshape(slots, nb, block))
    leaves = [jax.random.normal(key, (slots * nb + 1, heads, block, lanes))
              for key, (heads, lanes) in zip(keys[1:4], config.cache_leaves)]
    sides = [jax.random.normal(key, (slots, heads, steps, lanes))
             for key, (heads, lanes) in zip(keys[4:7], config.cache_leaves)]
    q, q_i = (jax.random.normal(key, (slots, heads, 1, lanes))
              for key, heads, lanes in (
                  (keys[7], config.num_heads, config.head_dim),
                  (keys[0], config.index_heads, config.index_dim)))
    return (config, leaves, live[:, None, :, None], tables, sides, q, q_i,
            entry, entry + taken, active)


def _plain_softmax(config, leaves, tables, sides, q, chosen):
    """float64 softmax of every slot's heads over the positions `chosen`
    [S, the table's ++ the round's] names, each read where it lies."""
    out = np.zeros(q.shape[:2] + q.shape[3:])
    group = config.num_heads // config.num_kv_heads
    for s in range(q.shape[0]):
        rows = [np.concatenate([
            np.asarray(leaf)[np.asarray(tables[s])].transpose(
                1, 0, 2, 3).reshape(leaf.shape[1], -1, leaf.shape[3]),
            np.asarray(side[s])], axis=1)[:, chosen[s]].astype(np.float64)
            for leaf, side in zip(leaves[:2], sides[:2])]
        for h in range(config.num_heads):
            k, v = rows[0][h // group], rows[1][h // group]
            scores = k @ np.asarray(q[s, h, 0], np.float64) * \
                config.softmax_scale
            w = np.exp(scores - scores.max())
            out[s, h] = (w / w.sum()) @ v
    return out


@pytest.mark.parametrize("scores", ["random", "all-tied"])
@pytest.mark.parametrize("form", ["plain", "plain-in-pieces", "kernel"])
def test_the_step_attends_the_chosen_positions_as_a_mask(
        step_inputs, form, scores, monkeypatch):
    """Both forms of the step against a plain softmax over the positions a
    stable sort of the same index scores takes: slots under `topk` 16, at
    it (14 in the pool, the round's one before and the token's own), one
    over, far over, one with nothing in the pool yet and one that decodes
    nothing.  With every
    indexer weight zero ALL scores tie at the limit's edge, and the 16
    lowest positions are the set.  Every dead cell of K and V (the rest
    of a last live block, the blocks past it, all of a slot that decodes
    nothing) holds NaN for the kernel, which zeroes what it did not walk,
    and a large finite value for the plain form, whose weights there are
    exact zeros as an extend's are; read in pieces of 32 positions the
    table of 48 ends mid-piece.  The counts are the same either way but
    for what was read of the pool."""
    kernel, span = form == "kernel", 32 if form == "plain-in-pieces" else 48
    monkeypatch.setattr(M, "_PREFIX_PIECE", span)
    config, leaves, held_live, tables, sides, q, q_i, entry, lengths, \
        active = step_inputs
    dead = jnp.nan if kernel else 1e4
    leaves = [jnp.where(held_live, leaf, dead) for leaf in leaves[:2]] + \
        [jnp.where(held_live, leaves[2], 0.0)]
    weights = jax.random.normal(jax.random.PRNGKey(5), (len(STEP_SLOTS), 1,
                                                        config.index_heads))
    if scores == "all-tied":
        weights = jnp.zeros_like(weights)
    held, steps = tables.shape[1] * 8, 4
    # a function of its own a case: the piece is read as it is traced
    out, counted = jax.jit(functools.partial(M._attend_slots, config, kernel))(
        leaves, tables, sides, q, q_i, weights, entry, lengths, 2, active)
    keys = np.concatenate([
        np.asarray(leaves[2])[np.asarray(tables)][:, :, 0].reshape(
            len(STEP_SLOTS), held, -1), np.asarray(sides[2][:, 0])], axis=1)
    index = np.asarray(M._scores(config, q_i, weights, jnp.asarray(keys)))[:, 0]
    at = np.arange(held + steps)[None]
    visible = np.where(at < held, at < np.asarray(entry)[:, None],
                       (at - held <= 2) & (np.asarray(entry)[:, None] + at -
                                           held <= np.asarray(lengths)[:, None]))
    chosen = _stable_top(np.where(visible, index, -np.inf), 16) & visible
    live = np.asarray(active)
    assert chosen[live].sum(1).tolist() == [3, 11, 16, 16, 16]
    if scores == "all-tied":            # the lowest positions, not the round's
        assert chosen[-1, :16].all()
    theirs = _plain_softmax(config, leaves, tables, sides, q, chosen)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out)[live], theirs[live],
                               rtol=2e-5, atol=2e-6)
    walked = np.where(live, np.asarray(entry), 0)
    read = walk_positions(walked, 8).sum() if kernel else \
        live.sum() * -(-walked.max() // span) * span
    assert np.asarray(counted).tolist() == [
        (np.asarray(lengths) + 1)[live].sum(), chosen[live].sum(), read,
        (live & (np.asarray(lengths) + 1 <= 16)).sum()]


# -- the expert layer: softmax scores, no shared expert, the chip's share --------

@pytest.mark.parametrize("tokens", [24, 200], ids=["decode-block", "tiles"])
@pytest.mark.parametrize("chips", [8, 2], ids=["eight-chips", "two-chips"])
def test_the_shares_add_up_to_the_uncut_layer(tokens, chips):
    """`chips` chips hold 8 / chips experts each of a layer of eight (top
    2 of a softmax over all eight, renormalised): what each gives, added
    up, is the reference's whole layer; there is no shared expert to count
    once."""
    held = 8 // chips
    whole = SIZES | {"num_experts": 8}
    layer = W.decoder_layer(W.key_for(SEED), 1, whole, jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 64)) * 4
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(R.feed_forward(layer, y, sizes=whole))
    total, pairs = np.zeros_like(theirs), 0
    normed = latent_moe.L.rms_norm(layer["ln_mlp"], y)
    for chip in range(chips):
        config = dataclasses.replace(model_config(), experts_first=chip * held,
                                     experts_held=held)
        out, counts = latent_moe.moe_ffn(
            share_layer(layer, chip * held, held), config, normed)
        total += np.asarray(out)
        pairs += int(counts[2])
        assert int(counts[3]) == tokens * 2
    assert pairs == tokens * 2              # every pair landed on one share
    assert np.abs(total - theirs).max() < 5e-5


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**32 + 11])
def test_the_router_is_plain_and_the_held_share_an_eighth_on_average(seed):
    """16 of 128 experts held, top 8: the router's columns are one draw an
    expert (no two alike, so a token's eight gates differ and a sigmoid in
    the softmax's place changes the layer), the held experts get an eighth
    of the pairs over many tokens, and a token may hit none of them or
    several."""
    sizes = SIZES | {"num_experts": 16, "num_experts_per_tok": 8,
                     "published": {"num_experts": 128}}
    layer = W.decoder_layer(W.key_for(seed), 0, sizes, jnp.float32)
    weights = np.asarray(layer["router"]["w"])
    assert weights.shape == (64, 128)
    assert len({column.tobytes() for column in weights.T}) == 128
    x = jax.random.normal(jax.random.PRNGKey(seed % 1000), (4000, 64))
    ours = dataclasses.replace(model_config(), num_experts=128, top_k=8,
                               experts_held=16)
    ids, gains = latent_moe.select_experts(
        ours, latent_moe.router_scores(ours, x @ weights))
    here = (np.asarray(ids) < 16).sum(axis=-1)
    assert abs(here.mean() / 8 - 1 / 8) < 0.01
    assert (here == 0).any() and (here >= 2).any()
    gains = np.asarray(gains)
    assert np.allclose(gains.sum(axis=-1), 1.0, atol=1e-5)
    assert np.median(gains.max(axis=-1) / gains.min(axis=-1)) > 2
    theirs = dataclasses.replace(ours, router_scores="sigmoid")
    softmax, _ = latent_moe.moe_ffn(layer, ours, x[:64])
    sigmoid, _ = latent_moe.moe_ffn(layer, theirs, x[:64])
    assert np.abs(np.asarray(softmax) - np.asarray(sigmoid)).max() > \
        0.05 * np.abs(np.asarray(softmax)).max()


def test_softmax_scores_and_sigmoid_scores_are_told_apart():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    ours = model_config()
    assert np.allclose(latent_moe.router_scores(ours, logits),
                       jax.nn.softmax(logits))
    theirs = latent_moe.LATENT_MOE_PRESETS["tiny"]
    assert np.allclose(latent_moe.router_scores(theirs, logits),
                       jax.nn.sigmoid(logits))


# -- the count from the published keys -------------------------------------------

def test_the_parameter_count_reproduces_the_published_size():
    """30.6 B, 3.5 B of them active a token, from the file's keys."""
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b-ep8-d12.json")) as f:
        sizes = json.load(f)
    assert ops.attention_params(sizes) == 18_874_624
    assert ops.indexer_params(sizes) == 2_261_120
    assert ops.expert_params(sizes) == 4_718_592
    assert round(ops.layer_params(sizes, 128) / 1e6, 1) == 625.4
    whole = ops.published_parameters(sizes)
    assert round(whole["total"] / 1e9, 1) == 30.6
    assert round(whole["active"] / 1e9, 1) == 3.5
    held = ops.params(sizes)
    assert round(held["total"] * 2 / 1e9, 2) == 2.48       # GB here
    config = model_config(sizes, jnp.bfloat16, 32768)
    made = jax.eval_shape(
        lambda: M.sparse_gqa_init(jax.random.PRNGKey(0), config))
    assert sum(leaf.size for leaf in jax.tree.leaves(made)) == held["total"]


# -- the pool: three leaves a layer ----------------------------------------------

def test_the_pool_takes_three_leaves_from_the_model():
    """K and V as a dense grouped-query model's, the indexer key padded
    from 64 lanes to a lane tile: 2,304 B a token and layer at the
    published widths (2,176 unpadded)."""
    published = dataclasses.replace(
        M.SparseGqaConfig(dtype=jnp.bfloat16), num_layers=3, vocab=256,
        experts_held=1)
    assert published.cache_leaves == ((4, 128), (4, 128), (1, 128))
    assert serving_paged.first_leaf(published) == (4, 128)
    assert serving_paged.reads_own_pool(published)
    pool = BlockPool(published, 32, False, initial_blocks=2, name="geo-pub")
    assert pool.block_nbytes == 32 * 3 * 2304
    assert len(pool.k_pools) == 3 and len(pool.v_pools) == 6
    assert [leaf.shape for leaf in pool.k_pools] == [(3, 4, 32, 128)] * 3
    assert [leaf.shape for leaf in pool.v_pools] == \
        [(3, 4, 32, 128)] * 3 + [(3, 1, 32, 128)] * 3
    assert pool.nbytes() == 3 * pool.block_nbytes
    sizes = {"num_key_value_heads": 4, "head_dim": 128,
             "sa_config": {"indexer_head_dim": 64}}
    assert ops.token_row_bytes(sizes, 2) == 2176
    # growth and copy walk every leaf
    tiny = BlockPool(model_config(), 8, False, initial_blocks=4, name="geo")
    assert tiny.block_nbytes == 8 * 2 * (2 * 2 * 16 + 128) * 4
    ids = tiny.alloc_blocks(2)
    assert tiny.copy_blocks(ids[:1], ids[1:]) == tiny.block_nbytes
    tiny.reserve(12)
    assert all(leaf.shape[0] >= 13 for leaf in tiny.k_pools + tiny.v_pools)


@pytest.mark.parametrize("leaves", [1, 2, 3])
def test_the_programs_split_and_join_the_pools_sides(leaves):
    layers = 4
    k_pools = [f"a{i}" for i in range(layers)]
    v_pools = [f"{'bc'[side]}{i}" for side in range(leaves - 1)
               for i in range(layers)]
    sides = serving_paged._pool_sides(k_pools, v_pools)
    assert len(sides) == leaves and all(len(s) == layers for s in sides)
    assert [side[2] for side in sides] == ["a2", "b2", "c2"][:leaves]
    assert serving_paged._join_sides(sides) == (k_pools, v_pools)


def test_a_norm_of_another_epsilon_is_refused():
    with pytest.raises(ValueError, match="1e-6"):
        dataclasses.replace(model_config(), norm_eps=1e-5)


def test_the_driver_refuses_keys_it_does_not_compute():
    import sparse_gqa_decoder
    for key, value in (("attention_bias", True), ("mlp_only_layers", [0]),
                       ("decoder_sparse_step", 2), ("norm_topk_prob", False),
                       ("use_sliding_window", True)):
        with pytest.raises(ValueError, match="the program computes"):
            sparse_gqa_decoder.model_config(SIZES | {key: value}, 64,
                                            jnp.float32)
    with pytest.raises(ValueError, match="ONE key head"):
        W.indexer_sizes(SIZES | {"sa_config": SIZES["sa_config"] |
                                 {"indexer_num_kv_heads": 2}})


def test_serving_tests_no_models_name():
    for module in ("serving.py", "serving_paged.py"):
        with open(os.path.join(ROOT, "aiko_services_tpu", module)) as f:
            text = f.read()
        assert "sparse_gqa" not in text and "SparseGqa" not in text

