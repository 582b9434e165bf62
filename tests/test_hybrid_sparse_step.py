# The hybrid decoder (tests/test_hybrid_sparse_layers.py has the suite's
# sizes and reference), the sparse layer's STEP over a pool laid out by
# hand against the reference's layer over the same sequence (ISSUE 37: the
# tile that holds a chosen group), the expert layer's eight-way share, and
# what the pool and the slot state take from the model.  No decoder.

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import latent_moe
from aiko_services_tpu.serving_paged import BlockPool, SlotState
from paged_model_cases import share_layer
from test_hybrid_sparse_layers import (CASES, LOGIT_TOLERANCE, SEED, SIZES, M,
                                       R, W, model_config)

# -- the step fetches the tile that holds a chosen group (ISSUE 37) --------------

def _reference_choice(layer, h, p):
    """The groups that the REFERENCE's rule chooses for the query at
    position p of one sequence h [T, dim], by its own projections."""
    _, _, q_i, k_i, weights = R.sparse_project(layer, h, jnp.int32(0),
                                               sizes=SIZES)
    whole = p // 4
    pooled = k_i[:4 * whole].reshape(whole, 4, -1).mean(axis=1)
    dots = jnp.einsum("qjd,gd->qjg", q_i[p:p + 1], pooled)
    scores = jnp.einsum("qj,qjg->qg", weights[p:p + 1], jax.nn.relu(dots))
    return np.asarray(R.chosen_groups(scores, p, SIZES))[0].nonzero()[0]


def _hidden_whose_choice(layer, p, wanted):
    """The first seeded input (whole groups, position p in the last) for
    which the reference's choice at position p is one the case asks for."""
    for seed in range(400):
        h = jax.random.normal(jax.random.PRNGKey(1000 + seed),
                              (p // 4 * 4 + 4, 64))
        chosen = _reference_choice(layer, h, p).tolist()
        if wanted(chosen):
            return h, chosen
    raise AssertionError("no input in 400 gives the case's choice")


def _step_over_a_pool(layer, h, p, block, table):
    """`_dsa_step` for the token at position p (the round's first step) of
    slot 0 of two; the pool holds the rows and pooled keys of h[:p] through
    `table`, and RANDOM rows and keys everywhere else: what a longer
    request left in a reused block, in the other half of a tile, in the
    rows past the slot's length.  -> (out [dim], the three counts)."""
    config = model_config()
    cos, sin = M.rope_tables(config)
    _, rows, _, k_i, _ = M._dsa_project(layer, config, h[None, :p], cos, sin,
                                        jnp.zeros((1,), jnp.int32))
    whole, blocks = p // 4, max(table) + 2
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    latent = np.array(3.0 * jax.random.normal(
        keys[0], (blocks, 1, block, config.kv_rank)))
    pooled = np.array(3.0 * jax.random.normal(
        keys[1], (blocks, 1, block // 4, config.index_dim)))
    for t in range(p):
        latent[table[t // block], 0, t % block] = rows[0, 0, t]
    for g in range(whole):
        pooled[table[g * 4 // block], 0, g % (block // 4)] = \
            k_i[0, 4 * g:4 * g + 4].mean(axis=0)
    tables = jnp.asarray([list(table) + [0] * (16 - len(table)), [0] * 16],
                         jnp.int32)
    lengths = jnp.asarray([p, 0], jnp.int32)
    left = jnp.stack([k_i[0, 4 * whole:p].sum(axis=0),
                      jnp.zeros((config.index_dim,))])
    x = jnp.stack([h[p:p + 1], jnp.zeros((1, 64))])
    live = jnp.asarray([True, False])      # at the round's entry, and now
    out, _, _, counted = jax.jit(
        lambda *args: M._dsa_step(layer, config, *args, 0, live, live))(
        x, cos, sin, tables, (jnp.asarray(latent), jnp.asarray(pooled)),
        (jnp.zeros((2, 1, 1, config.kv_rank)),
         jnp.zeros((2, 1, 1, config.index_dim))), left, lengths, lengths)
    return np.asarray(out[0, 0]), np.asarray(counted)[:3].tolist()


# a tile of 8 rows holds two groups of 4: group g is the lower half of its
# tile where g is even.  41 positions before the query: 10 complete groups
@pytest.mark.parametrize("p, block, wanted", [
    (41, 8, lambda c: len(c) == 3 and all(g % 2 == 0 for g in c)),
    (41, 8, lambda c: len(c) == 3 and all(g % 2 == 1 for g in c)),
    (41, 8, lambda c: any(g % 2 == 0 and g + 1 in c for g in c)),
    (10, 8, lambda c: c == [0, 1]),
    (49, 16, lambda c: 11 in c and all(g % 4 >= 2 for g in c)),
], ids=["lower-halves", "upper-halves", "both-halves-of-a-tile",
        "fewer-groups-than-the-limit", "a-blocks-last-tile"])
def test_the_step_fetches_tiles_and_attends_the_chosen_halves(
        p, block, wanted):
    """The sparse layer's step against the reference's layer over the
    same sequence, where the chosen groups lie as the case says in their
    tiles (a block of 16 holds two tiles, groups 2, 3 of its 4 the last;
    group 11 ends the third block of a slot of 49 positions).  What the
    other half of a fetched tile holds moves nothing, and the counts say
    what was fetched: a whole tile a group taken."""
    layer = CASES.params["layers"][3]
    h, chosen = _hidden_whose_choice(layer, p, wanted)
    table = [5, 2, 7, 1, 4, 6][:-(-(p + 1) // block)]
    ours, counted = _step_over_a_pool(layer, h, p, block, table)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(R.sparse_attention(layer, h, SIZES)[0][p])
    assert float(np.abs(theirs).max()) > 0.05
    assert np.abs(ours - theirs).max() < LOGIT_TOLERANCE
    assert counted == [p + 1, 4 * len(chosen) + p % 4 + 1, 8 * len(chosen)]


# -- the expert layer and its share ----------------------------------------------

@pytest.mark.parametrize("tokens", [24, 200], ids=["decode-block", "tiles"])
def test_the_eight_shares_add_up_to_the_uncut_layer(tokens):
    """Eight chips hold one expert each of a layer of eight (top 2, chosen
    by score + bias, weighted by score): what each gives beyond the shared
    expert, added up with the shared expert counted once, is the
    reference's whole layer, clamp and correction bias included."""
    layer = W.decoder_layer(W.key_for(SEED), 2, SIZES, jnp.float32,
                            ("kda", "sparse"))
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 64)) * 4
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(R._swiglu(layer["shared"], x, 10.0))
        whole = np.asarray(R.feed_forward(layer, x, sizes=SIZES))
    total, pairs = shared.copy(), 0
    for first in range(8):
        config = dataclasses.replace(model_config(), experts_first=first,
                                     experts_held=1)
        y, counts = latent_moe.moe_ffn(share_layer(layer, first, 1), config,
                                       x)
        total += np.asarray(y) - shared
        pairs += int(counts[2])
        assert int(counts[3]) == tokens * 2
    assert pairs == tokens * 2              # every pair landed on one share
    assert np.abs(total - whole).max() < 5e-5
    # the clamp is in the numbers: without it the layer is another
    unclamped = (jax.nn.silu(x @ layer["shared"]["gate"]["w"]) *
                 (x @ layer["shared"]["up"]["w"])) @ \
        layer["shared"]["down"]["w"]
    assert np.abs(np.asarray(unclamped) - shared).max() > 1e-3


def test_the_correction_bias_moves_the_choice_and_not_the_weights():
    config = model_config()
    scores = jnp.asarray([[.1, .9, .2, .8, .3, .4, .5, .6]])
    bias = jnp.asarray([0., 0., 0., 0., 0., 0., 0., .5])
    ids, weights = latent_moe.select_experts(config, scores, bias)
    assert sorted(np.asarray(ids)[0].tolist()) == [1, 7]
    assert sorted(np.asarray(weights)[0].tolist()) == pytest.approx(
        sorted([2.5 * .9 / 1.5, 2.5 * .6 / 1.5]))
    theirs = np.asarray(R.select(scores, bias, 2, 2.5))[0]
    assert theirs.nonzero()[0].tolist() == [1, 7]
    assert theirs[7] == pytest.approx(2.5 * .6 / 1.5)
    plain, _ = latent_moe.select_experts(config, scores)
    assert sorted(np.asarray(plain)[0].tolist()) == [1, 3]


# -- leaves a layer, state a slot ------------------------------------------------

def test_the_pool_and_the_state_take_their_geometry_from_the_model():
    """No KDA layer allocates pool blocks; the sparse layer keeps a latent
    row a token and one pooled key every four: 1,088 B a token at the
    published widths."""
    published = dataclasses.replace(
        M.HybridSparseConfig(dtype=jnp.bfloat16),
        layer_types=("kda", "kda", "kda", "kda", "dsa"),
        mlp_types=("dense",) + ("sparse",) * 4, vocab=256, experts_held=1)
    pool = BlockPool(published, 32, False, initial_blocks=2, name="geo-pub")
    assert pool.block_nbytes == 32 * 1088
    assert pool.k_pools[:4] == [None] * 4 and pool.v_pools[:4] == [None] * 4
    assert pool.k_pools[4].shape == (3, 1, 32, 512)
    assert pool.v_pools[4].shape == (3, 1, 8, 128)
    assert pool.nbytes() == 3 * 32 * 1088
    state = SlotState(published, 32)
    assert state.arrays[0][0].shape == (32, 64, 128, 128)
    assert state.arrays[0][0].dtype == jnp.float32
    assert state.arrays[0][1].shape == (32, 3 * 24576)
    assert state.arrays[4][0].shape == (32, 128)
    assert round(state.nbytes() / 1e9, 2) == 0.56
    # growth and copy walk the leaves that are there
    tiny = BlockPool(model_config(), 8, False, initial_blocks=4, name="geo")
    assert tiny.block_nbytes == 8 * (32 + 16 // 4) * 4
    ids = tiny.alloc_blocks(2)
    assert tiny.copy_blocks(ids[:1], ids[1:]) == tiny.block_nbytes
    tiny.reserve(12)
    assert tiny.k_pools[3].shape[0] >= 13 and tiny.k_pools[0] is None
    with pytest.raises(ValueError, match="whole rows"):
        BlockPool(model_config(), 6, False, name="odd")


def test_the_other_models_declare_the_same_leaves_for_every_layer():
    from aiko_services_tpu.models.llama import LLAMA_PRESETS
    from aiko_services_tpu.serving_paged import layer_leaves, token_nbytes
    llama = LLAMA_PRESETS["tiny"]
    assert layer_leaves(llama) == (((2, 16, 1), (2, 16, 1)),) * \
        llama.num_layers
    assert not getattr(llama, "slot_state", ())
    latent = latent_moe.LATENT_MOE_PRESETS["tiny"]
    assert layer_leaves(latent) == (((1, 128, 1),),) * 3
    assert token_nbytes(latent) == 3 * 128 * 4
    # who reads a slot's live blocks in the step is ONE answer a model
    from aiko_services_tpu.serving_paged import first_leaf, reads_own_pool
    # a head of 16: the kernel walks it in the interpreter, not on a chip
    assert llama.paged_model().walks(llama, False, True) == "kernel"
    assert llama.paged_model().walks(llama, False, False) is None
    assert llama.paged_model().walks(llama, True, True) is None
    assert not reads_own_pool(llama) and not reads_own_pool(latent)
    assert reads_own_pool(model_config())
    assert not hasattr(model_config(), "cache_leaves")
    assert first_leaf(model_config()) == (1, model_config().kv_rank)
    assert first_leaf(llama) == (2, 16) and first_leaf(latent) == (1, 128)


def test_groups_that_do_not_fill_a_tile_refuse_at_construction():
    with pytest.raises(ValueError, match="index_pool must divide a tile"):
        dataclasses.replace(model_config(), index_pool=3)
