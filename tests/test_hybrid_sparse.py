# The hybrid decoder (ISSUE 33: KDA slot state beside a sparse-selected latent
# pool, hyper-connected streams, held experts with a correction bias) at a
# small size on the CPU in float32: the model against the benchmark's plain
# reference (benchmark/reference/hybrid_sparse_lm.py: the one-token
# recurrence, expanded keys and values, experts as a loop, precision
# "highest"), prefill through admit and chunked extend then decode through
# the pool AND the slot state, the chunked scan against the recurrence, a
# padded bucket, a reused slot, the choice of groups, the eight-way share,
# Sinkhorn, the pool's geometry, and the serving paths that refuse.
#
# Comparisons are of LOGITS, states or attention outputs, never of sampled
# tokens.  Each tolerance states its reason.

import contextlib
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "benchmark", "drivers")):
    if path not in sys.path:
        sys.path.insert(0, path)

import aiko_services_tpu.serving as serving  # noqa: E402
from aiko_services_tpu.models import hybrid_sparse as M  # noqa: E402
from aiko_services_tpu.models import latent_moe  # noqa: E402
from aiko_services_tpu.serving import ContinuousDecoder  # noqa: E402
from aiko_services_tpu.serving_paged import BlockPool, SlotState  # noqa: E402
from benchmark import weights_hybrid_sparse as W  # noqa: E402
from benchmark.reference import hybrid_sparse_lm as R  # noqa: E402

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


def model_config(sizes=SIZES, dtype=jnp.float32, max_seq=128):
    import hybrid_sparse_decoder
    return hybrid_sparse_decoder.model_config(sizes, max_seq, dtype)


@pytest.fixture(scope="module")
def params():
    return W.decoder_weights(W.key_for(SEED), SIZES, jnp.float32)


def reference_logits(tokens, sizes=SIZES, seed=SEED):
    return np.asarray(R.forward_logits(tokens, sizes, seed, jnp.float32))


def test_seeded_weights_have_the_programs_layout(params):
    assert model_config() == M.HYBRID_SPARSE_PRESETS["tiny"]
    ours = jax.eval_shape(
        lambda: M.hybrid_sparse_init(jax.random.PRNGKey(0), model_config()))
    assert jax.tree.structure(ours) == jax.tree.structure(params)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(ours),
                                 jax.tree_util.tree_leaves_with_path(params)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype), \
            jax.tree_util.keystr(path)


def test_full_forward_agrees_with_the_reference(params):
    """90 tokens: 22 groups where a query attends 3 and its own, so the
    choice of groups is in every later logit."""
    tokens = np.random.default_rng(0).integers(1, 256, size=90)
    ours = M.hybrid_sparse_forward(params, model_config(),
                                   jnp.asarray(tokens)[None])[0]
    theirs = reference_logits(tokens)
    assert float(theirs.std()) > 0.5            # logits of spread ~1
    assert np.abs(np.asarray(ours) - theirs).max() < LOGIT_TOLERANCE


def test_bfloat16_would_fail(params):
    tokens = np.random.default_rng(0).integers(1, 256, size=90)
    low = jax.tree.map(lambda leaf: leaf.astype(jnp.bfloat16)
                       if leaf.ndim > 1 else leaf, params)
    ours = M.hybrid_sparse_forward(low, model_config(dtype=jnp.bfloat16),
                                   jnp.asarray(tokens)[None])[0]
    assert np.abs(np.asarray(ours) - reference_logits(tokens)).max() > \
        10 * LOGIT_TOLERANCE


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


def test_sinkhorn_gives_unit_row_and_column_sums(params):
    config = model_config()
    streams = jax.random.normal(jax.random.PRNGKey(2), (50, 4, 64)) * 3
    hc = params["layers"][1]["hc_attn"]
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


def test_program_and_reference_choose_the_same_groups(params):
    """One sparse layer over 96 positions (24 groups, 3 chosen and the open
    one): the program's masked absorbed attention against the reference's
    expanded softmax over ITS chosen positions.  A group chosen otherwise
    moves the output by its whole weight, far beyond the tolerance; with
    the choice widened to every group the output is another."""
    config = model_config()
    layer = params["layers"][3]
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


# -- through the decoder: admit, chunked extend, decode through pool and state ---

def decoder_for(params, name, buckets=(8, 32), chunk=32, slots=4, **kwargs):
    return ContinuousDecoder(
        params, model_config(), paged_kv=True, kv_block=8, max_slots=slots,
        max_seq=128, prefill_buckets=buckets, prefill_chunk=chunk,
        prefill_budget=chunk, steps_per_sync=4, name=name, **kwargs)


def serve(params, requests, name="hybrid", kernel=False, scan=False,
          **kwargs):
    decoder = decoder_for(params, name, **kwargs)
    assert decoder._walks_live and decoder.step_kernel is kernel
    served = {}
    for rid, (prompt, new) in requests.items():
        assert decoder.submit(rid, prompt, new, lambda rid, tokens:
                              served.__setitem__(rid, list(tokens)))
    with scan_kernel_interpreted(scan):
        for _ in range(400):
            if len(served) == len(requests):
                break
            decoder.pump()
    assert len(served) == len(requests)
    return served, decoder


@contextlib.contextmanager
def scan_kernel_interpreted(scan: bool):
    """`scan`: a prompt's pieces take ops/delta_chunk's kernel, in the
    interpreter (the model takes it unasked on a chip alone: the choice is
    made where the admit and the extend are TRACED, so the builders'
    caches, which know nothing of it, are emptied around)."""
    if not scan:
        yield
        return
    from aiko_services_tpu import serving_paged
    builders = (serving_paged._paged_admit_fn_for,
                serving_paged._paged_extend_fn_for)
    for builder in builders:
        builder.cache_clear()
    traced, kernel = [], M.delta_chunk_scan
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(M, "_scan_kernel", lambda config, interpret: True)
        patch.setattr(M, "delta_chunk_scan",
                      lambda *args: traced.append(1) or kernel(*args))
        yield
    for builder in builders:
        builder.cache_clear()
    assert traced, "no admit or extend was traced through the chunk kernel"


def served_gaps(requests, served):
    """Per request, how far each served token's logit lies below the
    reference's best at its position (one full teacher-forced forward),
    in standard deviations of that position's logits."""
    out = {}
    for rid, (prompt, _) in requests.items():
        tokens = served[rid]
        logits = reference_logits(np.asarray(prompt + tokens[:-1]))
        at = logits[len(prompt) - 1:]
        out[rid] = float(((at.max(-1) - at[np.arange(len(tokens)), tokens])
                          / at.std(-1)).max())
    return out


@pytest.mark.parametrize("scan", [False, True],
                         ids=["chunked-by-xla", "chunk-kernel-interpreted"])
def test_prefill_then_decode_through_pool_and_state_agrees_with_one_forward(
        params, scan):
    """Six requests over four slots: prompts of 10 and 30 go in by one
    padded admit, 5 by a narrow one, 45 and 77 by chains of 32-token
    extends whose last chunk is padded, 64 by two whole chunks; two wait
    for a slot that another request leaves.  All decode 11 tokens, past
    16 positions, so every step chooses groups; each served token is the
    reference's best at its position to within the tolerance.  `scan`:
    the admits' and the extends' KDA layers through ops/delta_chunk's
    kernel (ISSUE 41), as a chip runs them."""
    rng = np.random.default_rng(7)
    requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), 11)
                for n in (10, 45, 77, 5, 30, 64)}
    served, decoder = serve(params, requests, name=f"agree-{scan}",
                            scan=scan)
    stats = decoder.stats
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


def test_a_served_token_altered_is_seen(params):
    rng = np.random.default_rng(8)
    requests = {"a": (rng.integers(1, 256, size=12).tolist(), 6)}
    served, _ = serve(params, requests, name="altered")
    served["a"][2] = (served["a"][2] + 1) % 256
    assert served_gaps(requests, served)["a"] > 100 * LOGIT_TOLERANCE


@pytest.mark.parametrize("length, new", [(40, 9), (6, 7)],
                         ids=["three-groups-a-step", "one-then-two-groups"])
def test_the_step_attends_the_chosen_groups_and_the_open_one(params, length,
                                                             new):
    """One request of two slots decoding a step a round: at position p the
    sparse layer takes min(3, p // 4) groups from the pool, attends their
    4 rows each and its open group's p % 4 + 1 positions, of p + 1 live,
    and fetches a whole tile of 8 rows a group taken (ISSUE 37)."""
    prompt = np.random.default_rng(9).integers(1, 256, size=length).tolist()
    decoder = ContinuousDecoder(
        params, model_config(), paged_kv=True, kv_block=8, max_slots=2,
        max_seq=128, prefill_buckets=(8, 32), prefill_chunk=32,
        prefill_budget=32, steps_per_sync=1, name=f"counted-{length}")
    done = []
    decoder.submit("a", prompt, new, lambda rid, tokens: done.append(tokens))
    while not done:
        decoder.pump()
    positions = range(length, length + new - 1)   # the admit gives the first
    taken = [min(3, p // 4) for p in positions]
    stats = decoder.stats
    assert stats["dsa_positions_live"] == sum(p + 1 for p in positions)
    assert stats["dsa_positions_attended"] == sum(
        4 * groups + p % 4 + 1 for groups, p in zip(taken, positions))
    assert stats["dsa_rows_fetched"] == 8 * sum(taken)


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
        params, p, block, wanted):
    """The sparse layer's step against the reference's layer over the
    same sequence, where the chosen groups lie as the case says in their
    tiles (a block of 16 holds two tiles, groups 2, 3 of its 4 the last;
    group 11 ends the third block of a slot of 49 positions).  What the
    other half of a fetched tile holds moves nothing, and the counts say
    what was fetched: a whole tile a group taken."""
    layer = params["layers"][3]
    h, chosen = _hidden_whose_choice(layer, p, wanted)
    table = [5, 2, 7, 1, 4, 6][:-(-(p + 1) // block)]
    ours, counted = _step_over_a_pool(layer, h, p, block, table)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(R.sparse_attention(layer, h, SIZES)[0][p])
    assert float(np.abs(theirs).max()) > 0.05
    assert np.abs(ours - theirs).max() < LOGIT_TOLERANCE
    assert counted == [p + 1, 4 * len(chosen) + p % 4 + 1, 8 * len(chosen)]


def test_a_reused_slots_step_reads_nothing_the_longer_request_left(params):
    """One slot: a request of 115 + 6 positions, which fills every block
    of the pool, then one of 21 + 11 in blocks the first gave back.  The
    tiles the second fetches hold the first's rows in their other halves
    and past its length; its tokens are the reference's to within the
    tolerance."""
    rng = np.random.default_rng(31)
    first = (rng.integers(1, 256, size=115).tolist(), 6)
    second = (rng.integers(1, 256, size=21).tolist(), 11)
    both, decoder = serve(params, {"a": first, "b": second},
                          name="reused-tiles", slots=1)
    assert decoder.stats["slot_states_zeroed"] == 2
    leaf = np.asarray(decoder.pool.k_pools[3])
    # whichever blocks the second took, the first had written them
    assert (np.abs(leaf[1:]).max(axis=(1, 2, 3)) > 0.05).all()
    for rid, gap in served_gaps({"a": first, "b": second}, both).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)


# -- the step computes for the slots that decode (ISSUE 42) ----------------------

WINDOW = M._STEP_WINDOW
ROUND_SLOTS, ROUND_STEPS, ROUND_BLOCK = WINDOW + 4, 4, 8


def _round_inputs(config):
    """What `jit_step` takes for `ROUND_SLOTS` slots in the middle of
    their answers, as numpy: RANDOM pools and slot state (both forms of
    the layer read the same, whatever it is), lengths of 17 to 50 (every
    step chooses groups), each slot's table its own blocks."""
    rng = np.random.default_rng(42)
    width = -(-(64 + ROUND_STEPS) // ROUND_BLOCK)
    pool = BlockPool(config, ROUND_BLOCK, False,
                     initial_blocks=ROUND_SLOTS * width, name="windows")

    def drawn(leaf):
        return None if leaf is None else rng.standard_normal(
            leaf.shape).astype(np.float32)

    tables = 1 + rng.permutation(ROUND_SLOTS * width).reshape(
        ROUND_SLOTS, width).astype(np.int32)
    state = [tuple(0.3 * drawn(leaf) for leaf in layer)
             for layer in SlotState(config, ROUND_SLOTS).arrays]
    return dict(
        tokens=rng.integers(1, 256, ROUND_SLOTS).astype(np.int32),
        lengths=rng.integers(17, 50, ROUND_SLOTS).astype(np.int32),
        k_pools=[drawn(leaf) for leaf in pool.k_pools],
        v_pools=[drawn(leaf) for leaf in pool.v_pools],
        tables=tables, state=state)


@pytest.fixture(scope="module")
def round_programs(params):
    """`jit_step` of the tiny model twice: as it is, and with the sparse
    layer's body run ONCE over every slot (`_dsa_window` at the full
    width between the projections, what `_dsa_step` was before it took
    windows): -> (inputs,
    run(program name, active, budgets) -> the program's results)."""
    from aiko_services_tpu import serving_paged
    config = model_config()
    inputs = _round_inputs(config)

    def every_slot(layer, config, x, cos, sin, tables, leaves, sides, left,
                   entry_lengths, lengths, step_index, entry_active, active):
        o_lat, sides, left, counted = M._dsa_window(
            config, M._dsa_project(layer, config, x, cos, sin, lengths),
            tables, leaves, sides, left, entry_lengths, lengths, step_index,
            active)
        return (M.absorb_output(layer["attn"], config, o_lat, 1), sides,
                left, jnp.concatenate([counted, jnp.zeros((2,), jnp.int32)]))

    def arguments(active, budgets):
        return (params, inputs["tokens"], inputs["lengths"], active,
                budgets, inputs["k_pools"], inputs["v_pools"],
                inputs["tables"], inputs["state"])

    programs = {}
    first = arguments(np.ones(ROUND_SLOTS, bool),
                      np.ones(ROUND_SLOTS, np.int32))
    for name in ("windows", "every-slot"):
        with pytest.MonkeyPatch.context() as patch:
            if name == "every-slot":
                patch.setattr(M, "_dsa_step", every_slot)
            programs[name] = serving_paged._build_paged_step(
                config, False).lower(
                    *first, num_steps=ROUND_STEPS, eos=-1,
                    t_cap=128).compile()

    def run(name, active, budgets):
        emitted, emitted_active, _, lengths, k_pools, v_pools, counts, \
            state = programs[name](*jax.tree.map(
                jnp.asarray, arguments(active, budgets)))
        return dict(
            emitted=np.asarray(emitted), active=np.asarray(emitted_active),
            lengths=np.asarray(lengths), pools=jax.tree.map(
                np.asarray, [k_pools, v_pools]),
            counts=dict(zip(M.HYBRID_COUNTERS, np.asarray(counts).tolist())),
            state=jax.tree.map(np.asarray, state))

    return inputs, run


@pytest.mark.parametrize("live, slot_zero", [
    (0, False), (1, True), (1, False), (WINDOW - 1, False), (WINDOW, True),
    (WINDOW + 1, True), (WINDOW + 1, False), (ROUND_SLOTS, True)],
    ids=str)
def test_the_sparse_step_computes_for_the_slots_live_at_entry(
        round_programs, live, slot_zero):
    """A round of four steps over 12 slots of which `live` decode, slot 0
    among them or not, one of them out of budget after two steps: the
    step that takes the live slots in windows of 8 serves what the body
    over every slot serves (tokens exactly, the pools and the state of
    the live slots to the tolerance), leaves what a slot that was not
    live holds as it was to the last bit, counts the same positions and
    rows, and computes for whole windows of the live slots alone."""
    inputs, run = round_programs
    rng = np.random.default_rng(100 * live + slot_zero)
    others = 1 + rng.permutation(ROUND_SLOTS - 1)
    chosen = ([0] if slot_zero else []) + others.tolist()
    active = np.zeros(ROUND_SLOTS, bool)
    active[chosen[:live]] = True
    budgets = np.where(active, ROUND_STEPS, 0).astype(np.int32)
    if live:
        budgets[chosen[live - 1]] = 2
    ours, theirs = run("windows", active, budgets), \
        run("every-slot", active, budgets)
    assert (ours["emitted"] == theirs["emitted"]).all()
    assert (ours["active"] == theirs["active"]).all()
    assert ours["active"].sum() == max(0, 4 * live - 2)
    for one, other in zip(jax.tree.leaves(ours["pools"]),
                          jax.tree.leaves(theirs["pools"])):
        assert np.abs(one - other).max() < LOGIT_TOLERANCE
    for one, other, before in zip(*map(jax.tree.leaves, (
            ours["state"], theirs["state"], inputs["state"]))):
        assert np.abs(one - other)[active].max(initial=0) < LOGIT_TOLERANCE
        assert (one[~active] == before[~active]).all()
    for name in M._DSA_COUNTERS + M._KDA_COUNTERS:
        assert ours["counts"][name] == theirs["counts"][name], name
    assert (ours["counts"]["dsa_positions_live"] > 0) == (live > 0)
    steps = int(ours["active"].any(axis=1).sum())
    assert ours["counts"]["dsa_slots_computed"] == \
        -(-live // WINDOW) * WINDOW * steps
    assert ours["counts"]["dsa_slots_decoding"] == ours["active"].sum()


# -- the step's recurrence: which form, and what it counts (ISSUE 34) ------------

KDA_LAYERS = 3
# a head of whole lanes, a tile of whole sublanes (ops/kda_step.py)
WIDE = SIZES | {"linear_attn_config": SIZES["linear_attn_config"] |
                {"head_dim": 128, "num_heads": 8}}


def _kda_attend(kernel, sizes, backend, monkeypatch):
    """The jaxpr of one KDA layer's token mixing in the decode step, as
    `_step_attention(kernel)` traces it on `backend`."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    config = model_config(sizes)
    layer = W.decoder_layer(W.key_for(SEED), 1, sizes, jnp.float32,
                            ("kda", "sparse"))
    state = tuple(jnp.zeros((3,) + shape, dtype)
                  for shape, dtype in config.slot_state[1])
    attend = M._step_attention(kernel)
    lengths = jnp.zeros((3,), jnp.int32)
    return jax.make_jaxpr(lambda x, state, active: attend(
        None, layer, config, x, None, None, [], None, [], lengths, lengths,
        0, active, state, active))(
            jnp.ones((3, 1, 64)), state, jnp.asarray([True, False, True]))


@pytest.mark.parametrize("kernel, sizes, backend, takes", [
    (True, WIDE, "tpu", True),        # the cell's case, at a head of 128
    (False, WIDE, "tpu", False),      # the decoder said no: sharded, asked
    (True, SIZES, "tpu", False),      # the `tiny` head of 16 on a chip
    (False, SIZES, "cpu", False),     # every CPU test
    (True, SIZES, "cpu", True),       # asked for off the chip: interpreter
], ids=["lanes-on-tpu", "not-chosen", "head-16-on-tpu", "cpu", "interpreter"])
def test_the_step_takes_the_kernel_where_the_head_is_whole_lanes(
        monkeypatch, kernel, sizes, backend, takes):
    text = str(_kda_attend(kernel, sizes, backend, monkeypatch))
    assert ("pallas_call" in text) is takes
    # the plain recurrence's two products over the state, or neither
    assert (text.count("dot_general") >= 2) or takes


@pytest.mark.parametrize("impl, sizes, backend, kernel", [
    (None, SIZES, "cpu", False), (None, SIZES, "tpu", False),
    (None, WIDE, "cpu", False), (None, WIDE, "tpu", True),
    ("two_pass", WIDE, "tpu", False), ("paged_kernel", SIZES, "cpu", True),
], ids=["tiny-cpu", "tiny-tpu", "lanes-cpu", "lanes-tpu", "lanes-tpu-gather",
        "tiny-cpu-asked"])
def test_the_decoder_chooses_and_says_which_form_of_the_recurrence(
        monkeypatch, impl, sizes, backend, kernel):
    """Told nothing, a hybrid decoder takes the kernel on a TPU at a head
    of whole lanes (its weights and state on one device) and
    `kda_recurrent` everywhere else; it says which on its logger."""
    import logging
    monkeypatch.setattr(serving, "ATTENTION_IMPL", impl)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    heard = []

    class Heard(logging.Handler):
        def emit(self, record):
            heard.append(record.getMessage())

    name = "form-%s-%d-%s" % (impl, len(str(sizes)), backend)
    logger = logging.getLogger(f"serving.{name}")
    handler = Heard(logging.INFO)
    logger.addHandler(handler)
    try:
        decoder = ContinuousDecoder(
            W.decoder_weights(W.key_for(SEED), sizes, jnp.float32),
            model_config(sizes), paged_kv=True, kv_block=8, max_slots=2,
            max_seq=128, prefill_buckets=(8,), prefill_chunk=32, name=name)
    finally:
        logger.removeHandler(handler)
    assert decoder._walks_live and decoder.step_kernel is kernel
    said = [m for m in heard if "recurrence over slot state" in m]
    assert len(said) == 1, heard
    assert ("pallas kernel" in said[0]) is kernel
    assert ("every slot" in said[0]) is not kernel


def test_the_kernel_asked_for_serves_what_the_plain_recurrence_serves(
        params, monkeypatch):
    """The `tiny` decoder as every test builds it (`kda_recurrent`), and
    with the kernel asked for (the interpreter, a head of 16): requests
    that decode side by side and leave at different steps, a chunked
    prompt among them.  Both within the tolerance of the reference, the
    same counts."""
    rng = np.random.default_rng(21)
    requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), new)
                for n, new in ((10, 11), (45, 6), (5, 9))}
    plain, one = serve(params, requests, name="plain-form")
    monkeypatch.setattr(serving, "ATTENTION_IMPL", "paged_kernel")
    asked, other = serve(params, requests, name="kernel-form", kernel=True)
    for served in (plain, asked):
        for rid, gap in served_gaps(requests, served).items():
            assert gap < LOGIT_TOLERANCE, (rid, gap)
    assert plain == asked
    for name in M.HYBRID_COUNTERS:
        assert one.stats[name] == other.stats[name], name


def test_the_step_counts_the_states_it_must_move_and_those_it_holds(params):
    """Three slots, two requests that decode 9 and 4 tokens, a step a
    round: a KDA layer moves the state of the slots that decode in a
    step, and holds every slot's."""
    rng = np.random.default_rng(22)
    decoder = ContinuousDecoder(
        params, model_config(), paged_kv=True, kv_block=8, max_slots=3,
        max_seq=128, prefill_buckets=(8, 32), prefill_chunk=32,
        prefill_budget=32, steps_per_sync=1, name="states-counted")
    done = {}
    for rid, (n, new) in {"a": (12, 10), "b": (20, 5)}.items():
        decoder.submit(rid, rng.integers(1, 256, size=n).tolist(), new,
                       lambda rid, tokens: done.__setitem__(rid, tokens))
    while len(done) < 2:
        decoder.pump()
    stats = decoder.stats
    assert {"kda_states_moved", "kda_states_held"} <= set(stats)
    # the first token of each comes from its admit: 9 + 4 slot-steps
    assert stats["useful_steps"] == 13
    assert stats["kda_states_moved"] == KDA_LAYERS * 13
    assert stats["kda_states_held"] == KDA_LAYERS * 3 * stats["steps"]
    assert 9 <= stats["steps"] <= 13


def _state_after_prefill(params, prompt, name, **kwargs):
    """The slot state and the first token after `prompt` alone has been
    prefilled (no decode step yet: it asks for one token)."""
    decoder = decoder_for(params, name, slots=2, **kwargs)
    done = []
    decoder.submit("a", prompt, 1, lambda rid, tokens: done.append(tokens))
    while not done:
        decoder.pump()
    return jax.tree.map(lambda leaf: np.asarray(leaf[0]),
                        decoder.slot_state.arrays), done[0], decoder


def _assert_states_agree(one, other):
    # S of spread ~0.5, tails of spread ~1, key sums ~1: float32 sums in
    # another order (chunks of other sizes)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(other)):
        assert a.shape == b.shape and np.abs(a - b).max() < 2e-5


def test_chunked_extend_equals_one_shot(params):
    """A prompt of 62 by ONE admit (a bucket of 64) and by four chunks of
    16 (the last padded): the state a slot holds after it, the rows the
    pool holds, the first token."""
    prompt = np.random.default_rng(10).integers(1, 256, size=62).tolist()
    whole, first, one = _state_after_prefill(params, prompt, "oneshot",
                                             buckets=(8, 64), chunk=64)
    assert one.stats["prefills"] == 1 and not one.stats["prefill_chunks"]
    pieces, again, many = _state_after_prefill(params, prompt, "chunked",
                                               buckets=(8, 16), chunk=16)
    assert many.stats["prefill_chunks"] == 4 and not many.stats["prefills"]
    assert list(first) == list(again)
    _assert_states_agree(whole, pieces)
    assert np.abs(whole[0][0]).max() > 0.05       # a state that is not zero
    assert np.abs(whole[3][0]).max() > 0.05       # 62 % 4 = 2 keys summed
    for ours, theirs, rows in ((one, many, 62), ):
        for side in ("k_pools", "v_pools"):
            a = np.asarray(getattr(ours.pool, side)[3])
            b = np.asarray(getattr(theirs.pool, side)[3])
            table_a = ours._tables_np[0]
            table_b = theirs._tables_np[0]
            per_block = a.shape[2]
            count = rows * per_block // 8         # whole rows of the leaf
            flat_a = a[table_a].reshape(-1, a.shape[-1])[:count]
            flat_b = b[table_b].reshape(-1, b.shape[-1])[:count]
            assert np.abs(flat_a - flat_b).max() < 1e-5, side


def test_a_padded_admit_bucket_leaves_state_as_the_unpadded_run_does(params):
    """20 tokens in a bucket of 32 (12 positions of padding that the scan
    and the convolution tail must pass over) and in a bucket of 20."""
    prompt = np.random.default_rng(11).integers(1, 256, size=20).tolist()
    padded, first, _ = _state_after_prefill(params, prompt, "padded",
                                            buckets=(8, 32))
    exact, again, _ = _state_after_prefill(params, prompt, "exact",
                                           buckets=(8, 20), chunk=32)
    assert list(first) == list(again)
    _assert_states_agree(padded, exact)
    # and it is the state of 20 tokens, not of 32: the same prompt with 12
    # more tokens behind it leaves another
    longer, _, _ = _state_after_prefill(params, prompt + [7] * 12, "longer",
                                        buckets=(8, 32))
    assert np.abs(longer[0][0] - padded[0][0]).max() > 1e-3


def test_a_slot_reused_after_retirement_starts_from_zero(params):
    """One slot: the second request is served in the slot the first left,
    by a chunked prefill (whose first chunk starts from zeros) and by an
    admit; its tokens are those it gets alone."""
    rng = np.random.default_rng(12)
    first = (rng.integers(1, 256, size=50).tolist(), 6)
    for n in (41, 9):
        second = (rng.integers(1, 256, size=n).tolist(), 9)
        alone, _ = serve(params, {"b": second}, name=f"alone{n}", slots=1)
        both, decoder = serve(params, {"a": first, "b": second},
                              name=f"reused{n}", slots=1)
        assert decoder.stats["slot_states_zeroed"] == 2
        assert both["b"] == alone["b"]
        assert max(served_gaps({"b": second}, both).values()) < \
            LOGIT_TOLERANCE


# -- the expert layer and its share ----------------------------------------------

def share_layer(layer, first, held):
    return layer | {"experts": jax.tree.map(
        lambda w: w[first:first + held], layer["experts"])}


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
    assert state.arrays[0][1].shape == (32, 3, 24576)
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


# -- the paths slot state is not carried through refuse, by name -----------------

@pytest.mark.parametrize("kwargs, named", [
    (dict(paged_kv=False), "dense slot cache"),
    (dict(kv_cache_dtype="int8"), "int8 KV cache"),
    (dict(speculate_k=2), "speculative decoding"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(weight_quant=True), "weight-only int8"),
    (dict(prefill_chunk=None), "prefill_chunk must be set"),
    (dict(prefill_chunk=24), "divide max_seq"),
    (dict(kv_block=4), "kv_block must be a multiple of 8"),
], ids=["dense", "int8-kv", "speculation", "prefix-cache", "weight-quant",
        "no-chunk", "chunk-not-dividing", "block-of-half-a-tile"])
def test_paths_not_carried_refuse_at_construction(params, kwargs, named):
    kwargs = dict(paged_kv=True, kv_block=8, max_slots=2, max_seq=64,
                  prefill_chunk=32) | kwargs
    if kwargs.get("prefix_cache"):
        kwargs["prefix_cache"] = serving.PrefixKVCache(block_tokens=8)
    with pytest.raises(ValueError, match=named):
        ContinuousDecoder(params, model_config(max_seq=64), **kwargs)


def test_groups_that_do_not_fill_a_tile_refuse_at_construction():
    with pytest.raises(ValueError, match="index_pool must divide a tile"):
        dataclasses.replace(model_config(), index_pool=3)


def test_tensor_parallel_weights_refuse_at_construction(params):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    sharded = dict(params)
    sharded["lm_head"] = {"w": jax.device_put(
        params["lm_head"]["w"], NamedSharding(mesh, P(None, "model")))}
    with pytest.raises(ValueError, match="tensor-parallel"):
        ContinuousDecoder(sharded, model_config(max_seq=64), paged_kv=True,
                          kv_block=8, max_slots=2, max_seq=64,
                          prefill_chunk=32)


@pytest.mark.parametrize("path", ["drain", "wire-layout", "install",
                                  "disagg-client"])
def test_drain_and_the_kv_wire_refuse_by_name(params, path):
    decoder = ContinuousDecoder(params, model_config(max_seq=64),
                                paged_kv=True, kv_block=8, max_slots=2,
                                max_seq=64, prefill_chunk=32,
                                name=f"refuse-hybrid-{path}")
    with pytest.raises(ValueError, match="not carried"):
        if path == "drain":
            decoder.drain()
        elif path == "wire-layout":
            decoder.kv_wire_layout()
        elif path == "install":
            decoder.install_shipped_blocks([1] * 16, 0, [{}])
        else:
            from aiko_services_tpu.serving_disagg import PrefillClient
            PrefillClient(None, decoder)
