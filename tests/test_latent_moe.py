# The latent-attention (MLA), routed-expert decoder (ISSUE 31) at a small
# size on the CPU in float32: the model against the benchmark's plain
# reference (benchmark/reference/latent_moe_lm.py: expanded attention only,
# experts as a loop, precision "highest"), prefill through admit and chunked
# extend then decode through the latent pool (gather path, and the pallas
# walk in interpret mode), absorbed against expanded attention on one cache,
# the YaRN table, the sixteen-way share tied to the uncut layer, a token no
# held expert takes, and the serving paths that refuse at construction.
#
# Comparisons are of LOGITS (or of attention outputs), never of sampled
# tokens.  Each tolerance states its reason; `test_bfloat16_would_fail`
# shows that the same computation in bfloat16 breaks them.

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
from aiko_services_tpu.models import latent_moe as M  # noqa: E402
from aiko_services_tpu.models import layers as L  # noqa: E402
from aiko_services_tpu.serving import ContinuousDecoder  # noqa: E402
from benchmark import weights_latent_moe as W  # noqa: E402
from benchmark.reference import latent_moe_lm as R  # noqa: E402

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


def model_config(sizes=SIZES, dtype=jnp.float32, max_seq=128):
    import latent_moe_decoder
    return latent_moe_decoder.model_config(sizes, max_seq, dtype)


@pytest.fixture(scope="module")
def params():
    return W.decoder_weights(W.key_for(SEED), SIZES, jnp.float32)


def reference_logits(tokens, sizes=SIZES, seed=SEED):
    return np.asarray(R.forward_logits(tokens, sizes, seed, jnp.float32))


def test_seeded_weights_have_the_programs_layout(params):
    ours = jax.eval_shape(
        lambda: M.latent_moe_init(jax.random.PRNGKey(0), model_config()))
    assert jax.tree.structure(ours) == jax.tree.structure(params)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(ours),
                                 jax.tree_util.tree_leaves_with_path(params)):
        assert a.shape == b.shape, jax.tree_util.keystr(path)


def test_full_forward_agrees_with_the_reference(params):
    tokens = np.random.default_rng(0).integers(1, 256, size=48)
    ours = M.latent_moe_forward(params, model_config(),
                                jnp.asarray(tokens)[None])[0]
    theirs = reference_logits(tokens)
    assert float(theirs.std()) > 0.5            # logits of spread ~1
    assert np.abs(np.asarray(ours) - theirs).max() < LOGIT_TOLERANCE


def test_bfloat16_would_fail(params):
    """The tolerance is tight enough: the same forward with bfloat16
    weights and activations, where float32 is stated, breaks it."""
    tokens = np.random.default_rng(0).integers(1, 256, size=48)
    low = jax.tree.map(lambda leaf: leaf.astype(jnp.bfloat16), params)
    ours = M.latent_moe_forward(low, model_config(dtype=jnp.bfloat16),
                                jnp.asarray(tokens)[None])[0]
    assert np.abs(np.asarray(ours) - reference_logits(tokens)).max() > \
        10 * LOGIT_TOLERANCE


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


# -- through the decoder: admit, chunked extend, decode through the pool --------

def serve(params, requests, kernel, config=None, **kwargs):
    """Tokens served through submit / pump with the paged latent pool;
    `kernel` latches the pallas walk as a TPU would (the kernel itself then
    runs in the interpreter: the verify skill's note on step_kernel)."""
    config = config or model_config()
    real = jax.default_backend
    if kernel:
        jax.default_backend = lambda: "tpu"
    try:
        decoder = ContinuousDecoder(
            params, config, paged_kv=True, kv_block=8, max_slots=4,
            max_seq=128, prefill_buckets=(8, 16), prefill_chunk=16,
            prefill_budget=16, steps_per_sync=4,
            name=f"latent-{'walk' if kernel else 'gather'}", **kwargs)
    finally:
        jax.default_backend = real
    assert decoder._walks_live is kernel and decoder.step_kernel is kernel
    served = {}
    for rid, (prompt, new) in requests.items():
        assert decoder.submit(rid, prompt, new, lambda rid, tokens:
                              served.__setitem__(rid, list(tokens)))
    for _ in range(200):
        if len(served) == len(requests):
            break
        decoder.pump()
    assert len(served) == len(requests)
    return served, decoder


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


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "walk"])
def test_prefill_then_decode_through_the_pool_agrees_with_one_forward(
        params, kernel):
    """A prompt of 10 goes in by one admit, one of 45 by an admit-less
    chain of three 16-token extends (the expanded path over the pool's
    rows, piece by piece), one of 21 by two; all decode 9 tokens through
    the latent pool together (the absorbed path), and each served token is
    the reference's best at its position to within the tolerance: in
    float32 a served token that is not the best lies a float32 rounding
    below it (LOGIT_TOLERANCE of a spread of 1), where a bfloat16 slip
    reads 1e-2 and more."""
    rng = np.random.default_rng(7)
    requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), 9)
                for n in (10, 45, 21)}
    served, decoder = serve(params, requests, kernel)
    assert decoder.stats["prefill_chunks"] >= 5 and decoder.stats["prefills"]
    for rid, gap in served_gaps(requests, served).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)
    # the expert layers' counters came back with the rounds: every pair of
    # the whole model lands on a held expert
    stats = decoder.stats
    assert 0 < stats["moe_layer_steps"] <= 2 * stats["steps"]
    assert stats["moe_pairs_here"] == stats["moe_pairs_routed"] > 0
    assert 0 < stats["moe_experts_hit"] <= 8 * stats["moe_layer_steps"]


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "walk"])
def test_prefixes_of_several_pieces_and_walks_of_several_chunks(
        params, kernel, monkeypatch):
    """At the cell's size an extend reads its prefix in pieces of 512
    positions and the walk a slot in chunks of 512; here both are cut to
    16, so that prompts of 77 and 100 span five and seven of them (and a
    last piece that is partly dead cells)."""
    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.ops import paged_attention
    monkeypatch.setattr(M, "_PREFIX_PIECE", 16)
    monkeypatch.setattr(paged_attention, "_CHUNK", 16)
    for cached in (serving_paged._paged_step_for,
                   serving_paged._paged_extend_fn_for):
        cached.cache_clear()
    try:
        rng = np.random.default_rng(11)
        requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), 7)
                    for n in (77, 100, 9)}
        served, decoder = serve(params, requests, kernel)
        assert decoder.stats["prefill_chunks"] >= 11
        assert max(served_gaps(requests, served).values()) < LOGIT_TOLERANCE
    finally:
        for cached in (serving_paged._paged_step_for,
                       serving_paged._paged_extend_fn_for):
            cached.cache_clear()


def test_a_served_token_altered_is_seen(params):
    rng = np.random.default_rng(8)
    requests = {"a": (rng.integers(1, 256, size=12).tolist(), 6)}
    served, _ = serve(params, requests, False)
    served["a"][2] = (served["a"][2] + 1) % 256
    assert served_gaps(requests, served)["a"] > 100 * LOGIT_TOLERANCE


def test_walk_and_gather_paths_serve_the_same_logits(params):
    """Kernel against oracle on one cache: the walk's two-pass softmax
    chunk by chunk against one softmax over the gathered view."""
    rng = np.random.default_rng(9)
    requests = {"a": (rng.integers(1, 256, size=70).tolist(), 12),
                "b": (rng.integers(1, 256, size=5).tolist(), 12)}
    walked, _ = serve(params, requests, True)
    gathered, _ = serve(params, requests, False)
    for path in (walked, gathered):
        assert max(served_gaps(requests, path).values()) < LOGIT_TOLERANCE


def test_absorbed_and_expanded_attention_agree_on_one_cache(params):
    """The same numbers two ways: the last token's attention over a cache
    of 37 rows, W_kvb applied to every row (expanded) or folded into the
    query and the output (absorbed)."""
    config = model_config()
    layer = params["layers"][1]
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


def share_layer(layer, first, held):
    """What a chip holding experts [first, first + held) keeps of a whole
    layer: everything, and those experts' rows."""
    return layer | {"experts": jax.tree.map(
        lambda w: w[first:first + held], layer["experts"])}


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


def test_a_token_no_held_expert_takes_gets_its_shared_expert_only(params):
    layer = params["layers"][1]
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


# -- the paths a latent pool is not carried through refuse, by name ---------------

@pytest.mark.parametrize("kwargs, named", [
    (dict(paged_kv=False), "dense slot cache"),
    (dict(kv_cache_dtype="int8"), "int8 KV cache"),
    (dict(speculate_k=2), "speculative decoding"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(weight_quant=True), "weight-only int8"),
], ids=["dense", "int8-kv", "speculation", "prefix-cache", "weight-quant"])
def test_paths_not_carried_for_a_latent_pool_refuse_at_construction(
        params, kwargs, named):
    kwargs = dict(paged_kv=True, kv_block=8, max_slots=2, max_seq=64) | kwargs
    if kwargs.get("prefix_cache"):
        kwargs["prefix_cache"] = serving.PrefixKVCache(block_tokens=8)
    with pytest.raises(ValueError, match=named):
        ContinuousDecoder(params, model_config(max_seq=64), **kwargs)


def test_tensor_parallel_weights_refuse_at_construction(params):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    sharded = dict(params)
    sharded["lm_head"] = {"w": jax.device_put(
        params["lm_head"]["w"], NamedSharding(mesh, P(None, "model")))}
    with pytest.raises(ValueError, match="tensor-parallel"):
        ContinuousDecoder(sharded, model_config(max_seq=64), paged_kv=True,
                          kv_block=8, max_slots=2, max_seq=64)


@pytest.mark.parametrize("path", ["drain", "wire-layout", "install",
                                  "disagg-client"])
def test_drain_and_the_kv_wire_refuse_by_name(params, path):
    decoder = ContinuousDecoder(params, model_config(max_seq=64),
                                paged_kv=True, kv_block=8, max_slots=2,
                                max_seq=64, name=f"refuse-{path}")
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
