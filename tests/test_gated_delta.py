# The Gated-DeltaNet hybrid decoder (ISSUE 40: recurrent layers with one gate
# a head, 6 heads of [8, 16] here, whose state is a SLOT's, beside full
# layers whose K and V the shared paged kernel walks) at a small size on the
# CPU in float32: the model against the benchmark's plain reference
# (benchmark/reference/gated_delta_lm.py: the recurrence token by token,
# plain softmax, precision "highest"), prefill through admit and chunked
# extend then decode through the pool AND the slot state, gathered views and
# the kernels in the interpreter, a slot that decodes nothing, a reused slot,
# the pool's geometry, and the serving paths that refuse.
#
# Comparisons are of LOGITS or states, never of sampled tokens.  Each
# tolerance states its reason.

import contextlib
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
from aiko_services_tpu import serving_paged  # noqa: E402
from aiko_services_tpu.models import gated_delta as M  # noqa: E402
from aiko_services_tpu.serving import ContinuousDecoder  # noqa: E402
from benchmark import weights_gated_delta as W  # noqa: E402
from benchmark.reference import gated_delta_lm as R  # noqa: E402

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


def model_config(sizes=SIZES, dtype=jnp.float32, max_seq=128):
    import gated_delta_decoder
    return gated_delta_decoder.model_config(sizes, max_seq, dtype)


@pytest.fixture(scope="module")
def params():
    return W.decoder_weights(W.key_for(SEED), SIZES, jnp.float32)


def reference_logits(tokens, sizes=SIZES, seed=SEED):
    return np.asarray(R.forward_logits(np.asarray(tokens)[None], sizes, seed,
                                       jnp.float32))[0]


def test_seeded_weights_have_the_programs_layout(params):
    assert model_config() == M.GATED_DELTA_PRESETS["tiny"]
    ours = jax.eval_shape(
        lambda: M.gated_delta_init(jax.random.PRNGKey(0), model_config()))
    assert jax.tree.structure(ours) == jax.tree.structure(params)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(ours),
                                 jax.tree_util.tree_leaves_with_path(params)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype), \
            jax.tree_util.keystr(path)


def test_full_forward_agrees_with_the_reference(params):
    """90 tokens: a chunk of 64 and a padded one through every recurrent
    layer, beta up to 2, one full layer between them."""
    tokens = np.random.default_rng(0).integers(1, 256, size=90)
    with jax.default_matmul_precision("highest"):
        ours = M.gated_delta_forward(params, model_config(),
                                     jnp.asarray(tokens)[None])[0]
    theirs = reference_logits(tokens)
    assert float(theirs.std()) > 0.5            # logits of spread ~1
    assert np.abs(np.asarray(ours) - theirs).max() < LOGIT_TOLERANCE


def test_bfloat16_would_fail(params):
    tokens = np.random.default_rng(0).integers(1, 256, size=90)
    low = jax.tree.map(lambda leaf: leaf.astype(jnp.bfloat16)
                       if leaf.ndim > 1 else leaf, params)
    ours = M.gated_delta_forward(low, model_config(dtype=jnp.bfloat16),
                                 jnp.asarray(tokens)[None])[0]
    assert np.abs(np.asarray(ours) - reference_logits(tokens)).max() > \
        10 * LOGIT_TOLERANCE


def test_the_reference_reads_the_published_switches():
    """`linear_allow_neg_eigval` false is another function (beta a plain
    sigmoid), in the reference as in the program."""
    tokens = np.random.default_rng(1).integers(1, 256, size=40)
    plain = SIZES | {"linear_allow_neg_eigval": False}
    theirs = reference_logits(tokens, plain)
    assert np.abs(theirs - reference_logits(tokens)).max() > 0.05
    params = W.decoder_weights(W.key_for(SEED), plain, jnp.float32)
    with jax.default_matmul_precision("highest"):
        ours = M.gated_delta_forward(params, model_config(plain),
                                     jnp.asarray(tokens)[None])[0]
    assert np.abs(np.asarray(ours) - theirs).max() < LOGIT_TOLERANCE


# -- through the decoder ---------------------------------------------------------

def decoder_for(params, name, slots=4, **kwargs):
    return ContinuousDecoder(
        params, model_config(), **({
            "paged_kv": True, "kv_block": 8, "max_slots": slots,
            "max_seq": 128, "prefill_buckets": (8, 32), "prefill_chunk": 32,
            "prefill_budget": 64, "steps_per_sync": 4, "name": name}
            | kwargs))


def serve(params, requests, name="gated-delta", kernel=False, **kwargs):
    """`kernel`: the decoder is ASKED for its kernels off the chip
    (`serving.ATTENTION_IMPL`), so both run in the interpreter: the walk of
    the full layers' pool and the recurrence over the live slots' state."""
    before = serving.ATTENTION_IMPL
    serving.ATTENTION_IMPL = "paged_kernel" if kernel else None
    try:
        decoder = decoder_for(params, name, **kwargs)
    finally:
        serving.ATTENTION_IMPL = before
    assert decoder.step_kernel is kernel and decoder._walks_live is kernel
    served = {}
    for rid, (prompt, new) in requests.items():
        assert decoder.submit(rid, prompt, new, lambda rid, tokens:
                              served.__setitem__(rid, list(tokens)))
    with scan_kernel_interpreted(kernel):
        for _ in range(400):
            if len(served) == len(requests):
                break
            decoder.pump()
    assert len(served) == len(requests)
    return served, decoder


@contextlib.contextmanager
def scan_kernel_interpreted(kernel: bool):
    """`kernel`: a prompt's pieces take ops/delta_chunk's kernel too, in
    the interpreter (the model takes it unasked on a chip alone: the
    choice is made where the admit and the extend are TRACED, so the
    builders' caches, which know nothing of it, are emptied around)."""
    if not kernel:
        yield
        return
    builders = (serving_paged._paged_admit_fn_for,
                serving_paged._paged_extend_fn_for)
    for builder in builders:
        builder.cache_clear()
    traced, scan = [], M.delta_chunk_scan
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(M, "_scan_kernel", lambda config, interpret: True)
        patch.setattr(M, "delta_chunk_scan",
                      lambda *args: traced.append(1) or scan(*args))
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


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["views-and-recurrence", "kernels-interpreted"])
def test_prefill_then_decode_through_pool_and_state_agrees_with_one_forward(
        params, kernel):
    """Six requests over four slots: prompts of 10 and 30 go in by one
    padded admit, 5 by a narrow one, 45 and 77 by chains of 32-token
    extends whose last chunk is padded (each chunk from the state the last
    one left, the full layer reading the chunks before it from the pool),
    64 by two whole chunks; two wait for a slot that another request
    leaves.  All decode 11 tokens; each served token is the reference's
    best at its position to within the tolerance."""
    rng = np.random.default_rng(7)
    requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), 11)
                for n in (10, 45, 77, 5, 30, 64)}
    served, decoder = serve(params, requests, name=f"agree-{kernel}",
                            kernel=kernel)
    stats = decoder.stats
    assert stats["prefill_chunks"] == 7 and stats["prefills"] == 3
    assert stats["slot_states_zeroed"] == 6
    for rid, gap in served_gaps(requests, served).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)
    # four recurrent layers: a state moved for every token a step decoded,
    # every slot's held at every step that ran
    assert stats["gdn_states_moved"] == 4 * stats["tokens_decode"]
    assert stats["gdn_states_held"] % (4 * 4) == 0
    assert 0 < stats["gdn_states_moved"] < stats["gdn_states_held"]


def test_a_served_token_altered_is_seen(params):
    rng = np.random.default_rng(8)
    requests = {"a": (rng.integers(1, 256, size=20).tolist(), 6)}
    served, _ = serve(params, requests, name="altered")
    served["a"][3] = (served["a"][3] + 1) % 256
    assert served_gaps(requests, served)["a"] > 100 * LOGIT_TOLERANCE


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["recurrence", "kernel-interpreted"])
def test_a_slot_that_does_not_decode_keeps_its_states_bits(params, kernel):
    """One layer's token mixing in the step over three slots of which the
    middle one decodes nothing: its state S and its convolution tail come
    back bit for bit, the others' change."""
    config = model_config()
    layer = params["layers"][1]
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


def test_a_fresh_request_starts_from_zeros_in_a_reused_slot(params):
    """One slot: a request of 100 + 6 positions, then one of 21 + 11 in the
    slot and the blocks the first gave back.  The second's tokens are the
    reference's to within the tolerance: nothing of the first's state, its
    convolution tail or its rows reaches them."""
    rng = np.random.default_rng(31)
    first = (rng.integers(1, 256, size=100).tolist(), 6)
    second = (rng.integers(1, 256, size=21).tolist(), 11)
    both, decoder = serve(params, {"a": first, "b": second}, name="reused",
                          slots=1)
    assert decoder.stats["slot_states_zeroed"] == 2
    for rid, gap in served_gaps({"a": first, "b": second}, both).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)


def test_the_pool_holds_rows_for_the_full_layers_alone(params):
    decoder = decoder_for(params, "geometry")
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
    assert state[0][1].shape == (4, 3, 6 * (8 + 8 + 16))
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


@pytest.mark.parametrize("kwargs, named", [
    (dict(paged_kv=False), "dense slot cache"),
    (dict(kv_cache_dtype="int8"), "int8 KV cache"),
    (dict(speculate_k=2), "speculative decoding"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(weight_quant=True), "weight-only int8"),
    (dict(prefill_chunk=None), "prefill_chunk must be set"),
    (dict(prefill_chunk=24), "divide max_seq"),
], ids=["dense", "int8-kv", "speculation", "prefix-cache", "weight-quant",
        "no-chunk", "chunk-not-dividing"])
def test_paths_not_carried_refuse_at_construction(params, kwargs, named):
    """By name, as for the other model with slot state: nothing here runs
    another model's code on this one's cache."""
    kwargs = dict(paged_kv=True, kv_block=8, max_slots=2, max_seq=64,
                  prefill_chunk=32) | kwargs
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
                          kv_block=8, max_slots=2, max_seq=64,
                          prefill_chunk=32)


@pytest.mark.parametrize("path", ["drain", "wire-layout", "install",
                                  "disagg-client"])
def test_drain_and_the_kv_wire_refuse_by_name(params, path):
    decoder = ContinuousDecoder(params, model_config(max_seq=64),
                                paged_kv=True, kv_block=8, max_slots=2,
                                max_seq=64, prefill_chunk=32,
                                name=f"refuse-gated-delta-{path}")
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
