# Continuous-batching decode engine tests (serving.py): iteration-level
# scheduling must be BIT-IDENTICAL to whole-batch greedy decode — slot
# isolation, staggered admission, slot reuse, EOS ejection.
# This file: the dense slot cache against the whole-batch oracle.  Split
# by what a case's decoder is built with: chunked prefill and weight
# quantization in test_serving_chunked.py, the int8 KV cache and speculation
# in test_serving_kv_spec.py, the paged pool's attention choice in
# test_serving_attention.py; each takes CONFIG, `params`, `oracle` from here.

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models.llama import (LLAMA_PRESETS,
                                            llama_greedy_decode, llama_init)
from aiko_services_tpu.serving import ContinuousDecoder

CONFIG = dataclasses.replace(LLAMA_PRESETS["tiny"], max_seq_len=96)


@pytest.fixture(scope="module")
def params():
    return llama_init(jax.random.PRNGKey(0), CONFIG)


def oracle(params, prompt, max_new, eos_token=None):
    out = llama_greedy_decode(params, CONFIG,
                              jnp.asarray([prompt], jnp.int32),
                              max_tokens=max_new, eos_token=eos_token)
    tokens = [int(t) for t in np.asarray(out)[0]]
    # the serving engine returns the pre-EOS prefix; the whole-batch
    # oracle pads with EOS after stopping — truncate to compare
    if eos_token is not None and eos_token in tokens:
        tokens = tokens[:tokens.index(eos_token)]
    return tokens


def _run_decoder(decoder, requests, rounds=300):
    """Submit {rid: (prompt, max_new)} and pump to completion."""
    done = {}
    for rid, (prompt, max_new) in requests.items():
        decoder.submit(rid, prompt, max_new,
                       lambda rid, t: done.update({rid: t}))
    for _ in range(rounds):
        decoder.pump()
        if len(done) == len(requests):
            break
    assert len(done) == len(requests), \
        f"{len(done)}/{len(requests)} completed"
    return done


def test_single_request_matches_oracle(params):
    decoder = ContinuousDecoder(params, CONFIG, max_slots=4,
                                prefill_buckets=(16,), steps_per_sync=4)
    done = {}
    prompt = [5, 9, 23, 7]
    decoder.submit("r0", prompt, 12, lambda rid, t: done.update({rid: t}))
    for _ in range(40):
        decoder.pump()
        if done:
            break
    assert done["r0"] == oracle(params, prompt, 12)


def test_concurrent_requests_are_isolated(params):
    """Different prompts decoded in adjacent slots must each match their
    own single-request oracle (KV cache isolation)."""
    decoder = ContinuousDecoder(params, CONFIG, max_slots=4,
                                prefill_buckets=(16,), steps_per_sync=4)
    done = {}
    prompts = {f"r{i}": [i + 3, (i * 7) % 50 + 1, 11] for i in range(4)}
    for rid, prompt in prompts.items():
        decoder.submit(rid, prompt, 10,
                       lambda rid, t: done.update({rid: t}))
    for _ in range(60):
        decoder.pump()
        if len(done) == 4:
            break
    for rid, prompt in prompts.items():
        assert done[rid] == oracle(params, prompt, 10), rid


def test_staggered_admission_matches_oracle(params):
    """A request admitted while another is mid-generation decodes the
    same tokens as when run alone — the iteration-level join must not
    perturb positions or caches."""
    decoder = ContinuousDecoder(params, CONFIG, max_slots=2,
                                prefill_buckets=(16,), steps_per_sync=2)
    done = {}
    early = [4, 19, 2, 31]
    late = [8, 8, 40]
    decoder.submit("early", early, 16,
                   lambda rid, t: done.update({rid: t}))
    for _ in range(3):
        decoder.pump()                 # early is mid-flight
    assert decoder.active_count == 1 and not done
    decoder.submit("late", late, 16, lambda rid, t: done.update({rid: t}))
    for _ in range(80):
        decoder.pump()
        if len(done) == 2:
            break
    assert done["early"] == oracle(params, early, 16)
    assert done["late"] == oracle(params, late, 16)


def test_slot_reuse_more_requests_than_slots(params):
    decoder = ContinuousDecoder(params, CONFIG, max_slots=2,
                                prefill_buckets=(16,), steps_per_sync=4)
    done = {}
    prompts = {f"r{i}": [i + 1, 2 * i + 5] for i in range(6)}
    for rid, prompt in prompts.items():
        decoder.submit(rid, prompt, 8,
                       lambda rid, t: done.update({rid: t}))
    for _ in range(200):
        decoder.pump()
        if len(done) == 6:
            break
    assert len(done) == 6
    for rid, prompt in prompts.items():
        assert done[rid] == oracle(params, prompt, 8), rid
    assert decoder.stats["completed"] == 6
    assert decoder.idle


def test_eos_ejects_early(params):
    """Set EOS to the token the model actually emits mid-sequence: the
    request must complete at that point with the EOS stripped."""
    prompt = [5, 9, 23, 7]
    full = oracle(params, prompt, 12)
    eos = full[5]                      # fires at step 5
    expected = full[:full.index(eos)]
    decoder = ContinuousDecoder(params, CONFIG, max_slots=2,
                                prefill_buckets=(16,), steps_per_sync=3,
                                eos_token=eos)
    done = {}
    decoder.submit("r0", prompt, 12, lambda rid, t: done.update({rid: t}))
    for _ in range(40):
        decoder.pump()
        if done:
            break
    assert done["r0"] == expected
    assert decoder.idle


def test_long_prompt_picks_larger_bucket(params):
    decoder = ContinuousDecoder(params, CONFIG, max_slots=2,
                                prefill_buckets=(8, 32), steps_per_sync=2)
    done = {}
    long_prompt = [(3 * i) % 40 + 1 for i in range(20)]   # > bucket 8
    decoder.submit("long", long_prompt, 8,
                   lambda rid, t: done.update({rid: t}))
    for _ in range(60):
        decoder.pump()
        if done:
            break
    assert done["long"] == oracle(params, long_prompt, 8)


def test_occupancy_and_stats(params):
    decoder = ContinuousDecoder(params, CONFIG, max_slots=4,
                                prefill_buckets=(16,), steps_per_sync=4)
    done = {}
    for i in range(4):
        decoder.submit(f"r{i}", [i + 2, 3], 8,
                       lambda rid, t: done.update({rid: t}))
    for _ in range(80):
        decoder.pump()
        if len(done) == 4:
            break
    assert decoder.stats["prefills"] == 4
    assert decoder.stats["completed"] == 4
    assert 0.0 < decoder.mean_occupancy() <= 1.0


@pytest.mark.slow   # >10 s call — tier-1 wall budget (ISSUE 7)
def test_soak_ragged_lengths_all_match_oracle(params):
    """20 requests, random prompts and max_new_tokens (1..9), 3 slots,
    steps_per_sync=3: retirements land at every offset inside the scan
    window and every slot is reused repeatedly — each result must still
    be bit-identical to its own oracle."""
    rng = np.random.default_rng(42)
    decoder = ContinuousDecoder(params, CONFIG, max_slots=3,
                                prefill_buckets=(16,), steps_per_sync=3)
    done = {}
    want = {}
    for i in range(20):
        rid = f"r{i}"
        prompt = [int(t) for t in
                  rng.integers(1, CONFIG.vocab, rng.integers(1, 9))]
        max_new = int(rng.integers(1, 10))
        want[rid] = (prompt, max_new)
        decoder.submit(rid, prompt, max_new,
                       lambda rid, t: done.update({rid: t}))
    for _ in range(600):
        decoder.pump()
        if len(done) == 20:
            break
    assert len(done) == 20
    for rid, (prompt, max_new) in want.items():
        assert done[rid] == oracle(params, prompt, max_new), rid
    assert decoder.idle and decoder.stats["completed"] == 20


def test_tp_sharded_decoder_matches_oracle(params):
    """Continuous decoding with TENSOR-PARALLEL params: weights sharded
    over the model axis (heads/ffn/vocab), XLA inserting the
    collectives — the 'agent sharded over a slice' serving shape
    (BASELINE config 5).  Tokens must match the unsharded oracle."""
    from aiko_services_tpu.models.llama import llama_axes
    from aiko_services_tpu.parallel import create_mesh, shard_pytree

    mesh = create_mesh({"data": 2, "model": 4})
    placed = shard_pytree(params, llama_axes(CONFIG), mesh)
    assert "model" in str(
        placed["layers"][0]["gate"]["w"].sharding.spec)

    decoder = ContinuousDecoder(placed, CONFIG, max_slots=2,
                                prefill_buckets=(16,), steps_per_sync=4)
    done = {}
    prompts = {"r0": [5, 9, 23, 7], "r1": [40, 2]}
    for rid, prompt in prompts.items():
        decoder.submit(rid, prompt, 10,
                       lambda rid, t: done.update({rid: t}))
    for _ in range(80):
        decoder.pump()
        if len(done) == 2:
            break
    for rid, prompt in prompts.items():
        assert done[rid] == oracle(params, prompt, 10), rid


@pytest.mark.slow   # >10 s call — tier-1 wall budget (ISSUE 7)
def test_long_context_sp_prefill_matches_forward(params):
    """Sequence-parallel prefill (ring attention over the seq axis) is
    numerically the plain forward — the long-context path a single
    chip's memory cannot hold (SURVEY §5.7)."""
    from aiko_services_tpu.models.llama import (llama_forward,
                                                llama_forward_sp)
    from aiko_services_tpu.parallel import create_mesh

    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, CONFIG.vocab, (2, 64)),
        jnp.int32)
    expected = llama_forward(params, CONFIG, tokens)

    mesh = create_mesh({"data": 2, "seq": 4})
    got = llama_forward_sp(params, CONFIG, tokens, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)
    # greedy continuation from the SP prefill matches too
    assert np.array_equal(np.asarray(got).argmax(-1)[:, -1],
                          np.asarray(expected).argmax(-1)[:, -1])


def test_attach_runs_off_event_engine(params, engine):
    decoder = ContinuousDecoder(params, CONFIG, max_slots=2,
                                prefill_buckets=(16,), steps_per_sync=4)
    done = {}
    decoder.submit("r0", [7, 7, 7], 6, lambda rid, t: done.update({rid: t}))
    first = decoder.attach(engine, period=0.001)
    # idempotent re-attach: same timer, no orphaned duplicate pump
    assert decoder.attach(engine, period=0.001) == first
    assert decoder.attached
    for _ in range(200):
        engine.clock.advance(0.001)
        engine.step()
        if done:
            break
    decoder.detach(engine)
    assert done["r0"] == oracle(params, [7, 7, 7], 6)


@pytest.mark.slow   # >10 s call — tier-1 wall budget (ISSUE 7)
def test_mixed_bucket_burst_admits_in_groups(params):
    """A burst spanning BOTH prefill buckets with more requests than
    free slots: the batched group admit (stacked prefill + device-side
    scatter + pad-slot no-op rows) must stay bit-identical to the
    per-request oracle for every request."""
    decoder = ContinuousDecoder(params, CONFIG, max_slots=4,
                                prefill_buckets=(8, 32), steps_per_sync=3)
    prompts = {
        "s0": [5, 9, 23],                                  # bucket 8
        "s1": [7, 2],                                      # bucket 8
        "s2": [(3 * i) % 40 + 1 for i in range(20)],       # bucket 32
        "s3": [11, 4, 6, 8, 1],                            # bucket 8
        "s4": [(5 * i) % 40 + 1 for i in range(12)],       # bucket 32
        "s5": [9],                                         # bucket 8
        "s6": [2, 4, 8, 16, 32, 3, 5, 7],                  # bucket 8
    }
    done = {}
    for rid, prompt in prompts.items():
        decoder.submit(rid, prompt, 6,
                       lambda r, t: done.update({r: t}))
    for _ in range(200):
        decoder.pump()
        if len(done) == len(prompts):
            break
    assert len(done) == len(prompts)
    for rid, prompt in prompts.items():
        assert done[rid] == oracle(params, prompt, 6), rid
    # group admits: 7 requests must NOT have cost 7 prefill dispatches
    # worth of host syncs — prefills stat counts requests, but the admit
    # path batches (indirectly visible: all completed, decoder idle)
    assert decoder.idle


def test_admit_width_pow2_compile_reuse(params):
    """Admit widths pad to powers of two: bursts of 3 and 4 share the
    width-4 program; a later burst of 2 uses width 2 — the compiled
    prefill table stays bounded."""
    decoder = ContinuousDecoder(params, CONFIG, max_slots=4,
                                prefill_buckets=(16,), steps_per_sync=2)
    done = {}
    for i in range(3):
        decoder.submit(f"a{i}", [i + 1, 2, 3], 2,
                       lambda r, t: done.update({r: t}))
    decoder.pump()
    assert (16, 4) in decoder._prefill_fns     # 3 → width 4
    while not decoder.idle:
        decoder.pump()
    for i in range(2):
        decoder.submit(f"b{i}", [i + 5], 2,
                       lambda r, t: done.update({r: t}))
    decoder.pump()
    assert (16, 2) in decoder._prefill_fns     # 2 → width 2
    while not decoder.idle:
        decoder.pump()
    assert len(done) == 5
    assert len(decoder._prefill_fns) == 2      # no per-n compile storm


# -- MoE llama through the same serving engine (EP load-bearing) ---------

MOE_CONFIG = dataclasses.replace(
    LLAMA_PRESETS["tiny_moe"], max_seq_len=96,
    # top_k == num_experts: every token reaches every expert, so no
    # capacity drops — serving batch composition cannot perturb
    # routing and the bit-identical oracle contract holds
    num_experts=2, top_k=2)


def test_moe_llama_serves_and_matches_oracle():
    """An MoE-FFN llama decodes through ContinuousDecoder and matches
    whole-batch greedy decode — the expert path is served, not just
    unit-tested (VERDICT r3 item 7)."""
    params = llama_init(jax.random.PRNGKey(3), MOE_CONFIG)
    assert "moe" in params["layers"][0] and "gate" not in \
        params["layers"][0]
    decoder = ContinuousDecoder(params, MOE_CONFIG, max_slots=4,
                                prefill_buckets=(16,), steps_per_sync=4)
    done = {}
    prompts = {f"m{i}": [i + 2, (i * 5) % 40 + 1, 9] for i in range(3)}
    for rid, prompt in prompts.items():
        decoder.submit(rid, prompt, 8,
                       lambda rid, t: done.update({rid: t}))
    for _ in range(60):
        decoder.pump()
        if len(done) == 3:
            break
    for rid, prompt in prompts.items():
        out = llama_greedy_decode(params, MOE_CONFIG,
                                  jnp.asarray([prompt], jnp.int32),
                                  max_tokens=8)
        assert done[rid] == [int(t) for t in np.asarray(out)[0]], rid


def test_moe_llama_expert_sharded_serving():
    """The 4-expert tiny_moe preset served with expert weights sharded
    over an expert mesh axis (EP): requests complete and expert leaves
    are actually distributed."""
    from aiko_services_tpu.models.llama import llama_axes
    from aiko_services_tpu.parallel import create_mesh, shard_pytree

    config = dataclasses.replace(LLAMA_PRESETS["tiny_moe"],
                                 max_seq_len=96)
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = create_mesh({"expert": 4}, devices=jax.devices()[:4])
    params = llama_init(jax.random.PRNGKey(4), config)
    placed = shard_pytree(params, llama_axes(config), mesh)
    sharding = placed["layers"][0]["moe"]["w_in"].sharding
    assert not sharding.is_fully_replicated
    decoder = ContinuousDecoder(placed, config, max_slots=4,
                                prefill_buckets=(16,), steps_per_sync=4)
    done = {}
    decoder.submit("e0", [7, 3, 21], 6,
                   lambda rid, t: done.update({rid: t}))
    for _ in range(40):
        decoder.pump()
        if done:
            break
    assert len(done.get("e0", [])) == 6


@pytest.mark.slow   # >10 s call — tier-1 wall budget (ISSUE 7)
def test_randomized_soak_matches_oracle():
    """Property-style soak of the round-4 serving rewrite (deferred
    admit, in-scan budgets, retire-aligned rounds, cache resize):
    randomized prompts, budgets, EOS, and submit timing must all stay
    bit-identical to whole-batch greedy decode."""
    rng = np.random.default_rng(7)
    params = llama_init(jax.random.PRNGKey(11), CONFIG)
    # a real EOS id the random model actually emits sometimes
    eos = 17
    decoder = ContinuousDecoder(params, CONFIG, max_slots=6,
                                prefill_buckets=(8, 16),
                                steps_per_sync=8, eos_token=eos,
                                t_block=32)
    requests = {}
    # prompt/budget draws quantized to a few values: the soak tests
    # SCHEDULING randomness (admission timing, budgets, EOS), and
    # free-form lengths would cost ~40 oracle jit compilations
    lengths = (3, 8, 13)
    budgets = (4, 9, 19)
    for i in range(40):
        prompt = rng.integers(
            1, CONFIG.vocab,
            size=lengths[int(rng.integers(0, 3))]).tolist()
        requests[f"s{i}"] = (prompt, budgets[int(rng.integers(0, 3))])
    done = {}
    pending = list(requests.items())
    rounds = 0
    while (pending or len(done) < len(requests)) and rounds < 400:
        # staggered, bursty submission
        for _ in range(int(rng.integers(0, 4))):
            if pending:
                rid, (prompt, max_new) = pending.pop(0)
                decoder.submit(rid, prompt, max_new,
                               lambda rid, t: done.update({rid: t}))
        decoder.pump()
        rounds += 1
    assert len(done) == len(requests), f"{len(done)}/{len(requests)}"
    for rid, (prompt, max_new) in requests.items():
        assert done[rid] == oracle(params, prompt, max_new,
                                   eos_token=eos), rid
    assert decoder.wasted_fraction() < 0.5       # sanity, not a target
