# Continuous-batching decode engine tests (serving.py): iteration-level
# scheduling must be BIT-IDENTICAL to whole-batch greedy decode — slot
# isolation, staggered admission, slot reuse, EOS ejection.

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models.llama import (LLAMA_PRESETS, LlamaConfig,
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


# -- chunked prefill + latency SLOs (round 5) ----------------------------

def test_chunked_prefill_matches_oracle(params):
    """A prompt longer than the largest bucket streams in prefill_chunk
    pieces across rounds and must stay BIT-IDENTICAL to the whole-batch
    oracle — including the final chunk, which slides back to end at the
    prompt tail (overlap recompute is idempotent)."""
    decoder = ContinuousDecoder(params, CONFIG, max_slots=4,
                                prefill_buckets=(16,), steps_per_sync=4,
                                prefill_chunk=16)
    done = {}
    prompt = [(i * 13) % 50 + 1 for i in range(40)]   # 40 > bucket 16
    decoder.submit("long", prompt, 10,
                   lambda rid, t: done.update({rid: t}))
    for _ in range(60):
        decoder.pump()
        if done:
            break
    assert done["long"] == oracle(params, prompt, 10)
    # 40 tokens at chunk 16: [0,16) [16,32) then final slides to [24,40)
    assert decoder.stats["prefill_chunks"] == 3
    assert decoder.stats["chunk_admits"] == 1


def test_chunked_prefill_shorter_than_chunk(params):
    """Prompt between the bucket cap and one chunk: a single padded
    final chunk must still match the oracle (the garbage tail past the
    prompt is overwritten by decode before it is ever attended)."""
    decoder = ContinuousDecoder(params, CONFIG, max_slots=4,
                                prefill_buckets=(8,), steps_per_sync=4,
                                prefill_chunk=32)
    done = {}
    prompt = [(i * 7) % 40 + 2 for i in range(20)]    # 8 < 20 < 32
    decoder.submit("mid", prompt, 8,
                   lambda rid, t: done.update({rid: t}))
    for _ in range(40):
        decoder.pump()
        if done:
            break
    assert done["mid"] == oracle(params, prompt, 8)
    assert decoder.stats["prefill_chunks"] == 1


def test_chunked_prefill_mixed_with_short_requests(params):
    """Long prompts chunk in while short requests keep decoding; every
    request matches its own oracle (cache isolation across the extend
    scatter) and per-round prefill work stays bounded by
    prefill_budget + one guaranteed chunk."""
    budget = 16
    decoder = ContinuousDecoder(params, CONFIG, max_slots=4,
                                prefill_buckets=(16,), steps_per_sync=4,
                                prefill_chunk=16, prefill_budget=budget)
    done = {}
    prompts = {
        "s0": [3, 9, 4],
        "s1": [8, 2, 44, 6],
        "long0": [(i * 11) % 60 + 1 for i in range(40)],
        "long1": [(i * 5) % 30 + 7 for i in range(33)],
    }
    for rid in ("s0", "s1"):
        decoder.submit(rid, prompts[rid], 12,
                       lambda rid, t: done.update({rid: t}))
    decoder.pump()                       # shorts admitted and decoding
    for rid in ("long0", "long1"):
        decoder.submit(rid, prompts[rid], 8,
                       lambda rid, t: done.update({rid: t}))
    for _ in range(80):
        decoder.pump()
        if len(done) == len(prompts):
            break
    assert len(done) == len(prompts)
    for rid, prompt in prompts.items():
        max_new = 12 if rid.startswith("s") else 8
        assert done[rid] == oracle(params, prompt, max_new), rid
    assert decoder.stats["round_prefill_tokens_max"] <= budget + 16


def test_chunked_prefill_prompt_at_seq_cap(params):
    """The prompt-length cap with chunking is max_seq-1, not the
    largest bucket: a 95-token prompt (max_seq 96) admits, yields
    exactly its first token (zero decode budget — the owed-token
    path), and retires."""
    decoder = ContinuousDecoder(params, CONFIG, max_slots=2,
                                prefill_buckets=(16,), steps_per_sync=4,
                                prefill_chunk=32)
    done = {}
    prompt = [(i * 3) % 70 + 1 for i in range(95)]
    decoder.submit("cap", prompt, 8,
                   lambda rid, t: done.update({rid: t}))
    for _ in range(60):
        decoder.pump()
        if done:
            break
    assert done["cap"] == oracle(params, prompt, 8)[:len(done["cap"])]
    assert len(done["cap"]) == 1         # seq cap leaves room for one


def test_slo_stats_measured(params):
    """TTFT/ITL/stall percentiles come from per-request timestamps:
    every completed request contributes a TTFT sample, multi-token
    requests contribute ITL, and the fields are real milliseconds."""
    decoder = ContinuousDecoder(params, CONFIG, max_slots=4,
                                prefill_buckets=(16,), steps_per_sync=4)
    done = {}
    for i in range(8):
        decoder.submit(f"r{i}", [i + 2, 5, (i * 3) % 20 + 1], 10,
                       lambda rid, t: done.update({rid: t}))
    for _ in range(80):
        decoder.pump()
        if len(done) == 8:
            break
    assert len(done) == 8
    slo = decoder.slo_stats()
    assert slo["ttft_count"] == 8
    assert slo["itl_count"] == 8          # all emitted 10 tokens
    assert slo["ttft_p50_ms"] is not None and slo["ttft_p50_ms"] >= 0
    assert slo["ttft_p95_ms"] >= slo["ttft_p50_ms"]
    assert slo["itl_p50_ms"] is not None and slo["itl_p50_ms"] >= 0
    # multi-sync requests (10 tokens at 4 steps/sync) saw >=2 bursts,
    # so the stall metric has samples
    assert slo["stall_p95_ms"] is not None


@pytest.mark.slow   # >10 s call — tier-1 wall budget (ISSUE 7)
def test_prompt_heavy_bursty_soak_chunked(params):
    """Prompt-heavy bursty load through the chunked-prefill path: long
    prompts arrive in bursts while short requests decode.  Every
    request stays oracle-exact, per-round prefill work stays bounded
    (the admit-stall guarantee), and the SLO surface carries measured
    TTFT/ITL/stall percentiles for every completed request."""
    rng = np.random.default_rng(11)
    budget = 32
    decoder = ContinuousDecoder(params, CONFIG, max_slots=4,
                                prefill_buckets=(16,), steps_per_sync=4,
                                prefill_chunk=16, prefill_budget=budget)
    requests = {}
    for i in range(10):
        if i % 2:
            length = int(rng.integers(20, 60))     # prompt-heavy half
        else:
            length = int(rng.integers(2, 12))
        prompt = rng.integers(1, CONFIG.vocab, size=length).tolist()
        requests[f"b{i}"] = (prompt, int(rng.integers(4, 10)))
    done = {}
    pending = list(requests.items())
    rounds = 0
    while (pending or len(done) < len(requests)) and rounds < 300:
        for _ in range(int(rng.integers(0, 3))):   # bursty arrivals
            if pending:
                rid, (prompt, max_new) = pending.pop(0)
                decoder.submit(rid, prompt, max_new,
                               lambda rid, t: done.update({rid: t}))
        decoder.pump()
        rounds += 1
    assert len(done) == len(requests), f"{len(done)}/{len(requests)}"
    for rid, (prompt, max_new) in requests.items():
        assert done[rid] == oracle(params, prompt, max_new), rid
    # the admit-stall bound: no single round dispatched more prefill
    # work than the budget plus the one guaranteed progress chunk
    assert decoder.stats["round_prefill_tokens_max"] <= budget + 16
    slo = decoder.slo_stats()
    assert slo["ttft_count"] == len(requests)
    assert slo["itl_p95_ms"] is not None
    assert slo["stall_p95_ms"] is not None


@pytest.mark.slow   # >10 s call — tier-1 wall budget (ISSUE 7)
def test_weight_quant_serving_completes_and_tracks(params):
    """Weight-only int8 serving (weight_quant=True,
    layers.quantize_linear_tree): requests complete through the full
    engine and outputs stay exact-algebra consistent — the W8 decoder
    must agree WITH ITSELF across the engine's paths (bucketed
    prefill + decode scan vs the same engine at different slot
    pressure), since int8 rounding breaks bit-parity with the bf16
    oracle by design (measured device step −2.6% at 1b — a memory
    lever; see layers.quantize_linear)."""
    outs = {}
    for tag, slots in (("narrow", 2), ("wide", 6)):
        decoder = ContinuousDecoder(params, CONFIG, max_slots=slots,
                                    prefill_buckets=(16,),
                                    steps_per_sync=4,
                                    weight_quant=True)
        done = {}
        prompts = {f"r{i}": [i + 3, (i * 11) % 50 + 1, 7, 2]
                   for i in range(6)}
        for rid, prompt in prompts.items():
            decoder.submit(rid, prompt, 10,
                           lambda rid, t: done.update({rid: t}))
        for _ in range(120):
            decoder.pump()
            if len(done) == len(prompts):
                break
        assert len(done) == len(prompts)
        outs[tag] = done
    # scheduling must not change W8 outputs: same tokens regardless of
    # slot pressure (the bit-parity property, internal to the mode)
    assert outs["narrow"] == outs["wide"]


def test_quantize_linear_roundtrip_and_tree():
    """Per-output-channel int8: reconstruction error bounded by half a
    quantization step per channel; the tree walk converts linears
    only (conv 3-D weights, embeddings, norms, and excluded router
    keys untouched) and linear() consumes the result transparently."""
    from aiko_services_tpu.models import layers as L

    key = jax.random.PRNGKey(3)
    lin = L.linear_init(key, 24, 16, bias=True, dtype=jnp.float32)
    q = L.quantize_linear(lin)
    assert q["w8"].dtype == jnp.int8 and q["s"].shape == (16,)
    recon = np.asarray(q["w8"], np.float32) * np.asarray(q["s"])
    err = np.abs(recon - np.asarray(lin["w"]))
    assert np.all(err <= np.asarray(q["s"]) * 0.51 + 1e-7)

    x = jax.random.normal(jax.random.PRNGKey(4), (3, 24), jnp.float32)
    y_full = np.asarray(L.linear(lin, x))
    y_q = np.asarray(L.linear(q, x))
    assert np.allclose(y_full, y_q, atol=0.05, rtol=0.05)

    tree = {
        "lin": lin,
        "conv": L.conv1d_init(key, 4, 8, 3),
        "embed": L.embedding_init(key, 10, 6),
        "norm": L.layer_norm_init(6),
        "router": L.linear_init(key, 6, 4, bias=False),
        "stack": [L.linear_init(key, 8, 8, bias=False)],
    }
    out = L.quantize_linear_tree(tree)
    assert "w8" in out["lin"] and "b" in out["lin"]
    assert "w8" in out["stack"][0]
    assert "w" in out["conv"] and out["conv"]["w"].ndim == 3
    assert "table" in out["embed"]
    assert "scale" in out["norm"]
    assert "w" in out["router"] and "w8" not in out["router"]


# -- int8 KV cache + self-speculative decoding (round 7) -----------------

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


def test_int8_kv_logits_within_tolerance(params):
    """The serving int8 KV storage (layers.quantize_kv_cache,
    per-(batch, head, position) scales) perturbs a decode step's
    logits by at most int8 rounding: dequantized caches reproduce the
    f32-cache logits within tolerance — what bounds the engine-level
    divergence of the int8 decoder."""
    from aiko_services_tpu.models import layers as L
    from aiko_services_tpu.models.llama import (init_llama_caches,
                                                llama_decode_step)

    rng = np.random.default_rng(5)
    prompt = jnp.asarray(rng.integers(1, CONFIG.vocab, (2, 24)),
                         jnp.int32)
    caches = init_llama_caches(CONFIG, 2, 32)
    logits, caches = llama_decode_step(params, CONFIG, prompt, caches)
    next_tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    exact, _ = llama_decode_step(params, CONFIG, next_tok, caches,
                                 position_offset=24)
    rounded = []
    for cache in caches:
        kq = L.quantize_kv_cache(cache["k"])
        vq = L.quantize_kv_cache(cache["v"])
        assert kq["q"].dtype == jnp.int8
        assert kq["s"].shape == cache["k"].shape[:-1]
        rounded.append({
            "k": L.dequantize_kv_cache(kq, cache["k"].dtype),
            "v": L.dequantize_kv_cache(vq, cache["v"].dtype),
            "index": cache["index"]})
    approx, _ = llama_decode_step(params, CONFIG, next_tok, rounded,
                                  position_offset=24)
    exact, approx = np.asarray(exact), np.asarray(approx)
    scale = max(1.0, float(np.abs(exact).max()))
    assert float(np.abs(approx - exact).max()) / scale < 0.02
    # roundtrip error itself is bounded by half a quantization step
    kv = np.asarray(caches[0]["k"])
    deq = np.asarray(rounded[0]["k"])
    step = np.abs(kv).max(axis=-1, keepdims=True) / 127.0
    assert np.all(np.abs(deq - kv) <= step * 0.51 + 1e-7)


def test_int8_kv_engine_parity_multichunk(params):
    """kv_cache_dtype='int8' end-to-end through the engine — bucketed
    admits, MULTI-CHUNK prefill (extend writes quantized rows against a
    dequantized prefix), and decode — emits the same greedy tokens as
    the full-precision engine on this geometry (int8 KV rounding is
    far below the test model's argmax margins)."""
    requests = {
        "short": ([5, 9, 23, 7], 10),
        "mid": ([(i * 7) % 40 + 2 for i in range(14)], 8),
        # 40 tokens at chunk 16: exercises extend rounds + final slide
        "long": ([(i * 13) % 50 + 1 for i in range(40)], 8),
    }
    kwargs = dict(max_slots=4, prefill_buckets=(16,), steps_per_sync=4,
                  prefill_chunk=16)
    full = _run_decoder(
        ContinuousDecoder(params, CONFIG, **kwargs), requests)
    i8 = ContinuousDecoder(params, CONFIG, kv_cache_dtype="int8",
                           **kwargs)
    quant = _run_decoder(i8, requests)
    assert quant == full
    assert i8.stats["prefill_chunks"] >= 3      # chunked path ran
    assert i8.stats["tokens_prefill"] == sum(
        len(p) for p, _ in requests.values())


def test_int8_kv_cache_bytes_halved(params):
    """The allocation the mode exists for: int8 values + f32
    per-(slot, head, position) scales vs full-precision values —
    ~(D+4)/(4D) of the f32 cache here, well under half."""
    kwargs = dict(max_slots=4, prefill_buckets=(16,), steps_per_sync=4)
    full = ContinuousDecoder(params, CONFIG, **kwargs)
    i8 = ContinuousDecoder(params, CONFIG, kv_cache_dtype="int8",
                           **kwargs)
    assert i8.kv_cache_bytes() < 0.6 * full.kv_cache_bytes()
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ContinuousDecoder(params, CONFIG, kv_cache_dtype="int4",
                          **kwargs)


def test_speculative_greedy_equivalence(params):
    """speculate_k on/off emits IDENTICAL token ids — the acceptance
    rule's whole point.  The prompt set forces both fates: a repetitive
    prompt the n-gram drafter accepts from, and unstructured prompts
    whose drafts reject (rejected drafts must not corrupt the side
    merge or the emitted stream)."""
    requests = {
        "plain": ([5, 9, 23, 7], 16),
        "tiny": ([40, 2], 16),
        "loop": ([7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8], 16),
    }
    kwargs = dict(max_slots=4, prefill_buckets=(16,), steps_per_sync=4)
    base = _run_decoder(
        ContinuousDecoder(params, CONFIG, **kwargs), requests)
    spec = ContinuousDecoder(params, CONFIG, speculate_k=3, **kwargs)
    out = _run_decoder(spec, requests)
    assert out == base
    # both fates actually occurred
    assert spec.stats["spec_proposed"] > 0
    assert 0.0 < spec.accept_rate() < 1.0
    assert spec.stats["accepted_per_step"] > 1.0
    # fewer verify iterations than emitted tokens = multi-token steps
    assert spec.stats["useful_steps"] < spec.stats["tokens_decode"]


@pytest.mark.slow   # >10 s call — tier-1 wall budget (ISSUE 7)
def test_speculative_midstream_admit_and_eos(params):
    """Speculation under scheduler churn: requests admitted mid-stream
    (the verify scan must not perturb mid-prefill or newly-admitted
    slots) and an EOS retiring a slot mid-burst — all equal to the
    non-speculative engine under the same EOS."""
    prompt = [5, 9, 23, 7]
    full = oracle(params, prompt, 12)
    eos = full[5]
    kwargs = dict(max_slots=2, prefill_buckets=(16,), steps_per_sync=4,
                  eos_token=eos)

    def staged(decoder):
        done = {}
        decoder.submit("early", prompt, 12,
                       lambda rid, t: done.update({rid: t}))
        for _ in range(3):
            decoder.pump()
        for rid, (p, n) in {"late": ([8, 8, 40], 12),
                            "loop": ([3, 4, 3, 4, 3, 4, 3], 10)}.items():
            decoder.submit(rid, p, n,
                           lambda rid, t: done.update({rid: t}))
        for _ in range(200):
            decoder.pump()
            if len(done) == 3:
                break
        assert len(done) == 3
        return done

    base = staged(ContinuousDecoder(params, CONFIG, **kwargs))
    out = staged(ContinuousDecoder(params, CONFIG, speculate_k=3,
                                   **kwargs))
    assert out == base
    assert base["early"] == full[:full.index(eos)]


@pytest.mark.slow   # >10 s call — tier-1 wall budget (ISSUE 7)
def test_speculative_with_int8_kv(params):
    """The two ISSUE 7 levers COMPOSE: the speculative verify scan
    reading an int8 main cache (scale fold) with scatter-merged
    quantized side rows emits the same tokens as the non-speculative
    int8 engine — including through chunked prefill."""
    requests = {
        "loop": ([7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8], 12),
        "long": ([(i * 13) % 50 + 1 for i in range(40)], 8),
    }
    kwargs = dict(max_slots=4, prefill_buckets=(16,), steps_per_sync=4,
                  prefill_chunk=16, kv_cache_dtype="int8")
    base = _run_decoder(
        ContinuousDecoder(params, CONFIG, **kwargs), requests)
    out = _run_decoder(
        ContinuousDecoder(params, CONFIG, speculate_k=2, **kwargs),
        requests)
    assert out == base


def test_eos_as_first_token_counts_no_decode_tokens(params):
    """The prefill argmax itself being EOS retires the slot at wave
    resolution — the scan emissions the device produced for it are
    discarded AND excluded from tokens_decode (the counter tracks
    delivered token flow, not device work; useful/wasted_steps keep
    the device-work view)."""
    prompt = [5, 9, 23, 7]
    first = oracle(params, prompt, 1)[0]
    decoder = ContinuousDecoder(params, CONFIG, max_slots=2,
                                prefill_buckets=(16,), steps_per_sync=4,
                                eos_token=first)
    done = {}
    decoder.submit("r0", prompt, 8, lambda rid, t: done.update({rid: t}))
    for _ in range(20):
        decoder.pump()
        if "r0" in done:
            break
    assert done["r0"] == []                  # EOS stripped, nothing else
    assert decoder.stats["tokens_decode"] == 0
    assert decoder.stats["completed"] == 1


def test_offpath_prefill_stats_split(params):
    """The decode/prefill accounting stops aliasing: tokens_decode
    counts scan emissions, tokens_prefill counts prompt tokens, both
    mirror into the process metrics registry, and decode_s covers the
    scan wall only (the admit wave resolves first tokens without a
    scan of its own)."""
    from aiko_services_tpu.observe import default_registry

    decoder = ContinuousDecoder(params, CONFIG, max_slots=4,
                                prefill_buckets=(16,), steps_per_sync=4)
    requests = {f"r{i}": ([i + 2, 5, (i * 3) % 20 + 1], 8)
                for i in range(4)}
    _run_decoder(decoder, requests)
    assert decoder.stats["tokens_prefill"] == 12      # 4 prompts x 3
    # every generated token is a scan emission EXCEPT each request's
    # first (resolved from its admit wave, off-scan)
    assert decoder.stats["tokens_decode"] == 4 * (8 - 1)
    assert decoder.stats["decode_s"] > 0.0
    registry = default_registry()
    for kind in ("tokens_decode", "tokens_prefill"):
        assert registry.value("serving_decoder_total",
                              {"kind": kind}) >= decoder.stats[kind]


@pytest.mark.parametrize("impl", ["online", "vpu", "two-pass"])
def test_unknown_attention_impl_is_refused(params, impl):
    """serving.ATTENTION_IMPL has two values; anything else (the
    removed "online" and "vpu", a typo) is refused where a decoder is
    built, dense or paged, and never silently served as two_pass."""
    from aiko_services_tpu import serving
    before = serving.ATTENTION_IMPL
    serving.ATTENTION_IMPL = impl
    try:
        for paged in (False, True):
            with pytest.raises(ValueError, match="two_pass.*paged_kernel"):
                ContinuousDecoder(params, CONFIG, max_slots=2,
                                  prefill_buckets=(16,), paged_kv=paged)
    finally:
        serving.ATTENTION_IMPL = before


# a pool geometry whose live blocks the kernel walks by hand on a chip:
# a head of 128 (ops.paged_attention.walks_live_blocks)
HEAD128 = dataclasses.replace(LLAMA_PRESETS["tiny"], dim=256, num_heads=2,
                              num_kv_heads=1, max_seq_len=96)


def _paged_as(impl, params, config, **kwargs):
    """A paged decoder built with serving.ATTENTION_IMPL at `impl`."""
    from aiko_services_tpu import serving
    before = serving.ATTENTION_IMPL
    serving.ATTENTION_IMPL = impl
    try:
        return ContinuousDecoder(params, config, max_slots=4,
                                 prefill_buckets=(16,), steps_per_sync=4,
                                 paged_kv=True, kv_block=8, **kwargs)
    finally:
        serving.ATTENTION_IMPL = before


@pytest.mark.parametrize("impl, backend, kwargs, step_kernel, asked", [
    # told nothing: the gather path on the CPU (the kernel would run in
    # the interpreter), the kernel on a chip
    (None, "cpu", {}, False, False),
    (None, "tpu", {}, True, False),
    # the speculative step takes the kernel only where it was asked for
    (None, "tpu", {"speculate_k": 2}, False, False),
    # an int8 pool's scales are nothing mosaic slices out of HBM: the
    # kernel's table body reads every entry, no gain over views
    (None, "tpu", {"kv_cache_dtype": "int8"}, False, False),
    # both names, said out loud, mean what they mean wherever
    ("two_pass", "tpu", {}, False, False),
    ("paged_kernel", "cpu", {}, True, True),
    ("paged_kernel", "tpu", {"speculate_k": 2}, True, True),
])
def test_attention_choice_follows_what_the_decoder_observes(
        monkeypatch, impl, backend, kwargs, step_kernel, asked):
    params = llama_init(jax.random.PRNGKey(0), HEAD128)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    decoder = _paged_as(impl, params, HEAD128, **kwargs)
    assert decoder.step_kernel == step_kernel
    assert decoder.paged_kernel == asked        # the extend's, the spec's
    # a step that walks live blocks has no width: one program
    walks = step_kernel and not kwargs.get("kv_cache_dtype")
    assert decoder._walks_live == walks
    assert decoder._attend_widths == (96,)      # max_seq under the floor


def test_attention_choice_on_the_tiny_head_and_sharded_weights(monkeypatch):
    """What else the choice reads: a head of 16 is no geometry the
    kernel walks by hand on a chip, and a decoder whose weights came in
    sharded over several devices (tensor parallel: the decoder holds no
    mesh, leaf placements are what it can see) stays on the gather
    path."""
    from aiko_services_tpu.models.llama import llama_axes
    from aiko_services_tpu.parallel import create_mesh, shard_pytree
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tiny = llama_init(jax.random.PRNGKey(0), CONFIG)
    assert not _paged_as(None, tiny, CONFIG).step_kernel
    params = llama_init(jax.random.PRNGKey(0), HEAD128)
    assert _paged_as(None, params, HEAD128).step_kernel
    mesh = create_mesh({"model": 2}, devices=jax.devices()[:2])
    placed = shard_pytree(params, llama_axes(HEAD128), mesh)
    assert any(len(leaf.sharding.device_set) > 1
               for leaf in jax.tree_util.tree_leaves(placed))
    decoder = _paged_as(None, placed, HEAD128)
    assert not decoder.step_kernel and not decoder._walks_live


def test_kernel_decoder_has_one_step_program_and_records_its_walk(
        params, monkeypatch):
    """A decoder whose step walks live blocks compiles ONE program a
    step count (the gather decoder: one a width of its ladder) and its
    rounds record, as attend_width, the mean over the scanned slots of
    what the kernel walks for them: the length at round entry in whole
    blocks."""
    from aiko_services_tpu import serving
    from aiko_services_tpu.observe import profiler
    monkeypatch.setattr(serving, "_ATTEND_FLOOR", 24)
    gather = _paged_as("two_pass", params, CONFIG, name="choice-gather")
    kernel = _paged_as("paged_kernel", params, CONFIG, name="choice-kernel")
    assert gather._attend_widths == (24, 48, 96)
    assert kernel._attend_widths == (96,)
    requests = {"a": ([3 + i for i in range(3)], 10),
                "b": ([5 + i for i in range(14)], 10)}
    assert _run_decoder(gather, requests) == _run_decoder(kernel, requests)
    assert {key[:2] for key in kernel._step_programs} == {(4, 96)}
    assert {key[:2] for key in gather._step_programs} == \
        {(4, 24), (4, 48), (4, 96)}
    width = profiler.ROUND_RECORD.index("attend_width")
    steps = profiler.ROUND_RECORD.index("num_steps")
    walked = [record[width] for record in kernel.profiler.ring
              if record[steps]]
    # both admitted in one wave: the first scanned round enters at the
    # prompts' lengths, 3 and 14 -> 8 and 16 walked, the next rounds
    # four tokens later each: 7 and 18 -> 8 and 24, 11 and 22 -> 16, 24
    assert walked[:3] == [12.0, 16.0, 20.0]
    assert all(record[width] in (24, 48, 96)
               for record in gather.profiler.ring if record[steps])


def test_deadline_admission_sheds_doomed_request(params):
    """Deadline-aware admission (ISSUE 9): a request whose first-token
    deadline cannot survive the estimated admit wait is refused at
    submit — no callback, counted — while an open-deadline request and
    a comfortable one are admitted."""
    import time as _time

    decoder = ContinuousDecoder(params, CONFIG, max_slots=2,
                                prefill_buckets=(16,), steps_per_sync=4)
    called = []
    # cold decoder: no round EWMA yet, so admission must NOT shed even
    # against an absurd deadline (no number to shed on)
    assert decoder.estimated_admit_wait() is None
    assert decoder.submit("r0", [3, 5], 4, called.append,
                          deadline=_time.monotonic() - 1.0)
    # simulate a measured round and a backlog: the estimate scales with
    # the pending queue's share of the slot pool
    decoder._round_ewma = 0.5
    for i in range(4):
        decoder.submit(f"fill{i}", [7], 4, called.append)
    wait = decoder.estimated_admit_wait()
    assert wait is not None and wait > 0.5
    # doomed: deadline inside the estimated wait -> refused, counted
    shed_before = decoder.stats["admission_shed"]
    assert decoder.submit("doomed", [9], 4, called.append,
                          deadline=_time.monotonic() + 0.01) is False
    assert decoder.stats["admission_shed"] == shed_before + 1
    assert len(decoder._pending) == 5          # the refusal never queued
    # comfortable deadline and no deadline both admit
    assert decoder.submit("fine", [9], 4, called.append,
                          deadline=_time.monotonic() + 60.0)
    assert decoder.submit("open", [9], 4, called.append)
    assert len(decoder._pending) == 7
    assert called == []                        # refusals never call back


@pytest.mark.parametrize("kwargs, step_says, extend_says", [
    # 4 rows of tiny's 2 KV heads: 8 row windows against 2 blocks read
    # and 2 written; a chunk of 16 in blocks of 8: 3 blocks against 32
    ({}, "2 whole blocks a slot", "3 whole blocks a slot"),
    ({"kv_cache_dtype": "int8"}, "2 whole blocks a slot",
     "3 whole blocks a slot"),
    # one step a round of 2 heads: 2 rows against 2 x 2 blocks
    ({"steps_per_sync": 1}, "2 rows a slot", "3 whole blocks a slot"),
    # the speculative step's positions are no run: rejected drafts drop
    ({"speculate_k": 2}, "rows at sparse positions",
     "3 whole blocks a slot"),
])
def test_decoder_says_how_its_rows_reach_the_pool(kwargs, step_says,
                                                  extend_says):
    """PR 32: a run of a slot's new rows goes to the pool by whole
    blocks wherever that has fewer scatter windows than row by row, by
    static shapes alone; the decoder says which on its logger, the step
    at construction and an extend at its first build."""
    import logging
    params = llama_init(jax.random.PRNGKey(0), CONFIG)
    heard = []

    class Heard(logging.Handler):
        def emit(self, record):
            heard.append(record.getMessage())

    # the logger does not propagate: listen on it, from before it speaks
    name = "forms_%d" % abs(hash(tuple(sorted(kwargs))))
    logger = logging.getLogger(f"serving.{name}")
    handler = Heard(logging.INFO)
    logger.addHandler(handler)
    try:
        options = dict(max_slots=4, prefill_buckets=(16,), steps_per_sync=4,
                       paged_kv=True, kv_block=8, prefill_chunk=16,
                       name=name)
        options.update(kwargs)
        decoder = ContinuousDecoder(params, CONFIG, **options)
        done = {}
        decoder.submit("long", [(i * 7) % 50 + 1 for i in range(40)], 3,
                       lambda rid, tokens: done.update({rid: tokens}))
        for _ in range(40):
            decoder.pump()
            if done:
                break
        assert done
    finally:
        logger.removeHandler(handler)
    step = [m for m in heard if m.startswith("decode step writes")]
    extend = [m for m in heard if m.startswith("extend 16 x 1 writes")]
    assert len(step) == 1 and len(extend) == 1, heard
    assert step[0].endswith(step_says) and extend[0].endswith(extend_says)
