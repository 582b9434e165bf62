# Continuous-batching decode engine tests (serving.py), continued from
# tests/test_serving.py: chunked prefill, the latency SLOs' stats, and
# weight-only int8.

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.serving import ContinuousDecoder
from test_serving import CONFIG, oracle, params  # noqa: F401 (a fixture)


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
