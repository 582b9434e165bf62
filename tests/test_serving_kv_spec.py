# Continuous-batching decode engine tests (serving.py), continued from
# tests/test_serving.py: the int8 KV cache and self-speculative decoding.

import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.serving import ContinuousDecoder
from test_serving import (CONFIG, _run_decoder, oracle,
                          params)  # noqa: F401 (a fixture)



# -- int8 KV cache + self-speculative decoding (round 7) -----------------

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
