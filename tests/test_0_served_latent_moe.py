# The latent-attention, routed-expert decoder (tests/test_latent_moe_layers.py
# has the suite's sizes and reference) SERVED: prefill through admit and
# chunked extend then decode through the latent pool, by the gather path and
# by the pallas walk in interpret mode, and the serving paths that refuse at
# construction.  Two decoders serve every case but the last, which cuts the
# pieces and the chunks and so empties the builders' caches.

import jax
import numpy as np
import pytest

from paged_model_cases import NOT_CARRIED
from test_latent_moe_layers import CASES, LOGIT_TOLERANCE, M

serve, served_gaps = CASES.serve, CASES.served_gaps


def decoder_for(kernel):
    """The paged latent pool in chunks of 16; `kernel` latches the pallas
    walk as a TPU would (the kernel itself then runs in the interpreter:
    the verify skill's note on step_kernel)."""
    with pytest.MonkeyPatch.context() as patch:
        if kernel:
            patch.setattr(jax, "default_backend", lambda: "tpu")
        decoder = CASES.decoder_for(
            f"latent-{'walk' if kernel else 'gather'}", buckets=(8, 16),
            chunk=16)
    assert decoder._walks_live is kernel and decoder.step_kernel is kernel
    return decoder


@pytest.fixture(scope="module")
def decoders():
    return {kernel: decoder_for(kernel) for kernel in (False, True)}


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "walk"])
def test_prefill_then_decode_through_the_pool_agrees_with_one_forward(
        decoders, kernel):
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
    served, stats = serve(decoders[kernel], requests)
    assert stats["prefill_chunks"] >= 5 and stats["prefills"]
    for rid, gap in served_gaps(requests, served).items():
        assert gap < LOGIT_TOLERANCE, (rid, gap)
    # the expert layers' counters came back with the rounds: every pair of
    # the whole model lands on a held expert
    assert 0 < stats["moe_layer_steps"] <= 2 * stats["steps"]
    assert stats["moe_pairs_here"] == stats["moe_pairs_routed"] > 0
    assert 0 < stats["moe_experts_hit"] <= 8 * stats["moe_layer_steps"]


def test_a_served_token_altered_is_seen(decoders):
    assert CASES.altered_token_gap(decoders[False]) > 100 * LOGIT_TOLERANCE


def test_walk_and_gather_paths_serve_the_same_logits(decoders):
    """Kernel against oracle on one cache: the walk's two-pass softmax
    chunk by chunk against one softmax over the gathered view."""
    rng = np.random.default_rng(9)
    requests = {"a": (rng.integers(1, 256, size=70).tolist(), 12),
                "b": (rng.integers(1, 256, size=5).tolist(), 12)}
    for decoder in decoders.values():
        served, _ = serve(decoder, requests)
        assert max(served_gaps(requests, served).values()) < LOGIT_TOLERANCE


# -- the paths a latent pool is not carried through refuse, by name ---------------

@pytest.mark.parametrize("path", NOT_CARRIED)
def test_paths_not_carried_for_a_latent_pool_refuse_at_construction(path):
    CASES.refuses_to_build(*NOT_CARRIED[path])


def test_tensor_parallel_weights_refuse_at_construction():
    CASES.refuses_tensor_parallel_weights()


@pytest.mark.parametrize("path", ["drain", "wire-layout", "install",
                                  "disagg-client"])
def test_drain_and_the_kv_wire_refuse_by_name(decoders, path):
    CASES.refuses(decoders[False], path)


# -- last: the case that empties the builders' caches -----------------------------

@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "walk"])
def test_prefixes_of_several_pieces_and_walks_of_several_chunks(
        kernel, monkeypatch):
    """At the cell's size an extend reads its prefix in pieces of 512
    positions and the walk a slot in chunks of 512; here both are cut to
    16, so that prompts of 77 and 100 span five and seven of them (and a
    last piece that is partly dead cells).  The cuts are read where a
    program is TRACED: decoders of their own, the builders' caches
    emptied around."""
    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.ops import paged_attention
    monkeypatch.setattr(M, "_PREFIX_PIECE", 16)
    monkeypatch.setattr(paged_attention, "_CHUNK", 16)
    builders = (serving_paged._paged_step_for,
                serving_paged._paged_extend_fn_for)
    for cached in builders:
        cached.cache_clear()
    try:
        rng = np.random.default_rng(11)
        requests = {f"r{n}": (rng.integers(1, 256, size=n).tolist(), 7)
                    for n in (77, 100, 9)}
        served, stats = serve(decoder_for(kernel), requests)
        assert stats["prefill_chunks"] >= 11
        assert max(served_gaps(requests, served).values()) < LOGIT_TOLERANCE
    finally:
        for cached in builders:
            cached.cache_clear()
