# diagnostic harness: the console readout is the product
# graft: disable-file=lint-print
# Diagnose the whisper decode tail's HBM efficiency (r5, verdict item 3
# follow-through) with the same slope method that cracked the llama
# decode scan (serving.py KV_WRITE="block" — see its header comment):
#
#   1. decode-tail step time vs n_audio_ctx at the bench geometry
#      (whisper-small bf16, batch 256): the slope is the effective
#      cross-KV read bandwidth (bytes/frame is exact arithmetic), the
#      intercept is the fixed per-step cost (weights read + ~170 small
#      ops on [B,1,768] activations + self-KV);
#   2. the fused-program ladder extended to batch 512 (the bench stops
#      at 4x base = 256, which WON its ladder — meaning scaling hadn't
#      flattened when the ladder ran out).
#
# Usage (on the TPU machine):
#   python tools/diag_whisper_tail.py [--skip-512]
#
# Timing discipline per .claude/skills/verify: chained device programs
# with one host transfer of a scalar result per measurement.

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from aiko_services_tpu.models import whisper_init  # noqa: E402
from aiko_services_tpu.models.whisper import (  # noqa: E402
    WHISPER_PRESETS, encode, greedy_decode_from_audio,
    precompute_cross_kv)

BATCH = 256
MAX_TOKENS = 24
SPEC_GBPS = 819.0  # v5e


from diag_membw import timed_chain as timed  # noqa: E402  shared harness


# The achievable-bandwidth ceiling lives in tools/diag_membw.py (the
# two-point rep fit: ~730-750 GB/s measured r5).  A chain=4 sum probe
# lived here first and reported ~150 GB/s — it was timing the fixed
# dispatch floor, not the read.
ACHIEVABLE_GBPS = 740.0


def tail_config(n_audio_ctx):
    return dataclasses.replace(
        WHISPER_PRESETS["small"], n_audio_ctx=n_audio_ctx,
        n_text_ctx=MAX_TOKENS + 8, dtype=jnp.bfloat16)


def tail_step_ms(params, config, batch=BATCH):
    """Decode tail only: from precomputed audio features, run
    precompute_cross_kv + the 24-step greedy scan.  The cross-KV
    projection is subtracted via a second program that stops there."""
    audio = jnp.zeros((batch, config.n_audio_ctx, config.dim),
                      jnp.bfloat16)

    def tail(params, audio):
        tokens, lengths, score = greedy_decode_from_audio(
            params, config, audio, max_tokens=MAX_TOKENS)
        return jnp.sum(lengths) + jnp.sum(score, dtype=jnp.float32)

    def kv_only(params, audio):
        kv = precompute_cross_kv(params, config, audio)
        return sum(jnp.sum(leaf, dtype=jnp.float32)
                   for leaf in jax.tree_util.tree_leaves(kv))

    t_tail = timed(jax.jit(tail), params, audio)
    t_kv = timed(jax.jit(kv_only), params, audio)
    return (t_tail - t_kv) * 1000.0 / MAX_TOKENS


def cross_kv_bytes_per_frame(config, batch=BATCH):
    # K + V, every decoder layer, bf16
    return batch * config.dec_layers * 2 * config.dim * 2


def main():
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind}", flush=True)

    gbps = ACHIEVABLE_GBPS
    print(f"achievable-read reference: {gbps:.0f} GB/s "
          f"(tools/diag_membw.py two-point fit)", flush=True)

    ctxs = (125, 250, 375, 500)
    steps = []
    params = None
    for ctx in ctxs:
        config = tail_config(ctx)
        if params is None:
            params = whisper_init(jax.random.PRNGKey(0), config)
        ms = tail_step_ms(params, config)
        steps.append(ms)
        print(f"n_audio_ctx {ctx}: tail step {ms:.2f} ms", flush=True)

    # least-squares slope/intercept of step-ms vs ctx
    x = np.array(ctxs, float)
    y = np.array(steps, float)
    slope_ms, intercept_ms = np.polyfit(x, y, 1)
    bpf = cross_kv_bytes_per_frame(tail_config(250))
    eff_gbps = bpf / (slope_ms / 1000.0) / 1e9
    print(f"slope {slope_ms * 1000:.2f} us/frame, intercept "
          f"{intercept_ms:.2f} ms/step", flush=True)
    print(f"cross-KV bytes/frame {bpf} -> effective read bandwidth "
          f"{eff_gbps:.0f} GB/s ({eff_gbps / gbps:.0%} of achievable, "
          f"{eff_gbps / SPEC_GBPS:.0%} of spec)", flush=True)
    print(f"fixed per-step cost {intercept_ms:.2f} ms vs cross-KV read "
          f"at ctx 250: {250 * slope_ms:.2f} ms", flush=True)

    if "--skip-512" not in sys.argv:
        # does the fused ladder keep scaling past 256?
        from aiko_services_tpu.ops.audio import (WHISPER_HOP,
                                                 log_mel_spectrogram,
                                                 mulaw_decode)
        config = tail_config(250)
        samples = config.n_audio_ctx * 2 * WHISPER_HOP

        def fused(params, pcm):
            audio = mulaw_decode(pcm)
            mel = log_mel_spectrogram(audio, num_mels=config.n_mels)
            tokens, lengths, _ = greedy_decode_from_audio(
                params, config,
                encode(params, config, mel.astype(config.dtype)),
                max_tokens=MAX_TOKENS)
            return jnp.sum(lengths)

        jfused = jax.jit(fused)
        for batch in (256, 512):
            codes = jax.random.randint(
                jax.random.PRNGKey(2), (batch, samples), 0, 256,
                jnp.int32).astype(jnp.uint8)
            try:
                seconds = timed(jfused, params, codes)
            except Exception as exc:
                print(f"batch {batch}: failed {exc!r}", flush=True)
                break
            streams = batch * 5.0 / seconds
            print(f"batch {batch}: round {seconds * 1000:.0f} ms -> "
                  f"{streams:.0f} device-resident streams", flush=True)


if __name__ == "__main__":
    main()
