# diagnostic harness: the console readout is the product
# graft: disable-file=lint-print
# Which decode-attention pattern reaches this chip's real bandwidth
# ceiling, and does int8 KV with a PURE CONVERT dequant (per-tensor
# scale folded into the softmax scale) fuse into the dot?
#
# Measurement discipline (see .claude/skills/verify): one
# dispatch+sync ROUND TRIP has a fixed host cost, and a program not
# much longer than it measures that cost.  Each pattern therefore runs
# at TWO in-program rep counts (fori_loop feeding attention output
# back into the query) and reports the marginal rate
# (T_hi - T_lo) / (reps_hi - reps_lo): dispatch floor and compile-time
# constants cancel exactly, like the slope method that diagnosed the
# llama decode scan.
#
# Patterns (raw streaming-read ceiling: tools/diag_membw.py):
#   gqa4   — llama serving shape [S,8,G=4,1,64]x[S,8,T,64]
#   mha1   — whisper decode shape [B,12,1,64]x[B,12,T,64]
#   mha8   — whisper shape, 8 packed queries (is M=1 the limiter?)
#   mha1q  — mha1 with int8 K/V and pure-astype dequant (half bytes)

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from diag_membw import marginal_rate  # noqa: E402  shared 2-point harness


def main():
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    # raw streaming-read ceiling: see tools/diag_membw.py (slicesum /
    # matvec).  An additive-taint sum probe lived here first and
    # printed 5 TB/s — XLA rewrote sum(x + c) to sum(x) + N*c and
    # hoisted the loop-invariant sum(x); carry-fed consumers only.

    def attn_builder(einsum_a, einsum_b, k_scale=None,
                     v_scale=None):
        def build(reps):
            def f(q0, k, v):
                def body(i, q):
                    kk = k.astype(jnp.bfloat16) if k.dtype == jnp.int8 \
                        else k
                    vv = v.astype(jnp.bfloat16) if v.dtype == jnp.int8 \
                        else v
                    scores = jnp.einsum(
                        einsum_a, q, kk,
                        preferred_element_type=jnp.float32)
                    if k_scale is not None:
                        scores = scores * k_scale
                    w = jax.nn.softmax(scores, axis=-1).astype(
                        jnp.bfloat16)
                    out = jnp.einsum(
                        einsum_b, w, vv,
                        preferred_element_type=jnp.float32)
                    if v_scale is not None:
                        out = out * v_scale
                    return out.astype(jnp.bfloat16)
                return jnp.sum(jax.lax.fori_loop(0, reps, body, q0),
                               dtype=jnp.float32)
            return f
        return build

    # gqa4: llama 1b serving shape
    s, hkv, g, d, t = 256, 8, 4, 64, 2048
    k = jnp.ones((s, hkv, t, d), jnp.bfloat16)
    v = jnp.ones((s, hkv, t, d), jnp.bfloat16)
    q0 = jnp.ones((s, hkv, g, 1, d), jnp.bfloat16)
    marginal_rate("gqa4",
                  attn_builder("skgqd,sktd->skgqt",
                               "skgqt,sktd->skgqd"),
                  k.nbytes + v.nbytes, q0, k, v)
    del k, v, q0

    # whisper decode shape
    b, h, t, d = 256, 12, 2048, 64
    k = jnp.ones((b, h, t, d), jnp.bfloat16)
    v = jnp.ones((b, h, t, d), jnp.bfloat16)
    for num_q in (1, 8):
        q0 = jnp.ones((b, h, num_q, d), jnp.bfloat16)
        marginal_rate(f"mha{num_q}",
                      attn_builder("bhqd,bhtd->bhqt",
                                   "bhqt,bhtd->bhqd"),
                      k.nbytes + v.nbytes, q0, k, v)
    del k, v

    ki = jnp.ones((b, h, t, d), jnp.int8)
    vi = jnp.ones((b, h, t, d), jnp.int8)
    q0 = jnp.ones((b, h, 1, d), jnp.bfloat16)
    marginal_rate("mha1q",
                  attn_builder("bhqd,bhtd->bhqt", "bhqt,bhtd->bhqd",
                               k_scale=jnp.float32(1.0 / 127.0),
                               v_scale=jnp.float32(1.0 / 127.0)),
                  ki.nbytes + vi.nbytes, q0, ki, vi)


if __name__ == "__main__":
    main()
