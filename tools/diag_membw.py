# diagnostic harness: the console readout is the product
# graft: disable-file=lint-print
# What HBM streaming bandwidth can THIS chip actually reach?  The
# 819 GB/s v5e spec is the roofline denominator the bench uses;
# "bandwidth-bound" claims are only meaningful against the best
# ACHIEVABLE number, which this probe measures.
#
# Two hard-won measurement rules (.claude/skills/verify/SKILL.md):
#   1. One dispatch+sync has a fixed host cost that a millisecond
#      kernel cannot amortize — every pattern runs at TWO in-program
#      rep counts and reports the marginal rate
#      (T_hi - T_lo) / (reps_hi - reps_lo); the dispatch floor and
#      compile constants cancel exactly.
#   2. XLA's algebraic simplifier sees through additive taints:
#      sum(x + c) becomes sum(x) + N*c with sum(x) hoisted out of the
#      loop (a first version of this tool printed 5 TB/s that way).
#      Each iteration's read must therefore depend on the carry
#      through its ACTUAL consumer: the slice offset of the read, or
#      the operand fed back from the previous result — and inputs are
#      random, never jnp.ones (constants can fold entirely).
#
# Patterns:
#   slicesum — sum over a carry-offset dynamic_slice window of a 1 GiB
#              random array: pure streaming read, unfoldable
#   matvec   — [M, 4096] @ v with v fed back from the result: an
#              MXU-issued streaming read
#
# For the decode-attention shapes (the numbers that matter for the
# whisper/llama tails) see tools/diag_attn_patterns.py.

from __future__ import annotations

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

REPS_LO, REPS_HI = 64, 256


def timed(compiled, *args, repeats=5):
    np.asarray(compiled(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        np.asarray(compiled(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def timed_chain(fn, *args, chain=4, repeats=5):
    """Median per-call wall seconds with `chain` back-to-back calls per
    forced host-transfer sync — the queue-full amortization for
    100 ms+ programs (for sub-100 ms programs use the two-point rep
    fit below instead; the ~108 ms dispatch floor still leaks
    floor/chain into each measurement).  Shared by ab_cross_kv.py and
    diag_whisper_tail.py so the timing discipline cannot drift."""
    out = fn(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0])
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        for _ in range(chain - 1):
            out = fn(*args)
        np.asarray(jax.tree_util.tree_leaves(out)[0])
        times.append((time.perf_counter() - t0) / chain)
    return float(np.median(times))


def marginal_rate(name, build, traffic_bytes_per_rep, *args):
    t = {}
    for reps in (REPS_LO, REPS_HI):
        compiled = jax.jit(build(reps)).lower(*args).compile()
        t[reps] = timed(compiled, *args)
    dt = t[REPS_HI] - t[REPS_LO]
    gbps = traffic_bytes_per_rep * (REPS_HI - REPS_LO) / dt / 1e9
    print(f"{name:9s} {gbps:7.0f} GB/s marginal  "
          f"(lo {t[REPS_LO] * 1e3:.1f} ms, hi {t[REPS_HI] * 1e3:.1f} ms, "
          f"{traffic_bytes_per_rep / 1e9:.2f} GB/rep)", flush=True)
    return gbps


def main():
    print(f"device: {jax.devices()[0].device_kind}", flush=True)

    n = 1 << 29                                     # 1 GiB bf16
    window = n - 256
    x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.bfloat16)

    def build_slicesum(reps):
        def f(x):
            def body(i, carry):
                offset, acc = carry
                s = jnp.sum(
                    jax.lax.dynamic_slice(x, (offset,), (window,)),
                    dtype=jnp.float32)
                # next offset depends on the DATA just read — the
                # read can be neither hoisted nor precomputed
                offset = (jnp.abs(s).astype(jnp.int32) + i) % 256
                return offset, acc + s
            _, acc = jax.lax.fori_loop(0, reps, body,
                                       (jnp.int32(0), jnp.float32(0)))
            return acc
        return f

    marginal_rate("slicesum", build_slicesum, window * 2, x)
    del x

    a = jax.random.normal(jax.random.PRNGKey(1), (1 << 18, 4096),
                          jnp.bfloat16)             # 2 GiB
    v0 = jax.random.normal(jax.random.PRNGKey(2), (4096,), jnp.bfloat16)

    def build_mv(reps):
        def f(a, v0):
            def body(i, v):
                y = jnp.einsum("md,d->m", a, v,
                               preferred_element_type=jnp.float32)
                # feed the result back as the next operand (scaled to
                # stay finite): a real data dependence per iteration
                return (y[:4096] * (1.0 / jnp.maximum(
                    jnp.max(jnp.abs(y[:4096])), 1e-6))
                    ).astype(jnp.bfloat16)
            v = jax.lax.fori_loop(0, reps, body, v0)
            return jnp.sum(v, dtype=jnp.float32)
        return f

    marginal_rate("matvec", build_mv, a.nbytes, a, v0)


if __name__ == "__main__":
    main()
