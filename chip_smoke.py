#!/usr/bin/env python3
# chip smoke CLI: the console lines and the final JSON are the product
# graft: disable-file=lint-print
"""The quickest proof that the two serving paths still start on the chip.

    python3 chip_smoke.py              one TPU chip: kernels, speech, llama
    python3 chip_smoke.py --chips 4    four chips: TP=4 llama vs one chip, only
    python3 chip_smoke.py --rehearse   tiny presets on whatever jax finds
                                       (the CPU rehearsal; never says "ok")

One process, because one process owns the chip.  Inputs and weights are
made from --seed; nothing is read from the network or written outside
the checkout.  Every phase raises on a wrong result, so a non-zero exit
means a phase failed; on success the LAST stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases of the default run:
  kernels  every pallas kernel of the main path, compiled
           (interpret=False), against its XLA oracle
  speech   examples/speech/pipeline_transcription.json built as `aiko_tpu
           pipeline create` builds it — Whisper-small, bf16, batched through
           BatchingScheduler -> ComputeRuntime — fed seeded audio that lands
           in every mel bucket; tokens equal a direct jit of greedy_decode
  llama    ContinuousDecoder on LLAMA_PRESETS["1b"] at 16 heads of 128 (a
           pool the paged kernel walks by hand) with paged KV, eight seeded
           requests (64..1024-token prompts, chunked extend on the long
           ones): the gather path forced, against llama_greedy_decode; the
           paged kernel forced (step and extend); and a decoder that was
           told nothing, which on the chip must have taken the kernel
  latent   ContinuousDecoder on models/latent_moe.py (latent attention,
           routed experts) at the published widths and a depth of two (one
           dense, one sparse layer, 12 of 192 experts held), the same eight
           requests: the gather path forced, and a decoder that was told
           nothing, which on the chip must walk its latent pool; every
           served token held to the teacher-forced expanded forward
  hybrid   ContinuousDecoder on models/hybrid_sparse.py (KDA slot state
           beside a sparse-selected latent pool, hyper-connected streams)
           at the published widths and a depth of two (KDA + dense MLP,
           sparse attention + 12 of 288 experts): prompts that go in by an
           admit and by chains of chunked extends, one of them past
           index_topk positions, then decode; every served token held to
           the benchmark's plain reference
           (benchmark/reference/hybrid_sparse_lm.py, float32, its own
           weights from the seed)
  sparse_gqa  ContinuousDecoder on models/sparse_gqa.py (K, V and an
           indexer key a token, the exact top 2,048 positions chosen a
           query, softmax-routed experts and no shared one) at the
           published widths and a depth of two, 16 of 128 experts held:
           prompts on both sides of 2,048 positions, admit, chunked
           extends and decode, every slot served twice; prints the
           selection's and the experts' counters; every served token held
           to benchmark/reference/sparse_gqa_lm.py
  gated_delta  ops/kda_step.py's kernel with ONE gate a head against
           models/delta_rule.recurrent at the published head sizes (30
           heads of [96, 192]; some slots live, none, all), then
           ContinuousDecoder on models/gated_delta.py (recurrent slot state
           beside a K/V pool that the shared paged kernel walks) at the
           published widths, one period of four layers and the whole
           vocabulary: prompts admitted whole and prompts that go chunk by
           chunk, decode, every slot served twice; every served token held
           to benchmark/reference/gated_delta_lm.py
  ssm_hybrid  ops/kda_step.py's PLAIN decayed rule (Mamba-2: one k and one
           q a slot) against its four-line oracle at the published head
           sizes (64 heads of [128, 64]; some slots live, none, all) and
           its time for a step's 36 layers against the memory's speed;
           ops/ssm_chunk.py's chunked form against `ssm_chunked` and
           `ssm_plain` over a 512-token piece and, alone, its time for a
           piece's 36 layers beside XLA's form (ISSUE 46); then
           ContinuousDecoder on models/ssm_hybrid.py (Mamba-2 slot
           state beside a pool whose row is a K/V head's V and K side by
           side, which the shared paged kernel walks) at the published
           widths, four layers (three Mamba, one attention) and the whole
           tied vocabulary: prompts admitted whole and prompts that go
           chunk by chunk (both through the chunk kernel on the chip),
           decode, every slot served twice; every served token held to
           benchmark/reference/ssm_hybrid_lm.py

--only <phase> [<phase> ...] runs those phases alone (a builder's chip
minutes; a run that skips a phase never says "ok": it ends with the
rehearsal's line).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import zlib

ROOT = os.path.dirname(os.path.abspath(__file__))
SPEECH_DEFINITION = os.path.join(
    ROOT, "examples", "speech", "pipeline_transcription.json")

# the whole run, compilation included, must end well inside the
# driver's 1200 s; a hung device call otherwise outlives the caller
WATCHDOG_SECONDS = 1150


def say(message: str) -> None:
    print(f"[chip_smoke] {message}", flush=True)


def require(condition, message: str) -> None:
    if not condition:
        raise AssertionError(message)


class CompileClock:
    """Sums jax's own backend-compile durations and persistent-cache
    hit/miss events, so each phase can report compile seconds apart
    from run seconds (and a warm cache shows as fewer of them)."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.hits, self.misses


class Phase:
    """`with Phase("speech", clock):` — prints wall, compile and run
    seconds, cache hits/misses and HBM in use when the block ends; an
    exception inside propagates (a failed phase fails the run)."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        say(f"phase {self.name}: start")
        self.start = time.perf_counter()
        self.before = self.clock.snapshot()
        return self

    def __exit__(self, exc_type, exc, _tb):
        wall = time.perf_counter() - self.start
        seconds, hits, misses = (
            now - then for now, then in zip(self.clock.snapshot(),
                                            self.before))
        outcome = "FAILED" if exc_type else "ok"
        say(f"phase {self.name}: {outcome} wall={wall:.1f}s "
            f"compile={seconds:.1f}s run={max(0.0, wall - seconds):.1f}s "
            f"cache_hits={hits} cache_misses={misses} hbm={hbm_in_use()}")
        return False


def hbm_in_use() -> str:
    import jax
    parts = []
    for device in jax.devices():
        stats = device.memory_stats() or {}
        if "bytes_in_use" in stats:
            parts.append(f"{stats['bytes_in_use'] / 2**30:.2f}"
                         f"/{stats.get('peak_bytes_in_use', 0) / 2**30:.2f}")
    return ("GiB in-use/peak " + " ".join(parts)) if parts \
        else "not reported by this backend"


# -- kernels -----------------------------------------------------------------

def lowered_has_kernel(fn, *args, **kwargs) -> bool:
    """True when fn's lowering holds a compiled pallas kernel — the
    proof that the interpret/XLA branch was NOT taken."""
    return "tpu_custom_call" in fn.lower(*args, **kwargs).as_text()


def check_kernel(label: str, kernel, oracle, args, on_chip: bool) -> None:
    """Run a jitted kernel path and its XLA oracle on the same inputs.
    Both round to bf16 along different routes, so each element may
    differ by a few bf16 ulps of its own size (2^-6 relative) plus
    what the bf16 softmax weights accumulate (2^-7 absolute, for
    outputs of order one); a wrong block or mask is off by the
    output's whole spread."""
    import jax.numpy as jnp
    if on_chip:
        require(lowered_has_kernel(kernel, *args),
                f"{label} lowered without a tpu_custom_call")
    out = kernel(*args).astype(jnp.float32)
    ref = oracle(*args).astype(jnp.float32)
    diff = jnp.abs(out - ref)
    worst = float(jnp.max(diff))
    say(f"  {label}: max|kernel-oracle|={worst:.4f}, oracle std "
        f"{float(jnp.std(ref)):.3f}")
    require(float(jnp.max(diff - 2.0 ** -6 * jnp.abs(ref))) <= 2.0 ** -7,
            f"{label} off by {worst}")


def llama_config(shape: dict, preset_heads: bool = False):
    """The llama phases' model: the preset at shape["llama_heads"]
    heads (1b: 16 heads of 128, a pool whose live blocks the paged
    kernel walks by hand), or with the preset's own heads (1b: 32 of
    64, which keep the kernel's table body)."""
    from aiko_services_tpu.models.llama import LLAMA_PRESETS
    preset = LLAMA_PRESETS[shape["llama_preset"]]
    return dataclasses.replace(
        preset, dtype=shape["llama_dtype"], max_seq_len=shape["max_seq"],
        num_heads=preset.num_heads if preset_heads
        else shape["llama_heads"])


def phase_kernels(shape: dict, seed: int, on_chip: bool) -> None:
    import jax
    import jax.numpy as jnp

    from aiko_services_tpu.ops.attention import flash_attention
    from aiko_services_tpu.parallel import attention_reference

    keys = jax.random.split(jax.random.PRNGKey(seed), 8)

    # flash attention at the 3000-frame bucket's padded context
    b, h, s, d = shape["flash"]
    qkv = [jax.random.normal(key, (b, h, s, d), jnp.bfloat16)
           for key in keys[:3]]
    for causal in (False, True):
        check_kernel(
            f"flash_attention b{b} h{h} s{s} d{d} causal={causal}",
            jax.jit(functools.partial(flash_attention, causal=causal,
                                      interpret=not on_chip)),
            jax.jit(functools.partial(attention_reference,
                                      causal=causal)),
            qkv, on_chip)

    # paged decode attention at the llama phase's geometry and at the
    # preset's own heads, through the same layer wrappers the decode
    # scan calls, against the gather path: both of the kernel's bodies
    for config in dict.fromkeys((llama_config(shape),
                                 llama_config(shape, preset_heads=True))):
        check_paged_attention(config, shape, keys[3:7], on_chip)

    # the pool's run writer against the row scatter (PR 32), at the
    # llama phase's pool and at the latent phase's leaf
    config = llama_config(shape)
    check_run_writes("llama", (config.num_kv_heads, config.head_dim),
                     shape, keys[7], on_chip)
    check_run_writes("latent", shape["latent_config"].cache_leaves[0],
                     shape, keys[7], on_chip)

    # the KDA step's kernel over the live slots' state (PR 34) against
    # the plain recurrence, at the hybrid phase's heads
    check_kda_live_step(shape, keys[7], on_chip)


def check_paged_attention(config, shape: dict, keys, on_chip: bool) -> None:
    import jax
    import jax.numpy as jnp

    from aiko_services_tpu import serving, serving_paged
    from aiko_services_tpu.models import layers as L
    from aiko_services_tpu.models.llama import _layer_init

    layer = _layer_init(keys[0], config)
    slots, block = shape["slots"], 32
    t_cap, side_len = shape["max_seq"], shape["steps_per_sync"]
    nb = -(-t_cap // block)
    cos, sin = L.rope_frequencies(config.head_dim, config.max_seq_len,
                                  config.rope_theta)
    x = jax.random.normal(keys[1], (slots, 1, config.dim), config.dtype)
    pool_shape = (slots * nb + 1, config.num_kv_heads, block,
                  config.head_dim)
    native = [jax.random.normal(key, pool_shape, config.dtype)
              for key in keys[2:4]]
    tables = 1 + jnp.arange(slots * nb, dtype=jnp.int32).reshape(slots, nb)
    # ragged: a slot that holds nothing, one token, and up to the cap
    entry = jnp.linspace(1, t_cap - side_len, slots).astype(
        jnp.int32).at[1].set(0)
    sides = jnp.zeros((slots, config.num_kv_heads, side_len,
                       config.head_dim), config.dtype)

    # the layer's weights are an ARGUMENT: closed over, 120 MB of them
    # would be baked into each executable (and its cache entry)
    def kernel_path(layer, x, k_pool, v_pool):
        return serving_paged._kernel_attention_block(
            tables, layer, config, x, cos, sin, k_pool, v_pool,
            sides, sides, entry, entry, 0)[0]

    def gather_path(layer, x, k_pool, v_pool):
        k_cache, v_cache = (
            serving_paged._gather_views([pool], tables, t_cap)[0]
            for pool in (k_pool, v_pool))
        return serving._slot_attention_block(
            layer, config, x, cos, sin, k_cache, v_cache, sides,
            sides, entry, entry, 0)[0]

    for kv, pools in (
            (str(jnp.dtype(config.dtype)), native),
            ("int8", [L.quantize_kv_cache(pool) for pool in native])):
        check_kernel(
            f"paged_decode_attention S{slots} Hkv{config.num_kv_heads} "
            f"G{config.num_heads // config.num_kv_heads} "
            f"D{config.head_dim} B{block} nb{nb} P{side_len} {kv}",
            jax.jit(kernel_path), jax.jit(gather_path),
            [layer, x, *pools], on_chip)


def check_run_writes(label: str, leaf: tuple, shape: dict, key,
                     on_chip: bool) -> None:
    """layers.write_paged_runs (a slot's run of new rows written by the
    whole blocks it falls in) against scatter_paged_rows over the same
    positions, for a round's merge and for a chunk, native and int8:
    the two pools hold the same bits in every cell."""
    import jax
    import jax.numpy as jnp

    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.models import layers as L

    heads, lanes = leaf
    slots, block = shape["slots"], 32
    nb = -(-shape["max_seq"] // block)
    keys = jax.random.split(key, 4)
    native = jax.random.normal(
        keys[0], (slots * nb + 1, heads, block, lanes), jnp.bfloat16)
    tables = 1 + jax.random.permutation(
        keys[1], slots * nb).astype(jnp.int32).reshape(slots, nb)

    def by_rows(pool, tables, starts, side, live):
        positions = starts[:, None] + jnp.arange(side.shape[2])[None]
        return serving_paged._paged_scatter(
            [pool], tables, positions, live[:, None], [side],
            isinstance(pool, dict), block)[0]

    def by_blocks(pool, tables, starts, side, live):
        rows = L.quantize_kv_cache(side) if isinstance(pool, dict) else side
        return L.write_paged_runs(pool, tables, starts, rows, live)

    for run, (rows_n, width) in {
            "merge": (slots, shape["steps_per_sync"]),
            "chunk": (1, shape["prefill_chunk"])}.items():
        # starts anywhere: inside a block, across an edge, the last past
        # the table (its tail drops); one slot of a merge is not live
        starts = jnp.linspace(block - 2, nb * block - width + 1,
                              rows_n).astype(jnp.int32)
        live = jnp.arange(rows_n) != 1
        side = jax.random.normal(keys[2], (rows_n, heads, width, lanes),
                                 jnp.bfloat16)
        for kv, pool in (("bfloat16", native),
                         ("int8", L.quantize_kv_cache(native))):
            args = (pool, tables[:rows_n], starts, side, live)
            got, want = jax.jit(by_blocks)(*args), jax.jit(by_rows)(*args)
            same = all(bool(jnp.array_equal(a, b)) for a, b in zip(
                jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(want)))
            changed = any(bool(jnp.any(a != b)) for a, b in zip(
                jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(pool)))
            say(f"  write_paged_runs {label} H{heads} D{lanes} {run} "
                f"{rows_n}x{width} {kv}: equal to the row scatter {same}")
            require(same and changed,
                    f"write_paged_runs {label} {run} {kv} differs from "
                    f"the row scatter")


def check_kda_live_step(shape: dict, key, on_chip: bool) -> None:
    """ops.kda_step.kda_live_step against hybrid_sparse.kda_recurrent at
    the hybrid phase's heads (a gate a channel), some slots live, none,
    all."""
    config = hybrid_config(shape)
    check_live_step("a gate a channel", config.kda_heads,
                    config.kda_head_dim, config.kda_head_dim, False,
                    shape["slots"], key, on_chip)


def check_live_step(grain: str, heads: int, dk: int, dv: int, by_head: bool,
                    slots: int, key, on_chip: bool) -> bool:
    """ops.kda_step.kda_live_step against models/delta_rule.recurrent at
    `heads` heads of [dk, dv], a gate a channel or (`by_head`) ONE a head
    with beta up to 2 and the state laid with its heads side by side; some
    slots live, none, all: the live slots' output and state to float32
    rounding (both forms are float32 throughout), every other slot's state
    bit for bit, its output zeros.  -> whether a decode step takes the
    kernel at this geometry (nothing is compared where it does not)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu.models import delta_rule
    from aiko_services_tpu.ops import kda_step

    takes = kda_step.moves_live_states(heads, dk, not on_chip, value_dim=dv,
                                       by_head=by_head)
    say(f"  kda_live_step, {grain}, H{heads} [{dk}, {dv}]: a decode step "
        f"takes it {takes}")
    if not takes:                       # the rehearsal's head of 16
        return False
    keys = jax.random.split(key, 6)

    def unit(z):
        return z / jnp.linalg.norm(z, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (slots, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (slots, heads, dk)))
    v = jax.random.normal(keys[2], (slots, heads, dv))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(
        keys[3], (slots, heads) if by_head else (slots, heads, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (slots, heads))) * (
        2.0 if by_head else 1.0)
    state = jax.random.normal(keys[5], (slots, heads, dk, dv))
    laid = kda_step.heads_side_by_side(state) if by_head else state
    kernel = jax.jit(functools.partial(kda_step.kda_live_step,
                                       interpret=not on_chip))
    if on_chip:
        require(lowered_has_kernel(kernel, q, k, v, g, beta, laid,
                                   jnp.ones((slots,), bool)),
                "kda_live_step lowered without a tpu_custom_call")
    for label, live in (("some", np.arange(slots) % 3 == 1),
                        ("none", np.zeros(slots, bool)),
                        ("all", np.ones(slots, bool))):
        active = jnp.asarray(live)
        out, new = kernel(q, k, v, g, beta, laid, active)
        want_out, want = jax.jit(delta_rule.recurrent)(
            q, k, v, g * active.reshape((-1,) + (1,) * (g.ndim - 1)),
            beta * active[:, None], state)
        if by_head:
            want = kda_step.heads_side_by_side(want)
        worst = max(float(jnp.abs(out - want_out)[live].max(initial=0.0)),
                    float(jnp.abs(new - want)[live].max(initial=0.0)))
        kept = bool(np.array_equal(np.asarray(new)[~live],
                                   np.asarray(laid)[~live])) and \
            not np.asarray(out)[~live].any()
        say(f"  kda_live_step, {grain}, {label} of {slots} slots live: "
            f"max|kernel-oracle|={worst:.2e}, the others untouched {kept}")
        # float32 sums of a hundred terms in another order, values of
        # order one
        require(worst <= 1e-5 and kept,
                f"kda_live_step, {grain}, {label} live off by {worst}")
    return True


def check_chunk_scan(grain: str, heads: int, dk: int, dv: int, by_head: bool,
                     tokens: int, key, on_chip: bool) -> bool:
    """ops.delta_chunk.delta_chunk_scan against models/delta_rule.chunked
    (and `recurrent` token by token) over a prompt's piece of `tokens` at
    `heads` heads of [dk, dv], a gate a channel (to -5 a token) or
    (`by_head`) ONE a head, beta over (0, 2), from a state that is not zero
    and a tail that is not live: output and state to 2e-4 (float32 at
    HIGHEST on both sides, sums in another order).  -> whether a prompt's
    piece takes the kernel at this geometry on the chip (in a rehearsal the
    interpreter runs it whatever the geometry, and the model does not)."""
    import jax
    import jax.numpy as jnp

    from aiko_services_tpu.models import delta_rule
    from aiko_services_tpu.ops import delta_chunk, kda_step

    takes = delta_chunk.scans_chunks(heads, dk, dv, by_head)
    say(f"  delta_chunk_scan, {grain}, H{heads} [{dk}, {dv}]: a prompt's "
        f"piece takes it on the chip {takes}")
    if on_chip and not takes:
        return False
    keys = jax.random.split(key, 6)

    def unit(z):
        return z / jnp.linalg.norm(z, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (2, tokens, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (2, tokens, heads, dk)))
    v = jax.random.normal(keys[2], (2, tokens, heads, dv))
    live = jnp.arange(tokens)[None] < jnp.array([[tokens],
                                                 [tokens - tokens // 3]])
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(
        keys[3], (2, tokens, heads) + (() if by_head else (dk,))))
    g = g * live.reshape(live.shape + (1,) * (g.ndim - 2))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(
        keys[4], (2, tokens, heads))) * live[..., None]
    state = jax.random.normal(keys[5], (2, heads, dk, dv))
    laid = kda_step.heads_side_by_side(state) if by_head else state
    kernel = jax.jit(functools.partial(delta_chunk.delta_chunk_scan,
                                       interpret=not on_chip))
    if on_chip:
        require(lowered_has_kernel(kernel, q, k, v, g, beta, laid),
                "delta_chunk_scan lowered without a tpu_custom_call")
    out, new = kernel(q, k, v, g, beta, laid)
    if by_head:
        new = kda_step.heads_apart(new, heads)

    @jax.jit
    def token_by_token(q, k, v, g, beta, state):
        def one(state, xs):
            out, state = delta_rule.recurrent(*xs, state)
            return state, out
        state, out = jax.lax.scan(one, state, tuple(
            jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta)))
        return jnp.moveaxis(out, 0, 1), state

    for name, (want_out, want) in (
            ("chunked", jax.jit(delta_rule.chunked)(q, k, v, g, beta, state)),
            ("recurrent", token_by_token(q, k, v, g, beta, state))):
        worst = max(float(jnp.abs(out - want_out).max()),
                    float(jnp.abs(new - want).max()))
        say(f"  delta_chunk_scan, {grain}, {tokens} tokens: "
            f"max|kernel-{name}|={worst:.2e}")
        require(worst <= 2e-4,
                f"delta_chunk_scan, {grain}, off {name} by {worst}")
    return takes


# -- speech ------------------------------------------------------------------

def seeded_microphone(seed: int):
    """PE_MicrophoneSim's stand-in: the same source seam, but each
    posted frame yields `chunk_seconds` of noise made from (seed,
    stream, frame) instead of a timer-driven tone — the smoke decides
    how many frames of which length reach each mel bucket."""
    import numpy as np

    from aiko_services_tpu.pipeline import FrameOutput, PipelineElement

    class PE_SeededMicrophone(PipelineElement):
        contracts = {"out:audio": "f32[*]"}

        def process_frame(self, frame, **_) -> FrameOutput:
            seconds, _found = self.get_parameter("chunk_seconds", 1.0,
                                                 frame.stream)
            rng = np.random.default_rng(
                [seed, zlib.crc32(frame.stream_id.encode()),
                 frame.frame_id])
            audio = 0.1 * rng.standard_normal(int(float(seconds) * 16000))
            return FrameOutput(True, {"audio": audio.astype(np.float32)})

    return PE_SeededMicrophone


def phase_speech(shape: dict, seed: int, on_chip: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu.compute import ComputeRuntime
    from aiko_services_tpu.models.whisper import (greedy_decode_scored,
                                                  sot_sequence_for)
    from aiko_services_tpu.ops import attention as attention_ops
    from aiko_services_tpu.pipeline import (Pipeline,
                                            parse_pipeline_definition)
    from aiko_services_tpu.process import ProcessRuntime

    with open(SPEECH_DEFINITION) as f:
        data = json.load(f)
    data["parameters"] |= {
        "PE_WhisperASR.preset": shape["whisper_preset"],
        # random weights decode at ~log(1/vocab) per token: the
        # hallucination gates would blank every transcript
        "PE_WhisperASR.logprob_threshold": -1e9,
        "PE_WhisperASR.compression_ratio_threshold": 1e9,
    }
    definition = parse_pipeline_definition(data)
    max_tokens = int(data["parameters"]["PE_WhisperASR.max_tokens"])

    # what `aiko_tpu pipeline create` does (cli.create), minus the
    # endless runtime.run(): the smoke drives the engine itself
    runtime = ProcessRuntime(name="chip_smoke").initialize()
    ComputeRuntime(runtime, "compute")
    pipe = Pipeline(runtime, definition,
                    definition_pathname=SPEECH_DEFINITION,
                    element_classes={
                        "PE_MicrophoneSim": seeded_microphone(seed)})
    done: list = []
    pipe.add_frame_handler(done.append)
    asr = next(node.element for node in pipe.graph.nodes()
               if node.name == "PE_WhisperASR")
    stats = attention_ops.dispatch_stats
    stats.update(flash=0, xla=0)

    def feed(stream_id: str, chunk_seconds: float, frames: int) -> list:
        """Post `frames` chunks to one stream; PE_AudioFraming's
        3-chunk window makes the mel lengths 1x, 2x, 3x the chunk."""
        if stream_id not in pipe.streams:
            pipe.create_stream(stream_id, lease_time=0, parameters={
                "PE_MicrophoneSim.chunk_seconds": chunk_seconds})
        before = len(done)
        for _ in range(frames):
            pipe.post("process_frame", stream_id, {})
        finished = runtime.event.run_until(
            lambda: len(done) >= before + frames, timeout=600.0)
        require(finished and pipe.recovery_stats["frames_failed"] == 0,
                f"speech stream {stream_id}: {len(done) - before}/"
                f"{frames} frames completed, "
                f"{pipe.recovery_stats['frames_failed']} failed")
        return done[before:]

    try:
        short = feed("short", 1.5, 3)       # 150/300/450 mel frames
        require(stats["xla"] > 0 and stats["flash"] == 0,
                f"the 500-frame bucket must take XLA attention: {stats}")
        long = feed("long", 7.0, 3)         # 700/1400/2100 mel frames
        if on_chip:
            require(stats["flash"] > 0,
                    f"the 3000-frame bucket (n_audio_ctx 1536) must "
                    f"take the flash kernel: {stats}")
        say(f"  attention dispatch after both streams: {dict(stats)}")
        # a second, already-compiled round: run seconds without compile
        short += feed("short", 1.5, 2)
        long += feed("long", 7.0, 2)
        program = asr.compute.programs[asr._program]
        buckets = sorted(program.first_call_times)
        require(len(buckets) >= 2 and buckets[0] == 500
                and buckets[-1] >= 3000,
                f"expected the 500- and 3000-frame buckets, got {buckets}")
        for bucket in buckets:
            later = [s for b, s in program.recent_service if b == bucket]
            say(f"  bucket {bucket}: first call (compile+run) "
                f"{program.first_call_times[bucket]:.1f}s, later calls "
                f"{[round(s, 3) for s in later] or 'none'}")

        vocab = asr.config.n_vocab
        for frame in short + long:
            tokens = np.asarray(frame.swag["tokens"])
            require(tokens.ndim == 1 and 0 < tokens.size <= max_tokens
                    and tokens.min() >= 0 and tokens.max() < vocab,
                    f"frame {frame.stream_id}/{frame.frame_id}: bad "
                    f"tokens {tokens}")

        # one batch, decoded by a direct jit of the model on the same
        # mel on the same device, padded exactly as the element pads
        bucket = buckets[0]
        config = dataclasses.replace(asr.config, n_audio_ctx=bucket // 2)
        rows, _found = asr.get_parameter("max_batch", 32)
        batch = np.zeros((int(rows), bucket, config.n_mels), np.float32)
        for row, frame in enumerate(short):
            mel = np.asarray(frame.swag["mel"])
            batch[row, :mel.shape[0]] = mel
        direct = jax.jit(lambda params, mel: greedy_decode_scored(
            params, config, mel, max_tokens=max_tokens,
            sot_sequence=sot_sequence_for(config, timestamps=False),
            suppress_timestamps=True))
        tokens, lengths, _ = direct(asr.params,
                                    jnp.asarray(batch, jnp.bfloat16))
        tokens, lengths = np.asarray(tokens), np.asarray(lengths)
        for row, frame in enumerate(short):
            served = np.asarray(frame.swag["tokens"])
            expect = tokens[row, :lengths[row]]
            require(np.array_equal(served, expect),
                    f"pipeline tokens differ from the direct decode for "
                    f"frame {frame.frame_id}: {served} vs {expect}")
        say(f"  {len(short)} frames of bucket {bucket} equal the direct "
            f"greedy_decode; {len(short) + len(long)} frames completed, "
            f"e.g. {np.asarray(long[-1].swag['tokens'])[:8]}")
    finally:
        runtime.terminate()


# -- llama -------------------------------------------------------------------

def llama_setup(shape: dict, seed: int):
    import jax
    import numpy as np

    from aiko_services_tpu.models.llama import llama_init

    config = llama_config(shape)
    params = llama_init(jax.random.PRNGKey(seed), config)
    rng = np.random.default_rng(seed)
    requests = {
        f"r{i}": (rng.integers(1, config.vocab, size=length).tolist(),
                  shape["new_tokens"])
        for i, length in enumerate(shape["prompt_lengths"])}
    return config, params, requests


def llama_decoder(params, config, shape: dict,
                  attention: str | None = "two_pass"):
    """A paged ContinuousDecoder with the constructor's own paged-KV
    defaults (kv_block 32).  The attention implementation is read once,
    at construction — the AIKO_DECODE_ATTENTION seam: "two_pass" (the
    gather path), "paged_kernel", or None (told nothing: the decoder
    chooses by what it observes)."""
    from aiko_services_tpu import serving

    before = serving.ATTENTION_IMPL
    serving.ATTENTION_IMPL = attention
    try:
        return serving.ContinuousDecoder(
            params, config, paged_kv=True, max_slots=shape["slots"],
            max_seq=shape["max_seq"], t_block=shape["max_seq"],
            prefill_buckets=shape["prefill_buckets"],
            prefill_chunk=shape["prefill_chunk"],
            steps_per_sync=shape["steps_per_sync"],
            name={"two_pass": "gather", "paged_kernel": "kernel",
                  None: "unset"}[attention])
    finally:
        serving.ATTENTION_IMPL = before


def serve(decoder, requests: dict, limit: float = 600.0) -> dict:
    """The normal submit/pump path, to completion."""
    done: dict = {}
    for request_id, (prompt, new_tokens) in requests.items():
        require(decoder.submit(request_id, prompt, new_tokens,
                               lambda rid, tokens: done.update(
                                   {rid: [int(t) for t in tokens]})),
                f"request {request_id} refused")
    deadline = time.perf_counter() + limit
    while len(done) < len(requests):
        require(time.perf_counter() < deadline,
                f"{len(done)}/{len(requests)} requests after {limit}s")
        decoder.pump()
    for request_id, (_, new_tokens) in requests.items():
        tokens = done[request_id]
        require(len(tokens) == new_tokens and
                all(0 <= t < decoder.config.vocab for t in tokens),
                f"request {request_id}: bad tokens {tokens}")
    return done


class TeacherForced:
    """The plain reference every served token is held to: one causal
    forward of the model over prompt + served tokens, then for each
    served token the gap between the reference's best logit at that
    position and the served token's.  Zero when the server emitted the
    reference argmax.  bf16 programs that associate their sums
    differently (bucketed prefill, blockwise kernels, TP all-reduces)
    legitimately flip NEAR-TIES — random weights make them common —
    so a token passes when its gap is within `tolerance` standard
    deviations of that position's logits: a wrong KV row or position
    picks an unrelated token, several deviations down."""

    def __init__(self, params, config, length: int, hidden=None,
                 tolerance: float | None = None,
                 mean_tolerance: float | None = None):
        """`hidden(params, config, tokens) -> (hidden states, _)`: the
        model's uncached forward; llama's where none is given.
        `mean_tolerance` also holds the MEAN gap over all the tokens of
        a check (a model that CHOOSES experts: see phase_latent)."""
        import jax
        import jax.numpy as jnp

        from aiko_services_tpu.models import layers as L
        from aiko_services_tpu.models.llama import (init_llama_caches,
                                                    llama_hidden)

        self.length = length
        # rounding error of ~50 chained bf16 ops (16 layers) is a few
        # percent of a logit's spread; float32 leaves none to speak of
        self.tolerance = tolerance if tolerance is not None else \
            0.125 if config.dtype == jnp.bfloat16 else 1e-3
        self.mean_tolerance = mean_tolerance
        hidden = hidden or (lambda params, config, tokens: llama_hidden(
            params, config, tokens, init_llama_caches(config, 1, length)))

        def gaps(params, tokens, positions, served):
            hidden_states, _ = hidden(params, config, tokens)
            logits = L.linear_logits(params["lm_head"],
                                     hidden_states[0, positions])
            chosen = jnp.take_along_axis(logits, served[:, None], 1)[:, 0]
            return ((jnp.max(logits, axis=-1) - chosen) /
                    jnp.std(logits, axis=-1))

        self._gaps = jax.jit(gaps)
        self.params = params

    def check(self, label: str, requests: dict, served: dict) -> float:
        import numpy as np
        worst, total, count = 0.0, 0.0, 0
        for request_id, (prompt, _) in requests.items():
            tokens = served[request_id]
            full = np.zeros((1, self.length), np.int32)
            sequence = prompt + tokens[:-1]
            full[0, :len(sequence)] = sequence
            positions = len(prompt) - 1 + np.arange(len(tokens))
            gaps = np.asarray(self._gaps(
                self.params, full, positions.astype(np.int32),
                np.asarray(tokens, np.int32)))
            require(np.all(np.isfinite(gaps)) and
                    gaps.max() <= self.tolerance,
                    f"{label} {request_id}: token {int(gaps.argmax())} "
                    f"is {gaps.max():.3f} logit-std below the "
                    f"reference's best (tolerance {self.tolerance})")
            worst = max(worst, float(gaps.max()))
            total, count = total + float(gaps.sum()), count + len(gaps)
        require(self.mean_tolerance is None or
                total / count <= self.mean_tolerance,
                f"{label}: the served tokens lie {total / count:.4f} "
                f"logit-std below the reference's best in the mean "
                f"(tolerance {self.mean_tolerance})")
        return worst


def compare(label: str, requests: dict, ours: dict, theirs: dict) -> None:
    """Exact-identity census between two token streams (reported, and
    written into CHANGES.md; TeacherForced decides pass or fail)."""
    exact = 0
    for request_id, (prompt, _) in requests.items():
        a, b = ours[request_id], theirs[request_id]
        if a == b:
            exact += 1
            continue
        first = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
        say(f"  {label} {request_id} (prompt {len(prompt)}): first "
            f"divergence at token {first}: {a[first]} vs {b[first]}")
    say(f"  {label}: {exact}/{len(requests)} requests token-identical")


def greedy_oracle(params, config, requests: dict) -> dict:
    """llama_greedy_decode — the oracle the repo's tests hold the
    decoder to; the jit retraces once per distinct prompt length."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu.models.llama import llama_greedy_decode

    new_tokens = {n for _, n in requests.values()}.pop()
    decode = jax.jit(lambda params, prompt: llama_greedy_decode(
        params, config, prompt, max_tokens=new_tokens))
    return {
        request_id: [int(t) for t in np.asarray(
            decode(params, jnp.asarray([prompt], jnp.int32)))[0]]
        for request_id, (prompt, _) in requests.items()}


def timed_serve(label: str, decoder, requests: dict,
                clock: CompileClock) -> dict:
    start, compiled = time.perf_counter(), clock.seconds
    served = serve(decoder, requests)
    say(f"  {label}: {len(served)} requests in "
        f"{time.perf_counter() - start:.1f}s, of which compile "
        f"{clock.seconds - compiled:.1f}s")
    return served


def prefill_programs_hold(decoder, bucket: int, chunk: int,
                         kernel_name: str) -> tuple:
    """Whether the decoder's admit (one prompt of `bucket`) and its extend
    (one piece of `chunk`), lowered as it dispatches them, hold the pallas
    call named `kernel_name`: (admit, extend)."""
    import jax.numpy as jnp
    one, flag = jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool)
    pools = (decoder.params, decoder.pool.k_pools, decoder.pool.v_pools,
             decoder._tokens, decoder._lengths, decoder._context)
    table = -(-decoder._cache_t // decoder.kv_block)
    admit = decoder._admit_fn(bucket, 1).lower(
        *pools, jnp.zeros((1, bucket), jnp.int32), one, one, flag,
        jnp.zeros((1, -(-bucket // decoder.kv_block)), jnp.int32),
        *decoder._state_args())
    extend = decoder._extend_fn(chunk, 1).lower(
        *pools, jnp.zeros((1, chunk), jnp.int32), one, one, flag, flag, one,
        jnp.zeros((1, table), jnp.int32), *decoder._state_args(),
        t_cap=decoder._cache_t)
    return tuple(kernel_name in lowered.as_text()
                 for lowered in (admit, extend))


def decode_step_has_kernel(decoder) -> bool:
    import jax.numpy as jnp
    slots = decoder.max_slots
    nb = -(-decoder._cache_t // decoder.kv_block)
    return lowered_has_kernel(
        decoder._step, decoder.params, jnp.ones((slots,), jnp.int32),
        jnp.zeros((slots,), jnp.int32), jnp.ones((slots,), bool),
        jnp.ones((slots,), jnp.int32), decoder.pool.k_pools,
        decoder.pool.v_pools, jnp.zeros((slots, nb), jnp.int32),
        num_steps=decoder.steps_per_sync, eos=-1,
        t_cap=decoder._cache_t)


def phase_llama(shape: dict, seed: int, on_chip: bool,
                clock: CompileClock) -> None:
    config, params, requests = llama_setup(shape, seed)
    reference = TeacherForced(params, config, shape["max_seq"])

    gather = llama_decoder(params, config, shape)
    cold = timed_serve("gather path, first pass", gather, requests,
                       clock)
    warm = timed_serve("gather path, second pass", gather, requests,
                       clock)
    require(cold == warm, "the same requests served twice differ")
    require(gather.stats["prefill_chunks"] > 0,
            "no prompt took the chunked extend")
    say(f"  gather path: prefill_chunks="
        f"{gather.stats['prefill_chunks']} rounds="
        f"{gather.stats['rounds']} pool_blocks="
        f"{gather.pool.num_blocks - 1}x{gather.kv_block} tokens")
    worst = reference.check("gather", requests, cold)
    say(f"  gather path: every token within {reference.tolerance} "
        f"logit-std of the teacher-forced reference (worst {worst:.4f})")
    compare("gather vs llama_greedy_decode", requests, cold,
            greedy_oracle(params, config, requests))

    kernel = llama_decoder(params, config, shape, "paged_kernel")
    require(kernel.paged_kernel and kernel.step_kernel and
            not gather.paged_kernel and not gather.step_kernel,
            "attention implementation was not latched at construction")
    if on_chip:
        require(decode_step_has_kernel(kernel),
                "the kernel decoder's decode step lowered without a "
                "tpu_custom_call")
        require(not decode_step_has_kernel(gather),
                "the gather decoder's decode step holds a kernel")
    fused = timed_serve("paged kernel, first pass", kernel, requests,
                        clock)
    timed_serve("paged kernel, second pass", kernel, requests, clock)
    require(kernel.stats["prefill_chunks"] > 0,
            "no prompt took the kernel's chunked extend")
    worst = reference.check("kernel", requests, fused)
    say(f"  paged kernel: every token within {reference.tolerance} "
        f"logit-std of the reference (worst {worst:.4f})")
    compare("kernel vs gather", requests, fused, cold)

    # told nothing: on the chip the plain step takes the kernel (this
    # pool's live blocks are walked by hand) and the extend gathers;
    # anywhere else (the rehearsal) it is the gather decoder again
    unset = llama_decoder(params, config, shape, None)
    require(not unset.paged_kernel,
            "a decoder that was told nothing says it was asked for the "
            "kernel")
    if on_chip:
        require(unset.step_kernel and unset._walks_live and
                decode_step_has_kernel(unset),
                "a decoder built with AIKO_DECODE_ATTENTION unset on the "
                "chip did not take the paged kernel for its step")
    else:
        require(not unset.step_kernel,
                "off the chip a decoder that was told nothing took the "
                "kernel (it would run in the interpreter)")
    chosen = timed_serve("attention unset, first pass", unset, requests,
                         clock)
    worst = reference.check("unset", requests, chosen)
    say(f"  attention unset ({'kernel' if unset.step_kernel else 'gather'}"
        f" step): every token within {reference.tolerance} logit-std of "
        f"the reference (worst {worst:.4f})")
    compare("unset vs gather", requests, chosen, cold)


# -- latent attention, routed experts -------------------------------------------

def phase_latent(shape: dict, seed: int, on_chip: bool,
                 clock: CompileClock) -> None:
    """models/latent_moe.py through the same decoder: the absorbed walk
    over the latent pool in the step, the expanded path in admit and
    extend, the experts held here.  Paths the latent pool is not
    carried through (the kernel's extend, speculation, int8) have no
    decoder to build."""
    import jax
    import numpy as np

    from aiko_services_tpu.models import latent_moe as M

    config = dataclasses.replace(
        shape["latent_config"], dtype=shape["llama_dtype"],
        max_seq_len=shape["max_seq"])
    params = M.latent_moe_init(jax.random.PRNGKey(seed), config)
    rng = np.random.default_rng(seed)
    requests = {
        f"r{i}": (rng.integers(1, config.vocab, size=length).tolist(),
                  shape["new_tokens"])
        for i, length in enumerate(shape["prompt_lengths"])}
    # a model that CHOOSES experts: where a bfloat16 rounding lands a
    # token the other side of a near-tie between its 8th and 9th
    # expert, the step (absorbed attention) and the reference (expanded)
    # compute two different functions from there on, and the token
    # picked can lie most of a deviation down (PERF.md §4, correctness:
    # sound runs read up to 1.3; first chip run of this phase 0.27).  So
    # a token passes within 2 deviations (a wrong row or position picks
    # an unrelated token, 3 to 4 down) and the MEAN over all tokens is
    # held to 0.05 (sound 0.01, float8 weights 0.2 and more)
    import jax.numpy as jnp
    routed = config.dtype == jnp.bfloat16
    reference = TeacherForced(
        params, config, shape["max_seq"], hidden=M.latent_moe_hidden,
        tolerance=2.0 if routed else None,
        mean_tolerance=0.05 if routed else None)

    gather = llama_decoder(params, config, shape)
    cold = timed_serve("gather path, first pass", gather, requests,
                       clock)
    warm = timed_serve("gather path, second pass", gather, requests,
                       clock)
    require(cold == warm, "the same requests served twice differ")
    require(gather.stats["prefill_chunks"] > 0,
            "no prompt took the chunked extend")
    require(not gather.step_kernel and gather.pool.v_pools == [],
            "the gather decoder holds a kernel, or a V pool")
    stats = gather.stats
    say(f"  gather path: prefill_chunks={stats['prefill_chunks']} "
        f"rounds={stats['rounds']} pool leaf "
        f"{gather.pool.k_pools[0].shape}; experts hit "
        f"{stats['moe_experts_hit']} in {stats['moe_layer_steps']} "
        f"sparse-layer steps, pairs here {stats['moe_pairs_here']} of "
        f"{stats['moe_pairs_routed']}")
    require(0 < stats["moe_pairs_here"] < stats["moe_pairs_routed"],
            "the share's counters say every pair, or none, landed here")
    worst = reference.check("gather", requests, cold)
    say(f"  gather path: every token within {reference.tolerance} "
        f"logit-std of the teacher-forced reference (worst {worst:.4f})")

    unset = llama_decoder(params, config, shape, None)
    if on_chip:
        require(unset.step_kernel and unset._walks_live and
                decode_step_has_kernel(unset),
                "a decoder built with AIKO_DECODE_ATTENTION unset on the "
                "chip did not walk its latent pool")
    else:
        require(not unset.step_kernel,
                "off the chip a decoder that was told nothing took the "
                "kernel (it would run in the interpreter)")
    chosen = timed_serve("attention unset, first pass", unset, requests,
                         clock)
    worst = reference.check("unset", requests, chosen)
    say(f"  attention unset ({'walk' if unset.step_kernel else 'gather'}"
        f" step): every token within {reference.tolerance} logit-std of "
        f"the reference (worst {worst:.4f})")
    compare("unset vs gather", requests, chosen, cold)


# -- recurrent state beside a sparse-selected latent pool ------------------------

def hybrid_config(shape: dict):
    """The hybrid phase's model: the cell's configuration file through
    its own driver."""
    import jax.numpy as jnp

    from benchmark import run as bench
    own = shape["hybrid"]
    return bench.load_module("drivers", own["sizes"]["driver"]).model_config(
        own["sizes"], own["max_seq"], jnp.dtype(shape["llama_dtype"]))


def phase_hybrid(shape: dict, seed: int, on_chip: bool,
                 clock: CompileClock) -> None:
    """models/hybrid_sparse.py through the same decoder: the slot state
    zeroed at admit and carried from chunk to chunk, the KDA recurrence
    and the gather of the chosen groups in the step, the chunked scan
    and the masked absorbed attention in admit and extend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu import serving
    from aiko_services_tpu.models import hybrid_sparse
    from benchmark import weights_hybrid_sparse as W
    from benchmark.reference import hybrid_sparse_lm

    own = shape["hybrid"]
    # the cell's configuration file under its published keys, cut to this
    # phase's depth: the program's weights and the reference's are two
    # readings of one seed (benchmark/weights_hybrid_sparse.py)
    sizes = own["sizes"]
    dtype = jnp.dtype(shape["llama_dtype"])
    config = hybrid_config(shape)
    params = W.decoder_weights(W.key_for(seed), sizes, dtype)
    rng = np.random.default_rng(seed)
    requests = {
        f"r{i}": (rng.integers(1, config.vocab, size=length).tolist(),
                  shape["new_tokens"])
        for i, length in enumerate(own["prompt_lengths"])}
    require(max(own["prompt_lengths"]) > config.index_topk,
            "no prompt reaches past index_topk positions")
    decoder = serving.ContinuousDecoder(
        params, config, paged_kv=True, max_slots=own["slots"],
        max_seq=own["max_seq"], t_block=own["max_seq"],
        prefill_buckets=own["prefill_buckets"],
        prefill_chunk=own["prefill_chunk"],
        prefill_budget=own["prefill_chunk"],
        steps_per_sync=shape["steps_per_sync"], name="hybrid")
    # told nothing, the step's KDA recurrence is the kernel on the chip
    # at the published head of 128 and `kda_recurrent` in the rehearsal
    require(decoder._walks_live and decoder.step_kernel == (
        on_chip and config.kda_head_dim % 128 == 0),
        f"hybrid: step_kernel {decoder.step_kernel} at a head of "
        f"{config.kda_head_dim}, on the chip {on_chip}")
    # a prompt's pieces run KDA's chunked form as ONE kernel a layer on
    # the chip (checked against delta_rule.chunked first), XLA's program
    # in the rehearsal
    scans = check_chunk_scan(
        "a gate a channel", config.kda_heads, config.kda_head_dim,
        config.kda_head_dim, False, own["prefill_chunk"],
        jax.random.PRNGKey(seed + 33), on_chip)
    held = prefill_programs_hold(decoder, own["prefill_buckets"][-1],
                                 own["prefill_chunk"], "kda_chunk_scan")
    require(held == (on_chip and scans,) * 2,
            f"hybrid: admit and extend hold the chunk kernel {held}, on "
            f"the chip {on_chip}")
    cold = timed_serve("first pass", decoder, requests, clock)
    warm = timed_serve("second pass (every slot reused)", decoder,
                       requests, clock)
    require(cold == warm, "the same requests served twice differ: a "
            "slot's state outlived its request")
    stats = decoder.stats
    require(stats["prefill_chunks"] > 0 and stats["prefills"] > 0,
            "the prompts did not take both the admit and the extend")
    require(stats["slot_states_zeroed"] == 2 * len(requests),
            "a request did not start from zeroed state")
    require(0 < stats["dsa_positions_attended"] <
            stats["dsa_positions_live"],
            "the sparse layer attended everything, or nothing")
    require(decoder.pool.k_pools[0] is None and
            decoder.pool.block_nbytes == decoder.kv_block * int(
                (config.kv_rank + config.index_dim / config.index_pool)
                * jnp.dtype(config.dtype).itemsize),
            "a KDA layer holds pool blocks")
    say(f"  prefill_chunks={stats['prefill_chunks']} rounds="
        f"{stats['rounds']} leaves {decoder.pool.k_pools[-1].shape} "
        f"{decoder.pool.v_pools[-1].shape} state "
        f"{decoder.slot_state.nbytes() / 1e6:.1f} MB; attended "
        f"{stats['dsa_positions_attended']} of "
        f"{stats['dsa_positions_live']} live positions; pairs here "
        f"{stats['moe_pairs_here']} of {stats['moe_pairs_routed']}; KDA "
        f"states moved {stats['kda_states_moved']} of "
        f"{stats['kda_states_held']} held; the sparse step computed for "
        f"{stats['dsa_slots_computed']} slots where "
        f"{stats['dsa_slots_decoding']} decoded")
    # the sparse layer's step takes the slots live at a round's entry a
    # window at a time: fewer decode here than a window leaves over, so
    # it never computes for the house
    sparse_layers = sum(kind == "dsa" for kind in config.layer_types)
    require(stats["dsa_slots_decoding"] <= stats["dsa_slots_computed"] and (
        len(requests) >= own["slots"] - hybrid_sparse._STEP_WINDOW or
        stats["dsa_slots_computed"] <
        own["slots"] * stats["steps"] * sparse_layers),
        f"hybrid: the sparse step computed for "
        f"{stats['dsa_slots_computed']} slots over {stats['steps']} steps "
        f"of {own['slots']} slots, {stats['dsa_slots_decoding']} decoding")
    # experts are chosen, and groups: as in phase_latent a token passes
    # within 2 deviations and the MEAN is held to the cell's own limit in
    # bfloat16; float32 against float32 leaves near-ties alone
    routed = dtype == jnp.bfloat16
    tolerance = 2.0 if routed else 1e-3
    limits = sizes["correctness"]["limits"]
    numbers = hybrid_sparse_lm.check(
        [{"prompt": prompt, "served": cold[request_id]}
         for request_id, (prompt, _) in requests.items()],
        sizes, seed, str(dtype), say=lambda line: say("  " + line))["numbers"]
    worst = max(numbers["served_token_gap_std"])
    require(np.isfinite(worst) and worst <= tolerance,
            f"hybrid: a served token is {worst:.3f} logit-std below the "
            f"reference's best (tolerance {tolerance})")
    for name, limit in limits.items():
        require(not routed or max(numbers[name]) <= limit,
                f"hybrid: {name} {max(numbers[name]):.4f} over the cell's "
                f"limit {limit}")
    say(f"  every token within {tolerance} logit-std of the plain "
        f"reference's best (worst {worst:.4f}, mean "
        f"{numbers['served_token_gap_mean_std'][0]:.4f})")


# -- a third leaf beside K and V, keys chosen token by token --------------------

def phase_sparse_gqa(shape: dict, seed: int, on_chip: bool,
                     clock: CompileClock) -> None:
    """models/sparse_gqa.py through the same decoder: K, V and an indexer
    key a token in three pool leaves, every live position scored, the
    exact top of them chosen as a mask and the slot's live blocks attended
    under it in the step (on the chip: the walk of ops/paged_attention);
    a chunk's queries each choosing their own positions of the prefix."""
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu import serving
    from benchmark import run as bench
    from benchmark import weights_sparse_gqa as W
    from benchmark.reference import sparse_gqa_lm

    own = shape["sparse_gqa"]
    sizes = own["sizes"]
    dtype = jnp.dtype(shape["llama_dtype"])
    config = bench.load_module("drivers", sizes["driver"]).model_config(
        sizes, own["max_seq"], dtype)
    params = W.decoder_weights(W.key_for(seed), sizes, dtype)
    rng = np.random.default_rng(seed)
    requests = {
        f"r{i}": (rng.integers(1, config.vocab, size=length).tolist(),
                  shape["new_tokens"])
        for i, length in enumerate(own["prompt_lengths"])}
    require(min(own["prompt_lengths"]) + shape["new_tokens"] <
            config.index_topk < max(own["prompt_lengths"]),
            "the prompts do not lie on both sides of topk")
    decoder = serving.ContinuousDecoder(
        params, config, paged_kv=True, max_slots=own["slots"],
        max_seq=own["max_seq"], t_block=own["max_seq"],
        prefill_buckets=own["prefill_buckets"],
        prefill_chunk=own["prefill_chunk"],
        prefill_budget=own["prefill_chunk"],
        steps_per_sync=shape["steps_per_sync"], name="sparse_gqa")
    # told nothing, the step attends through the masked walk on the chip
    # at the published head of 128, and the plain form in the rehearsal
    require(decoder._walks_live and decoder.step_kernel == (
        on_chip and config.head_dim % 128 == 0),
        f"sparse_gqa: step_kernel {decoder.step_kernel} at a head of "
        f"{config.head_dim}, on the chip {on_chip}")
    cold = timed_serve("first pass", decoder, requests, clock)
    warm = timed_serve("second pass (every slot reused)", decoder,
                       requests, clock)
    require(cold == warm, "the same requests served twice differ")
    stats, pool = decoder.stats, decoder.pool
    require(stats["prefill_chunks"] > 0 and stats["prefills"] > 0,
            "the prompts did not take both the admit and the extend")
    require(0 < stats["dsa_positions_attended"] <
            stats["dsa_positions_live"],
            "the selection attended everything, or nothing")
    # a slot-step attends at most the round's own rows beside what it
    # read of the pool, chosen or not
    require(0 < stats["dsa_slot_steps_dense"] and
            stats["dsa_rows_fetched"] >= stats["dsa_positions_attended"] -
            shape["steps_per_sync"] * config.num_layers *
            stats["tokens_decode"],
            "no slot-step attended all it held, or positions were attended "
            "that the step did not read")
    layers = config.num_layers
    require(len(pool.k_pools) == layers and len(pool.v_pools) == 2 * layers
            and pool.block_nbytes == decoder.kv_block * layers * int(
                (2 * config.num_kv_heads * config.head_dim +
                 config.index_row_lanes) * jnp.dtype(config.dtype).itemsize),
            "the pool does not hold K, V and an indexer key a layer")
    say(f"  prefill_chunks={stats['prefill_chunks']} rounds="
        f"{stats['rounds']} leaves {pool.k_pools[-1].shape} "
        f"{pool.v_pools[layers - 1].shape} {pool.v_pools[-1].shape}; "
        f"attended {stats['dsa_positions_attended']} of "
        f"{stats['dsa_positions_live']} live positions, "
        f"{stats['dsa_rows_fetched']} read of the pool, "
        f"{stats['dsa_slot_steps_dense']} slot-steps attended all; pairs "
        f"here {stats['moe_pairs_here']} of {stats['moe_pairs_routed']}, "
        f"experts hit {stats['moe_experts_hit']} over "
        f"{stats['moe_layer_steps']} layer-steps")
    # as in phase_hybrid: a token passes within 2 deviations and the MEAN
    # is held in bfloat16, here to TEN times the cell's own limit: the
    # limit is for a run's several hundred served tokens, and of this
    # phase's hundred one token a near-tie apart moves the mean by a
    # hundredth of its gap
    routed = dtype == jnp.bfloat16
    tolerance = 2.0 if routed else 1e-3
    numbers = sparse_gqa_lm.check(
        [{"prompt": prompt, "served": cold[request_id]}
         for request_id, (prompt, _) in requests.items()],
        sizes, seed, str(dtype), say=lambda line: say("  " + line))["numbers"]
    worst = max(numbers["served_token_gap_std"])
    require(np.isfinite(worst) and worst <= tolerance,
            f"sparse_gqa: a served token is {worst:.3f} logit-std below "
            f"the reference's best (tolerance {tolerance})")
    for name, limit in sizes["correctness"]["limits"].items():
        require(not routed or max(numbers[name]) <= 10 * limit,
                f"sparse_gqa: {name} {max(numbers[name]):.5f} over ten "
                f"times the cell's limit {limit}")
    say(f"  every token within {tolerance} logit-std of the plain "
        f"reference's best (worst {worst:.4f}, mean "
        f"{numbers['served_token_gap_mean_std'][0]:.5f})")


# -- recurrent state beside a K/V pool the shared kernel walks -------------------

def phase_gated_delta(shape: dict, seed: int, on_chip: bool,
                      clock: CompileClock) -> None:
    """models/gated_delta.py through the same decoder: recurrent layers
    whose state is a slot's (zeroed at admit, carried from chunk to chunk,
    moved by ops/kda_step's kernel in the step on the chip) beside full
    layers whose K and V pool the shared paged kernel walks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu import serving
    from benchmark import run as bench
    from benchmark import weights_gated_delta as W
    from benchmark.reference import gated_delta_lm

    own = shape["gated_delta"]
    sizes = own["sizes"]
    dtype = jnp.dtype(shape["llama_dtype"])
    config = bench.load_module("drivers", sizes["driver"]).model_config(
        sizes, own["max_seq"], dtype)
    require(check_live_step(
        "a gate a head", config.gdn_heads, config.key_dim, config.value_dim,
        True, 8 * own["slots"], jax.random.PRNGKey(seed + 40), on_chip),
        "the phase's head sizes do not take the kernel")
    scans = check_chunk_scan(
        "a gate a head", config.gdn_heads, config.key_dim, config.value_dim,
        True, own["prefill_chunk"], jax.random.PRNGKey(seed + 41), on_chip)
    require(scans or not on_chip,
            "the phase's head sizes do not take the chunk kernel")
    params = W.decoder_weights(W.key_for(seed), sizes, dtype)
    rng = np.random.default_rng(seed)
    requests = {
        f"r{i}": (rng.integers(1, config.vocab, size=length).tolist(),
                  shape["new_tokens"])
        for i, length in enumerate(own["prompt_lengths"])}
    decoder = serving.ContinuousDecoder(
        params, config, paged_kv=True, max_slots=own["slots"],
        max_seq=own["max_seq"], t_block=own["max_seq"],
        prefill_buckets=own["prefill_buckets"],
        prefill_chunk=own["prefill_chunk"],
        prefill_budget=own["prefill_chunk"],
        steps_per_sync=shape["steps_per_sync"], name="gated_delta")
    # told nothing, on the chip the step is the kernels' for BOTH reasons
    # (the walk of the pool, the recurrence over the live slots' state);
    # in the rehearsal gathered views and the recurrence over every slot
    require(decoder.step_kernel == on_chip and
            decoder._walks_live == on_chip,
            f"gated_delta: step_kernel {decoder.step_kernel}, walks live "
            f"{decoder._walks_live}, on the chip {on_chip}")
    # a prompt's pieces run the chunked form as ONE kernel a layer on the
    # chip, XLA's program (delta_rule.chunked) in the rehearsal
    held = prefill_programs_hold(decoder, own["prefill_buckets"][-1],
                                 own["prefill_chunk"], "gdn_chunk_scan")
    require(held == (on_chip, on_chip),
            f"gated_delta: admit and extend hold the chunk kernel {held}, "
            f"on the chip {on_chip}")
    cold = timed_serve("first pass", decoder, requests, clock)
    warm = timed_serve("second pass (every slot reused)", decoder,
                       requests, clock)
    require(cold == warm, "the same requests served twice differ: a "
            "slot's state outlived its request")
    stats, pool = decoder.stats, decoder.pool
    require(stats["prefill_chunks"] > 0 and stats["prefills"] > 0,
            "the prompts did not take both the admit and the extend")
    require(stats["slot_states_zeroed"] == 2 * len(requests),
            "a request did not start from zeroed state")
    require(0 < stats["gdn_states_moved"] <= stats["gdn_states_held"],
            "the recurrence moved no state, or more than the layers hold")
    full = [i for i, kind in enumerate(config.layer_types) if kind == "full"]
    require(all((pool.k_pools[i] is not None) == (i in full)
                for i in range(config.num_layers)) and
            pool.block_nbytes == decoder.kv_block * len(full) * 2 *
            config.num_heads * config.head_dim *
            jnp.dtype(config.dtype).itemsize,
            "a recurrent layer holds pool blocks, or a full layer none")
    say(f"  prefill_chunks={stats['prefill_chunks']} rounds="
        f"{stats['rounds']} leaves {pool.k_pools[full[0]].shape} state "
        f"{decoder.slot_state.nbytes() / 1e6:.1f} MB; states moved "
        f"{stats['gdn_states_moved']} of {stats['gdn_states_held']} held")
    # no expert and no group is chosen: a token passes within the dense
    # cell's own gap in bfloat16, and float32 leaves near-ties alone
    half = dtype == jnp.bfloat16
    tolerance = 0.5 if half else 1e-3
    numbers = gated_delta_lm.check(
        [{"prompt": prompt, "served": cold[request_id]}
         for request_id, (prompt, _) in requests.items()],
        sizes, seed, str(dtype), say=lambda line: say("  " + line))["numbers"]
    worst = max(numbers["served_token_gap_std"])
    require(np.isfinite(worst) and worst <= tolerance,
            f"gated_delta: a served token is {worst:.3f} logit-std below "
            f"the reference's best (tolerance {tolerance})")
    for name, limit in sizes["correctness"]["limits"].items():
        require(not half or max(numbers[name]) <= limit,
                f"gated_delta: {name} {max(numbers[name]):.4f} over the "
                f"cell's limit {limit}")
    say(f"  every token within {tolerance} logit-std of the plain "
        f"reference's best (worst {worst:.4f}, mean "
        f"{numbers['served_token_gap_mean_std'][0]:.5f})")



def check_plain_live_step(heads: int, width: int, state_lanes: int,
                          slots: int, layers: int, key,
                          on_chip: bool) -> bool:
    """ops.kda_step.kda_live_step's plain rule (beta None: S <- exp(g) S +
    k v^T, o = S^T q, ONE k and ONE q a slot) against the four lines
    written out, at `heads` heads of [state_lanes, width] laid side by
    side; some slots live, none, all.  On the chip also its TIME: `layers`
    calls in one program (a decode step's Mamba layers) with three slots
    in four live, against the live states' bytes once in and once out at
    the memory's speed.  -> whether a decode step takes the kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu.ops import kda_step

    takes = kda_step.moves_live_states(heads, state_lanes, not on_chip,
                                       value_dim=width, by_head=True)
    say(f"  kda_live_step, the plain rule, H{heads} [{state_lanes}, "
        f"{width}]: a decode step takes it {takes}")
    if not takes:
        return False
    keys = jax.random.split(key, 5)
    q = jax.random.normal(keys[0], (slots, state_lanes))
    k = jax.random.normal(keys[1], (slots, state_lanes))
    v = jax.random.normal(keys[2], (slots, heads, width))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], (slots, heads)))
    state = jax.random.normal(keys[4], (slots, state_lanes, heads * width))

    def plain(q, k, v, g, state, active):
        return kda_step.kda_live_step(q, k, v, g, None, state, active,
                                      interpret=not on_chip)

    @jax.jit
    def oracle(q, k, v, g, state):
        new = state * jnp.repeat(jnp.exp(g), width, axis=-1)[:, None, :] + \
            k[:, :, None] * v.reshape(slots, 1, -1)
        return jnp.einsum("sd,sdl->sl", q, new,
                          precision=jax.lax.Precision.HIGHEST).reshape(
                              v.shape), new

    kernel = jax.jit(plain)
    if on_chip:
        require(lowered_has_kernel(kernel, q, k, v, g, state,
                                   jnp.ones((slots,), bool)),
                "kda_live_step lowered without a tpu_custom_call")
    want_out, want = oracle(q, k, v, g, state)
    for label, live in (("some", np.arange(slots) % 3 == 1),
                        ("none", np.zeros(slots, bool)),
                        ("all", np.ones(slots, bool))):
        out, new = kernel(q, k, v, g, state, jnp.asarray(live))
        scale = float(jnp.abs(want_out).max())
        worst = max(float(jnp.abs(out - want_out)[live].max(initial=0.0))
                    / scale,
                    float(jnp.abs(new - want)[live].max(initial=0.0)))
        kept = bool(np.array_equal(np.asarray(new)[~live],
                                   np.asarray(state)[~live])) and \
            not np.asarray(out)[~live].any()
        say(f"  kda_live_step, the plain rule, {label} of {slots} slots "
            f"live: max|kernel-oracle|={worst:.2e}, the others untouched "
            f"{kept}")
        # float32 sums of a hundred terms in another order
        require(worst <= 1e-5 and kept,
                f"kda_live_step, the plain rule, {label} live off by {worst}")
    if on_chip:
        live = np.arange(slots) % 4 != 3
        active = jnp.asarray(live)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(state):
            def layer(_, carry):
                state, total = carry
                out, state = plain(q, k, v, g * 0.01, state, active)
                return state, total + out
            return jax.lax.fori_loop(0, layers, layer,
                                     (state, jnp.zeros_like(v)))

        held = step(state + 0.0)
        jax.block_until_ready(held)
        rounds, start = 10, time.perf_counter()
        for _ in range(rounds):
            held = step(held[0])
        jax.block_until_ready(held)
        seconds = (time.perf_counter() - start) / rounds
        moved = 2 * int(live.sum()) * layers * state[0].nbytes
        say(f"  kda_live_step, the plain rule, {layers} calls with "
            f"{int(live.sum())} of {slots} slots live: {seconds * 1e3:.3f} "
            f"ms a step's worth (host clock, one dispatch), {moved / 1e9:.3f}"
            f" GB in and out, {moved / seconds / 1e9:.0f} GB/s = "
            f"{100 * moved / seconds / 819e9:.1f}% of 819 GB/s")
    return True


def check_ssm_chunk_scan(heads: int, width: int, state: int, tokens: int,
                         layers: int, key, on_chip: bool) -> bool:
    """ops.ssm_chunk.ssm_chunk_scan against models/ssm_hybrid.ssm_chunked
    (and `ssm_plain` token by token) over a prompt's piece of `tokens` at
    `heads` heads of `width` that share B and C of `state`, dt and A drawn
    as the cell's weights make them, from a state that is not zero and a
    tail of dt = 0 in the second row: output and state to 2e-4 of their
    scale (float32 at HIGHEST on both sides, sums in another order).  On
    the chip also the kernel ALONE against XLA's form, one row of `tokens`:
    the host's clock around one dispatch of `layers` calls.  -> whether a
    prompt's piece takes the kernel at this geometry on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu.models import ssm_hybrid
    from aiko_services_tpu.ops import ssm_chunk

    takes = ssm_chunk.scans_ssm_chunks(heads, width, state)
    say(f"  ssm_chunk_scan, H{heads} x {width}, N {state}: a prompt's piece "
        f"takes it on the chip {takes}")
    if on_chip and not takes:
        return False
    keys = jax.random.split(key, 6)
    live = jnp.arange(tokens)[None] < jnp.array([[tokens],
                                                 [tokens - tokens // 3]])
    x = jax.random.normal(keys[0], (2, tokens, heads, width))
    dt = jnp.exp(jax.random.uniform(
        keys[1], (2, tokens, heads), minval=np.log(1e-3),
        maxval=np.log(0.1))) * live[..., None]
    b = jax.random.normal(keys[2], (2, tokens, state))
    c = jax.random.normal(keys[3], (2, tokens, state))
    a = -jnp.exp(jax.random.uniform(keys[4], (heads,), minval=0.0,
                                    maxval=np.log(16.0)))
    memory = jax.random.normal(keys[5], (2, state, heads * width))
    kernel = jax.jit(functools.partial(ssm_chunk.ssm_chunk_scan,
                                       interpret=not on_chip))
    if on_chip:
        require(lowered_has_kernel(kernel, x, dt, b, c, a, memory),
                "ssm_chunk_scan lowered without a tpu_custom_call")
    out, new = kernel(x, dt, b, c, a, memory)
    for name, form in (("chunked", ssm_hybrid.ssm_chunked),
                       ("plain", ssm_hybrid.ssm_plain)):
        want_out, want = jax.jit(form)(x, dt, b, c, a, memory)
        worst = max(float(jnp.abs(out - want_out).max() /
                          jnp.abs(want_out).max()),
                    float(jnp.abs(new - want).max() / jnp.abs(want).max()))
        say(f"  ssm_chunk_scan, {tokens} tokens: max|kernel-{name}| of the "
            f"scale={worst:.2e}")
        require(worst <= 2e-4, f"ssm_chunk_scan off {name} by {worst}")
    if on_chip:
        one = (x[:1], dt[:1], b[:1], c[:1], a)
        for name, form in (("the kernel", kernel),
                           ("XLA's ssm_chunked", ssm_hybrid.ssm_chunked)):
            @functools.partial(jax.jit, donate_argnums=(0,))
            def piece(held, form=form):
                def layer(_, carry):
                    held, total = carry
                    out, held = form(*one, held)
                    return held, total + out[:, -1]
                return jax.lax.fori_loop(
                    0, layers, layer, (held, jnp.zeros_like(x[:1, -1])))

            held = piece(memory[:1] + 0.0)
            jax.block_until_ready(held)
            rounds, start = 10, time.perf_counter()
            for _ in range(rounds):
                held = piece(held[0])
            jax.block_until_ready(held)
            seconds = (time.perf_counter() - start) / rounds
            say(f"  {name}, {layers} calls over one row of {tokens} tokens: "
                f"{seconds * 1e3:.3f} ms a piece's worth (host clock, one "
                f"dispatch), {seconds / layers * 1e6:.0f} us a call")
    return takes


def phase_ssm_hybrid(shape: dict, seed: int, on_chip: bool,
                     clock: CompileClock) -> None:
    """models/ssm_hybrid.py through the same decoder: Mamba-2 layers whose
    state is a slot's (zeroed at admit, carried from chunk to chunk, moved
    by ops/kda_step's plain rule in the step on the chip) beside attention
    layers of 64-wide K/V heads whose one pool leaf, a head's V and K side
    by side, the shared paged kernel walks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu import serving
    from benchmark import run as bench
    from benchmark import weights_ssm_hybrid as W
    from benchmark.reference import ssm_hybrid_lm

    own = shape["ssm_hybrid"]
    sizes = own["sizes"]
    dtype = jnp.dtype(shape["llama_dtype"])
    config = bench.load_module("drivers", sizes["driver"]).model_config(
        sizes, own["max_seq"], dtype)
    takes = check_plain_live_step(
        config.ssm_heads, config.ssm_head_dim, config.ssm_state,
        own["kernel_slots"], own["kernel_layers"],
        jax.random.PRNGKey(seed + 45), on_chip)
    require(takes, "the phase's head sizes do not take the kernel")
    # a prompt's pieces run the chunked form as ONE kernel a layer on the
    # chip (checked against ssm_chunked and timed alone first), XLA's
    # program in the rehearsal
    scans = check_ssm_chunk_scan(
        config.ssm_heads, config.ssm_head_dim, config.ssm_state,
        own["kernel_tokens"], own["kernel_layers"],
        jax.random.PRNGKey(seed + 46), on_chip)
    params = W.decoder_weights(W.key_for(seed), sizes, dtype)
    rng = np.random.default_rng(seed)
    requests = {
        f"r{i}": (rng.integers(1, config.vocab, size=length).tolist(),
                  shape["new_tokens"])
        for i, length in enumerate(own["prompt_lengths"])}
    decoder = serving.ContinuousDecoder(
        params, config, paged_kv=True, max_slots=own["slots"],
        max_seq=own["max_seq"], t_block=own["max_seq"],
        prefill_buckets=own["prefill_buckets"],
        prefill_chunk=own["prefill_chunk"],
        prefill_budget=own["prefill_chunk"],
        steps_per_sync=shape["steps_per_sync"], name="ssm_hybrid")
    # told nothing, on the chip the step is the kernels' for BOTH reasons
    # (the walk of the pool's rows, the plain rule over the live slots'
    # state): the decoder's one flag keeps one meaning (ISSUE 45, route 1)
    require(decoder.step_kernel == on_chip and
            decoder._walks_live == on_chip and
            bool(decoder._model_kernel),
            f"ssm_hybrid: step_kernel {decoder.step_kernel}, walks live "
            f"{decoder._walks_live}, on the chip {on_chip}")
    held = prefill_programs_hold(decoder, own["prefill_buckets"][-1],
                                 own["prefill_chunk"], "ssm_chunk_scan")
    require(held == (on_chip and scans,) * 2,
            f"ssm_hybrid: admit and extend hold the chunk kernel {held}, on "
            f"the chip {on_chip}")
    cold = timed_serve("first pass", decoder, requests, clock)
    warm = timed_serve("second pass (every slot reused)", decoder,
                       requests, clock)
    require(cold == warm, "the same requests served twice differ: a "
            "slot's state outlived its request")
    stats, pool = decoder.stats, decoder.pool
    require(stats["prefill_chunks"] > 0 and stats["prefills"] > 0,
            "the prompts did not take both the admit and the extend")
    require(stats["slot_states_zeroed"] == 2 * len(requests),
            "a request did not start from zeroed state")
    require(0 < stats["ssm_states_moved"] <= stats["ssm_states_held"],
            "the recurrence moved no state, or more than the layers hold")
    attending = [i for i, kind in enumerate(config.layer_types)
                 if kind == "attention"]
    require(all((pool.k_pools[i] is not None) == (i in attending)
                for i in range(config.num_layers)) and not pool.v_pools and
            pool.block_nbytes == decoder.kv_block * len(attending) * 2 *
            config.num_kv_heads * config.head_dim *
            jnp.dtype(config.dtype).itemsize,
            "a Mamba layer holds pool blocks, or an attention layer none")
    varied = min(len(set(tokens)) / len(tokens) for tokens in cold.values())
    say(f"  prefill_chunks={stats['prefill_chunks']} rounds="
        f"{stats['rounds']} leaves {pool.k_pools[attending[0]].shape} state "
        f"{decoder.slot_state.nbytes() / 1e6:.1f} MB; states moved "
        f"{stats['ssm_states_moved']} of {stats['ssm_states_held']} held; "
        f"distinct tokens a served token, the least {varied:.2f}")
    require(varied > 0.5, "a request was served the same few tokens over "
            "and over: the tied head reads the embedding back")
    # no expert and no group is chosen: a token passes within the dense
    # cell's own gap in bfloat16, and float32 leaves near-ties alone
    half = dtype == jnp.bfloat16
    tolerance = 0.5 if half else 1e-3
    numbers = ssm_hybrid_lm.check(
        [{"prompt": prompt, "served": cold[request_id]}
         for request_id, (prompt, _) in requests.items()],
        sizes, seed, str(dtype), say=lambda line: say("  " + line))["numbers"]
    worst = max(numbers["served_token_gap_std"])
    require(np.isfinite(worst) and worst <= tolerance,
            f"ssm_hybrid: a served token is {worst:.3f} logit-std below "
            f"the reference's best (tolerance {tolerance})")
    for name, limit in sizes["correctness"]["limits"].items():
        require(not half or max(numbers[name]) <= limit,
                f"ssm_hybrid: {name} {max(numbers[name]):.4f} over the "
                f"cell's limit {limit}")
    say(f"  every token within {tolerance} logit-std of the plain "
        f"reference's best (worst {worst:.4f}, mean "
        f"{numbers['served_token_gap_mean_std'][0]:.5f})")


# -- four chips --------------------------------------------------------------

def phase_tensor_parallel(shape: dict, seed: int, on_chip: bool,
                          clock: CompileClock) -> None:
    """Llama over create_mesh({"model": 4}) against the one-chip
    decoder, same process, same requests."""
    import jax

    from aiko_services_tpu.models.llama import llama_axes
    from aiko_services_tpu.parallel import create_mesh, shard_pytree

    devices = jax.devices()
    require(len(devices) >= 4, f"--chips 4 needs four devices, jax "
                               f"sees {len(devices)}")
    config, params, requests = llama_setup(shape, seed)
    mesh = create_mesh({"model": 4}, devices=devices[:4])
    placed = shard_pytree(params, llama_axes(config), mesh)

    # are the weights really spread?  Code that has only ever seen one
    # chip may leave everything on the first
    sharded = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(placed):
        if leaf.sharding.is_fully_replicated:
            continue
        sharded += 1
        sizes = {shard.device.id: shard.data.nbytes
                 for shard in leaf.addressable_shards}
        require(len(sizes) == 4 and
                all(size * 4 == leaf.nbytes for size in sizes.values()),
                f"{jax.tree_util.keystr(path)}: shard bytes {sizes} are "
                f"not a quarter of {leaf.nbytes}")
    total = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(placed))
    require(sharded > 0, "no leaf is sharded over the model axis")
    say(f"  {sharded} TP-sharded leaves, each device holds a quarter of "
        f"each; params {total / 2**30:.2f} GiB")
    if on_chip:
        for device in devices[1:4]:
            in_use = device.memory_stats()["bytes_in_use"]
            require(in_use > total // 16,
                    f"device {device.id} holds {in_use} bytes: the "
                    f"weights are not on it")

    reference = TeacherForced(params, config, shape["max_seq"])
    single = timed_serve("one chip", llama_decoder(params, config, shape),
                         requests, clock)
    # told nothing: weights sharded over four devices keep the gather
    # path (a pallas_call over a heads-sharded pool needs a shard_map)
    tp_decoder = llama_decoder(placed, config, shape, None)
    require(not tp_decoder.step_kernel,
            "the tensor-parallel decoder took the paged kernel")
    tp = timed_serve("TP=4, first pass", tp_decoder, requests, clock)
    # the first pass returns state with the compiler's shardings, so
    # the second may compile again; the third is the compiled path
    timed_serve("TP=4, second pass", tp_decoder, requests, clock)
    timed_serve("TP=4, third pass", tp_decoder, requests, clock)
    for label, served in (("one chip", single), ("TP=4", tp)):
        worst = reference.check(label, requests, served)
        say(f"  {label}: every token within {reference.tolerance} "
            f"logit-std of the reference (worst {worst:.4f})")
    compare("TP=4 vs one chip", requests, tp, single)
    pools = jax.tree_util.tree_leaves(tp_decoder.pool.k_pools)
    say(f"  TP=4 KV pool leaf sharding: {pools[0].sharding}")


# -- entry -------------------------------------------------------------------

def shapes(rehearse: bool) -> dict:
    import jax.numpy as jnp

    from aiko_services_tpu.models.latent_moe import (LATENT_MOE_PRESETS,
                                                     LatentMoeConfig)
    from benchmark import run as bench

    def hybrid_sizes(max_seq: int, held: int | None) -> dict:
        """The cell's configuration file (its `rehearse` sizes laid over
        it for a rehearsal) cut to a KDA layer with the dense MLP and a
        sparse-attention layer with `held` of the experts."""
        sizes = bench.load_json("benchmark", "configs",
                                "glm-5.3-flash-ep8-d5.json")
        if rehearse:
            sizes = bench.merged(sizes, sizes["rehearse"])
        return bench.merged(sizes, {
            "num_hidden_layers": 2,
            "layer_types": ["linear_attention", "deepseek_sparse_attention"],
            "mlp_layer_types": ["dense", "sparse"],
            "n_routed_experts": held or sizes["n_routed_experts"],
            "serving": {"max_seq": max_seq}})

    def sparse_gqa_sizes(max_seq: int) -> dict:
        """The newest cell's configuration file (its `rehearse` sizes
        laid over it for a rehearsal) cut to two layers."""
        sizes = bench.load_json("benchmark", "configs",
                                "keye-vl-2.0-30b-a3b-ep8-d12.json")
        if rehearse:
            sizes = bench.merged(sizes, sizes["rehearse"])
        return bench.merged(sizes, {"num_hidden_layers": 2,
                                    "serving": {"max_seq": max_seq}})

    def gated_delta_sizes(max_seq: int) -> dict:
        """The Gated-DeltaNet cell's configuration file (its `rehearse`
        sizes laid over it for a rehearsal) cut to one period: three
        recurrent layers and a full one."""
        sizes = bench.load_json("benchmark", "configs",
                                "olmo-hybrid-7b-d16.json")
        if rehearse:
            sizes = bench.merged(sizes, sizes["rehearse"])
        return bench.merged(sizes, {
            "num_hidden_layers": 4,
            "layer_types": ["linear_attention"] * 3 + ["full_attention"],
            "serving": {"max_seq": max_seq}})

    def ssm_hybrid_sizes(max_seq: int) -> dict:
        """The Mamba-2 cell's configuration file (its `rehearse` sizes laid
        over it for a rehearsal) cut to four layers, one of them
        attention."""
        sizes = bench.load_json("benchmark", "configs",
                                "granite-4.0-h-micro.json")
        if rehearse:
            sizes = bench.merged(sizes, sizes["rehearse"])
        return bench.merged(sizes, {
            "num_hidden_layers": 4,
            "layer_types": ["mamba", "mamba", "attention", "mamba"],
            "serving": {"max_seq": max_seq}})
    if rehearse:
        # the CPU rehearsal: same code paths, toy widths
        return {"whisper_preset": "test", "llama_preset": "tiny",
                "latent_config": dataclasses.replace(
                    LATENT_MOE_PRESETS["tiny"], experts_first=2,
                    experts_held=4),
                "hybrid": {
                    "sizes": hybrid_sizes(128, None),
                    "max_seq": 128, "slots": 16, "prefill_buckets": (8, 32),
                    "prefill_chunk": 32,
                    "prompt_lengths": (8, 20, 44, 100)},
                "sparse_gqa": {
                    "sizes": sparse_gqa_sizes(128),
                    "max_seq": 128, "slots": 4, "prefill_buckets": (8, 32),
                    "prefill_chunk": 32,
                    "prompt_lengths": (5, 20, 44, 100)},
                "gated_delta": {
                    "sizes": gated_delta_sizes(128),
                    "max_seq": 128, "slots": 4, "prefill_buckets": (8, 32),
                    "prefill_chunk": 32,
                    "prompt_lengths": (5, 20, 44, 100)},
                "ssm_hybrid": {
                    "sizes": ssm_hybrid_sizes(128),
                    "max_seq": 128, "slots": 4, "prefill_buckets": (8, 32),
                    "prefill_chunk": 32, "kernel_slots": 5,
                    "kernel_layers": 2, "kernel_tokens": 40,
                    "prompt_lengths": (5, 20, 44, 100)},
                "llama_heads": 4,
                "llama_dtype": jnp.float32, "max_seq": 128, "slots": 8,
                "prefill_buckets": (8, 32), "prefill_chunk": 32,
                "steps_per_sync": 4, "new_tokens": 8,
                "prompt_lengths": (8, 8, 20, 20, 44, 44, 100, 100),
                "flash": (1, 2, 256, 64)}
    return {"whisper_preset": "small", "llama_preset": "1b",
            # the published widths, a dense and a sparse layer, the share
            # of the benchmark's cell (12 of 192 experts, an eighth of
            # the vocabulary): 2.9 GB in bfloat16
            "latent_config": LatentMoeConfig(
                vocab=20480, num_layers=2, experts_held=12),
            # the published widths, a KDA layer with the dense MLP and a
            # sparse-attention layer with 12 of the 288 experts, an
            # eighth of the vocabulary: 1.8 GB in bfloat16; a prompt of
            # 2,600 positions reaches past the 2,048 attended at most;
            # sixteen slots for four requests, so that the sparse step's
            # window of eight leaves slots it does not compute for
            "hybrid": {
                "sizes": hybrid_sizes(3072, 12),
                "max_seq": 3072, "slots": 16, "prefill_buckets": (64, 256),
                "prefill_chunk": 256,
                "prompt_lengths": (64, 200, 1024, 2600)},
            # the published widths, two layers with 16 of the 128 experts,
            # an eighth of the vocabulary: 0.5 GB in bfloat16; prompts on
            # both sides of the 2,048 positions attended at most
            "sparse_gqa": {
                "sizes": sparse_gqa_sizes(3072),
                "max_seq": 3072, "slots": 4, "prefill_buckets": (64, 256),
                "prefill_chunk": 256,
                "prompt_lengths": (64, 200, 1024, 2600)},
            # the published widths, one period (three recurrent layers and
            # a full one) and the whole vocabulary: 3.2 GB in bfloat16;
            # prompts that are admitted whole and prompts that go chunk by
            # chunk, each chunk from the state the last one left
            "gated_delta": {
                "sizes": gated_delta_sizes(1024),
                "max_seq": 1024, "slots": 4, "prefill_buckets": (64, 256),
                "prefill_chunk": 256,
                "prompt_lengths": (64, 200, 600, 900)},
            # the published widths, four layers (three Mamba-2 and an
            # attention one) and the whole tied vocabulary: 0.9 GB in
            # bfloat16; the kernels alone at the cell's 32 slots, a step's
            # (and a piece's) 36 layers and the extend's 512 tokens
            "ssm_hybrid": {
                "sizes": ssm_hybrid_sizes(1024),
                "max_seq": 1024, "slots": 4, "prefill_buckets": (64, 256),
                "prefill_chunk": 256, "kernel_slots": 32,
                "kernel_layers": 36, "kernel_tokens": 512,
                "prompt_lengths": (64, 200, 600, 900)},
            "llama_heads": 16,
            "llama_dtype": jnp.bfloat16, "max_seq": 1280, "slots": 8,
            "prefill_buckets": (64, 256), "prefill_chunk": 256,
            "steps_per_sync": 4, "new_tokens": 32,
            "prompt_lengths": (64, 64, 200, 200, 448, 448, 1024, 1024),
            "flash": (4, 12, 1536, 64)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs ONLY the TP=4 llama phase and the "
                             "one-chip decoder it is compared with")
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny presets on any backend (CPU "
                             "rehearsal); never prints ok")
    parser.add_argument("--only", nargs="+", metavar="PHASE",
                        help="run these phases alone; never prints ok")
    args = parser.parse_args(argv)

    import faulthandler
    faulthandler.dump_traceback_later(WATCHDOG_SECONDS, exit=True)

    import jax

    device = jax.devices()[0]
    found = {"platform": device.platform, "kind": device.device_kind,
             "count": len(jax.devices())}
    on_chip = device.platform == "tpu"
    if not on_chip and not args.rehearse:
        print(f"chip_smoke: jax found no TPU ({found})", file=sys.stderr)
        return 2

    from aiko_services_tpu.compute import enable_compile_cache
    # a rehearsal's CPU executables are no use to a chip run
    cache_dir = "off (rehearsal)" if args.rehearse \
        else enable_compile_cache()
    clock = CompileClock()
    say(f"device {found}, jax {jax.__version__}, compile cache "
        f"{cache_dir}")
    require(len(jax.devices()) >= args.chips,
            f"--chips {args.chips} but jax sees {found['count']}")
    from aiko_services_tpu.native import NATIVE_AVAILABLE
    say(f"NATIVE_AVAILABLE={NATIVE_AVAILABLE}")

    shape = shapes(args.rehearse)
    if args.chips == 4:
        phases = {"tensor_parallel": functools.partial(
            phase_tensor_parallel, clock=clock)}
    else:
        phases = {"kernels": phase_kernels, "speech": phase_speech,
                  "llama": functools.partial(phase_llama, clock=clock),
                  "latent": functools.partial(phase_latent, clock=clock),
                  "hybrid": functools.partial(phase_hybrid, clock=clock),
                  "sparse_gqa": functools.partial(phase_sparse_gqa,
                                                  clock=clock),
                  "gated_delta": functools.partial(phase_gated_delta,
                                                   clock=clock),
                  "ssm_hybrid": functools.partial(phase_ssm_hybrid,
                                                  clock=clock)}
    if args.only:
        require(set(args.only) <= set(phases),
                f"--only names {args.only}; the phases are {list(phases)}")
        phases = {name: phases[name] for name in args.only}
    for name, phase in phases.items():
        with Phase(name, clock):
            phase(shape, args.seed, on_chip)
    seconds, hits, misses = clock.snapshot()
    say(f"total compile={seconds:.1f}s cache_hits={hits} "
        f"cache_misses={misses} (a second run with the same cache "
        f"directory should show hits and less compile)")
    faulthandler.cancel_dump_traceback_later()
    if args.rehearse or args.only:
        print(json.dumps({"rehearsal": "passed", "device": found}))
    else:
        print(json.dumps({"ok": True, "device": found}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
