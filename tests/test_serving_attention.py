# Continuous-batching decode engine tests (serving.py), continued from
# tests/test_serving.py: which attention a paged decoder takes from what
# it observes, deadline admission, and how rows reach the pool.

import dataclasses

import jax
import pytest

from aiko_services_tpu.models.llama import LLAMA_PRESETS, llama_init
from aiko_services_tpu.serving import ContinuousDecoder
from test_serving import CONFIG, _run_decoder, params  # noqa: F401 (a fixture)


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
