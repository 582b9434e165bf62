"""`step_kv_merge_ms` (PR 25): the sixth region of the decode step as a
per-layer metric, read like its five siblings by
`regions.step_region_ms`: from a hand-made trace with the merge spelled
both ways (copies of a pool leaf around the scatter, which the region
adopts, and the scatter alone), from runs with nothing to read, from the
chip recording of PR 24, and its entry in the manifest."""

import json
import os

import pytest

from benchmark import run
from benchmark.trace import regions as G

MS = 1e6        # ns
NAME = "step_kv_merge_ms"


def a_trace(copies: bool):
    """One chip, two rounds of `jit_step` of four steps each: an MLP, then
    the merge: its destinations (1 ms) and two pool leaves, each a scatter
    of 1 ms, with or without the compiler's scopeless copy before it (7 ms)
    and after it (9 ms), which the region of the operation before adopts."""
    scopes = [G.UNSCOPED, G.COMPILER, "aiko.mlp", "aiko.kv_merge"]
    ops, modules, at = [], [], 0
    for _ in range(2):
        start = at
        ops.append([at * MS, 40 * MS, 2, 0])
        ops.append([(at + 40) * MS, 1 * MS, 3, 0])
        at += 41
        for _leaf in range(2):
            for length, scope in ((7, 1), (1, 3), (9, 1)):
                if scope == 3 or copies:
                    ops.append([at * MS, length * MS, scope, 0])
                    at += length
        modules.append(["jit_step(11)", start * MS, (at - start) * MS])
        at += 5
    return {"scopes": scopes, "programs": ["jit_step(11)"],
            "devices": [{"name": "/device:TPU:0", "modules": modules,
                         "ops": ops}],
            "host": [["bench.traced", 0.0, at * MS]]}


def a_run(steps=8):
    return {"trace": {"devices": 1},
            "trace_counters": {"before": {"steps": 100},
                               "after": {"steps": 100 + steps}},
            "config": {"trace": {"programs": {"decode_step": ["jit_step"]}}}}


def read(name, of):
    return run.load_module("layer_metrics", name).read(of)


@pytest.mark.parametrize("copies, merge_ms, adopted_ms",
                         [(True, 2 * (1 + 2 * 17.0), 2 * 2 * 16.0),
                          (False, 2 * (1 + 2 * 1.0), 0.0)],
                         ids=["copied-leaves", "in-place"])
def test_reads_the_merge_region_of_a_synthetic_trace(
        monkeypatch, tmp_path, copies, merge_ms, adopted_ms):
    trace = a_trace(copies)
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    assert read(NAME, a_run()) == pytest.approx(merge_ms / 8)
    # the sibling beside it is what it was, and the two are the step
    assert read("step_mlp_ms", a_run()) == pytest.approx(80.0 / 8)
    with open(tmp_path / "program_spans.json") as f:
        noted = json.load(f)["decode_step_regions_ms"]
    assert noted["seconds"]["aiko.kv_merge"] == pytest.approx(merge_ms / 8)
    assert noted["adopted"].get("aiko.kv_merge", 0.0) == \
        pytest.approx(adopted_ms / 8)
    assert sum(noted["seconds"].values()) == pytest.approx(
        (80.0 + merge_ms) / 8)


def test_a_step_without_the_region_reads_zero(monkeypatch, tmp_path):
    trace = a_trace(False)
    trace["devices"][0]["ops"] = [op for op in trace["devices"][0]["ops"]
                                  if op[2] == 2]
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    assert read(NAME, a_run()) == 0.0


@pytest.mark.parametrize("of", [
    {"trace": None, "trace_counters": {}},
    {"trace": {"devices": 0}, "trace_counters": {}}],
    ids=["untraced", "on-the-cpu"])
def test_nothing_to_read_reads_none(of):
    assert read(NAME, of) is None


def test_a_trace_without_scopes_reads_none(monkeypatch, tmp_path):
    """The parent of PR 24 under the driver's overlay: device operations
    and no `jax.named_scope` in any of them."""
    bare = a_trace(True)
    for op in bare["devices"][0]["ops"]:
        op[2] = min(op[2], 1)
    monkeypatch.setattr(G, "of_run", lambda run: (bare, str(tmp_path)))
    assert read(NAME, a_run()) is None
    assert not os.path.exists(tmp_path / "program_spans.json")


def test_reads_the_chip_recording_of_pr_24(monkeypatch, tmp_path):
    """0.2 s of `decode_saturated` on a v5e with the merge as PR 24 had
    it: the region is a fifth of the step, nearly all of it adopted."""
    with open(os.path.join(run.ROOT, "benchmark", "tests", "data",
                           "decode_saturated_regions_v5e.json")) as f:
        trace = json.load(f)
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    merge = read(NAME, a_run(steps=8))
    others = sum(read(name, a_run(steps=8)) for name in (
        "step_kv_view_ms", "step_attn_proj_ms", "step_attn_core_ms",
        "step_mlp_ms", "step_head_ms"))
    assert 0.15 < merge / (merge + others) < 0.30
    found = G.region_seconds(trace, ["jit_step"])
    assert merge == pytest.approx(
        1e3 * found["seconds"]["aiko.kv_merge"] / 8)


def test_the_manifest_entry():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernel",
        "moves": "llm_tokens_per_s", "workloads": ["decode_saturated"]}
    siblings = [m for m in manifest["per_layer"]
                if m["name"].startswith("step_") and m is not entry]
    assert len(siblings) == 5
    for sibling in siblings:
        assert {k: v for k, v in sibling.items() if k != "name"} == \
            {k: v for k, v in entry.items() if k != "name"}
    assert os.path.exists(os.path.join(
        run.ROOT, "benchmark", "layer_metrics", NAME + ".py"))
