"""What PR 40 adds to the benchmark for `olmo-hybrid-7b-d16`: the
configuration file against the published keys, the operations and bytes
against hand counts, the plain reference's control and planted faults, each
new reader on a built trace or built counters (and on runs with nothing to
read), and the manifest's entries found BY NAME: no position, no "last", no
exact length of a list that later cells share."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import ops_bytes_gated_delta as ops
from benchmark import run
from benchmark.reference import gated_delta_lm
from benchmark.trace import regions as G

MS = 1e6        # ns
CONFIG = "olmo-hybrid-7b-d16"
CELL = "gdn_decode_saturated"
SOURCE = "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
LIN, FULL = "linear_attention", "full_attention"
# the catalog's entry for the source, key for key
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": [LIN, LIN, LIN, FULL] * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SUFFIXED = ["slot_occupancy", "decode_step_device_ms",
            "prefill_device_ms_per_ktok", "attend_width",
            "device_idle_share", "device_wait_on_host_ms", "round_host_ms",
            "round_longest_ms"]
REGIONS = {"gdn_step_proj_ms": "aiko.gdn_proj",
           "gdn_step_conv_ms": "aiko.gdn_conv",
           "gdn_step_state_ms": "aiko.gdn_state",
           "gdn_step_attn_core_ms": "aiko.attn_core",
           "gdn_step_mlp_ms": "aiko.mlp", "gdn_step_head_ms": "aiko.head"}
ROOFLINES = ["gdn_state_roofline", "gdn_scan_roofline",
             "gdn_decode_step_roofline"]
OWN = sorted(REGIONS) + ["gdn_extend_scan_ms", "gdn_state_moved_share"] + \
    ROOFLINES


@pytest.fixture(scope="module")
def sizes():
    return run.load_json("benchmark", "configs", CONFIG + ".json")


def test_the_file_carries_every_published_key_but_the_two_cut(sizes):
    reduced = {"num_hidden_layers": 16,
               "layer_types": [LIN, LIN, LIN, FULL] * 4}
    assert sorted(sizes["reduced"]) == sorted(reduced)
    for key, value in PUBLISHED.items():
        assert sizes[key] == reduced.get(key, value), key
    for key in reduced:
        assert key in sizes["reduced_why"]
    # whole periods: the cut ends on a full layer, three to one
    assert sizes["layer_types"].count(FULL) * 3 == \
        sizes["layer_types"].count(LIN)
    assert sizes["source"] == SOURCE
    deployment = sizes["deployment"]
    assert deployment["pipeline_stages"] == 2
    assert deployment["this_stage"] == 0
    assert deployment["layers_a_stage"] == 16
    for assumed in ("block_order", "qk_norm", "head_dim", "rope_theta_null",
                    "gated_delta_layer", "weights", "max_slots", "eos_token"):
        assert assumed in sizes["assumed"]
    assert "no bias" in sizes["assumed"]["gated_delta_layer"]
    assert "float32" in sizes["assumed"]["gated_delta_layer"]
    assert "NO rotary" in sizes["assumed"]["rope_theta_null"]
    assert "forced to length" in sizes["assumed"]["eos_token"]
    assert "pipeline stages" in sizes["stands_for"]
    serve = sizes["serving"]
    assert serve["max_slots"] == 64
    assert serve["max_seq"] == serve["t_block"] == 1024
    assert serve["prefill_chunk"] == serve["prefill_budget"] == 512
    assert serve["max_seq"] % serve["prefill_chunk"] == 0
    assert serve["kv_block"] == 32 and serve["prefill_buckets"] == [512]
    # a request ends and one is admitted every round: 64 rounds a request
    # for 64 slots, so the window meets no wave of admits (PERF.md §6)
    assert serve["steps_per_sync"] * serve["max_slots"] == 384
    assert "steps_per_sync" in sizes["assumed"]
    assert sizes["driver"] == "gated_delta_decoder"
    assert sizes["reference"] == "gated_delta_lm"
    assert sizes["trace"]["programs"] == {
        "decode_step": ["jit_step"], "prefill": ["jit_admit", "jit_extend"]}
    assert sizes["correctness"]["limits"] == {
        "served_token_gap_mean_std": 0.015, "served_token_gap_std": 0.3}
    # the rehearsal's heads: unequal sides, a count that is no multiple of 8
    small = sizes["rehearse"]
    assert small["linear_key_head_dim"] != small["linear_value_head_dim"]
    assert small["linear_num_value_heads"] % 8


def test_the_weights_pool_and_state_fill_what_the_file_says(sizes):
    """The arithmetic of the cut: 4.10 B parameters = 8.20 GB, a pool of
    64 x 1,024 positions at 15,360 B a full layer = 4.03 GB, slot state of
    2,211,840 + 69,120 B a recurrent layer = 1.75 GB: 13.98 GB of 15.75."""
    assert ops.gdn_params(sizes) == 88_750_332
    assert ops.full_params(sizes) == 58_990_080
    assert ops.layer_params(sizes, LIN) == 215_570_172
    assert ops.layer_params(sizes, FULL) == 185_809_920
    held = ops.params(sizes)
    assert held["embedding"] == 385_351_680
    assert round(held["total"] / 1e9, 3) == 4.101
    assert round(held["streamed"] * 2 / 1e9, 2) == 7.43
    serve = sizes["serving"]
    tokens = serve["max_slots"] * serve["max_seq"]
    assert ops.kv_bytes_per_token(sizes, 2) == 4 * 15_360
    pool = tokens * ops.kv_bytes_per_token(sizes, 2)
    assert round(pool / 1e9, 2) == 4.03
    assert ops.state_bytes(sizes) == 2_211_840          # nothing padded
    assert ops.tail_bytes(sizes, 2) == 69_120
    state = serve["max_slots"] * 12 * (2_211_840 + 69_120)
    assert round(state / 1e9, 2) == 1.75
    everything = held["total"] * 2 + pool + state
    assert round(everything / 1e9, 2) == 13.98
    assert 0.25 * 16.9e9 < everything < 15.75 * 2 ** 30


def test_a_state_that_is_not_whole_tiles_counts_its_padding(sizes):
    """`gdn_state_roofline` counts the state AS LAID OUT: 6 heads of
    [10, 16] lie in 16 rows of 128 lanes."""
    odd = sizes | {"linear_num_value_heads": 6, "linear_key_head_dim": 10,
                   "linear_value_head_dim": 16}
    assert ops.state_bytes(odd) == 16 * 128 * 4


def test_operations_and_bytes_against_hand_counts(sizes):
    # 700 slot-layer states a step: each 2,211,840 B in and out; a head
    # decays S, reads it twice and writes it once: 7 x 96 x 192
    state = ops.state_step(sizes, 700)
    assert state == {"bytes": 2 * 2_211_840 * 700,
                     "flops": 7 * 30 * 96 * 192 * 700}
    assert ops.roofline_seconds(state, PEAKS)["bound"] == "bytes"
    # 2,000 prompt tokens in 5 pieces through 12 layers: the state in and
    # out a piece, q, k (96), v, o (192) and the two gates a token
    scan = ops.scan(sizes, 2000, 5)
    assert scan == {
        "flops": 7 * 30 * 96 * 192 * 2000 * 12,
        "bytes": (2 * 2_211_840 * 5
                  + (2 * 96 + 2 * 192 + 2) * 30 * 4 * 2000) * 12}
    streamed = ops.params(sizes)["streamed"]
    assert streamed == 12 * 215_570_172 + 4 * 185_809_920 + 3840 \
        + 3840 * 100352
    whole = ops.decode_step(sizes, 2, 60, 30_000, 720)
    assert whole["bytes"] == streamed * 2 + 2 * 2_211_840 * 720 \
        + 2 * 69_120 * 720 + 61_440 * (30_000 + 60)
    assert whole["flops"] == 2 * streamed * 60 \
        + 7 * 30 * 96 * 192 * 720 + 4 * 3840 * 30_000 * 4
    assert ops.roofline_seconds(whole, PEAKS)["bound"] == "bytes"
    # the cell's step by bytes: 7.43 GB of weights, 3.40 GB of state, some
    # 2 GB of keys and values: 15.7 ms at 819 GB/s
    cell = ops.decode_step(sizes, 2, 64, 64 * 512, 64 * 12)
    assert 15.0e-3 < ops.roofline_seconds(cell, PEAKS)["seconds"] < 16.5e-3


def _small(sizes):
    small = run.merged(sizes, sizes["rehearse"]) | dict(
        vocab_size=2048, hidden_size=128, intermediate_size=256)
    small.pop("serving")
    return small


def test_the_float8_control_comes_out_as_not_correct(sizes):
    """The control at a size a test run can hold (PERF.md has the cell's
    own readings): the reference with float8 weights puts first, somewhere
    in some hundred positions, a token that lies further below the
    full-precision best than the configuration's limits allow."""
    limits = sizes["correctness"]["limits"]
    rng = np.random.default_rng(3)
    samples = [{"prompt": rng.integers(1, 2048, size=64).tolist(),
                "served": rng.integers(1, 2048, size=64).tolist()}
               for _ in range(2)]
    control = gated_delta_lm.check(samples, _small(sizes), 9, jnp.bfloat16,
                                   control=True)["control"]
    assert any(max(control[name]) > limit for name, limit in limits.items()), \
        control


def _greedy(params, config, prompt, count: int):
    """`count` tokens decoded greedily by the PROGRAM's full forward pass
    in float32 (no pool, no cache): what a sound or a faulty decoder
    would serve."""
    import jax
    from aiko_services_tpu.models.gated_delta import gated_delta_forward
    width = len(prompt) + count
    forward = jax.jit(lambda tokens: gated_delta_forward(
        params, config, tokens[None])[0])
    row = np.zeros((width,), np.int32)
    row[:len(prompt)] = prompt
    for at in range(len(prompt), width):
        row[at] = int(jnp.argmax(forward(jnp.asarray(row))[at - 1]))
    return row[len(prompt):].tolist()


@pytest.mark.parametrize("fault", ["sound", "beta-a-plain-sigmoid",
                                   "taps-turned-round", "a-rotary"])
def test_a_planted_fault_comes_out_as_not_correct(fault, sizes):
    """What only this configuration has, broken one thing at a time in what
    is SERVED (the program's forward pass in float32, so a sound run reads
    0.0) and held to the reference by the harness's own comparison and the
    configuration's limits, at a size a test holds: beta in (0, 1) where
    the model's reaches 2; the convolution's taps in the other order; the
    full layers' keys and queries rotated where `rope_theta` is null."""
    import dataclasses
    import sys
    import jax
    sys.path.insert(0, os.path.join(run.ROOT, "benchmark", "drivers"))
    from gated_delta_decoder import model_config
    from aiko_services_tpu.models import gated_delta as M
    from aiko_services_tpu.models import layers as L
    from benchmark import weights_gated_delta as W
    limits = sizes["correctness"]["limits"]
    small = _small(sizes)
    seed = 9
    params = W.decoder_weights(W.key_for(seed), small, jnp.float32)
    config = model_config(small, 256, jnp.float32)
    patched = pytest.MonkeyPatch()
    if fault == "beta-a-plain-sigmoid":
        config = dataclasses.replace(config, neg_eigval=False)
    if fault == "taps-turned-round":
        for layer in params["layers"]:
            if "gdn" in layer:
                layer["gdn"]["conv"]["w"] = layer["gdn"]["conv"]["w"][::-1]
    if fault == "a-rotary":
        cos, sin = L.rope_frequencies(config.head_dim, 256, 10000.0)
        project = M._full_project

        def rotated(layer, config, x):
            q, k, v = project(layer, config, x)
            return (L.apply_rope(q, cos, sin, 0), L.apply_rope(k, cos, sin, 0),
                    v)

        patched.setattr(M, "_full_project", rotated)
    rng = np.random.default_rng(5)
    samples = []
    try:
        for _ in range(2):
            prompt = rng.integers(1, 2048, size=96).tolist()
            samples.append({"prompt": prompt,
                            "served": _greedy(params, config, prompt, 96)})
    finally:
        patched.undo()
    found = gated_delta_lm.check(samples, small, seed, jnp.float32)["numbers"]
    over = any(max(found[name]) > limit for name, limit in limits.items())
    assert over == (fault != "sound"), (fault, found)


# -- the readers --------------------------------------------------------------

SCOPES = [G.UNSCOPED, G.COMPILER, "aiko.attn_proj", "aiko.attn_core",
          "aiko.mlp", "aiko.head", "aiko.kv_merge", "aiko.gdn_proj",
          "aiko.gdn_conv", "aiko.gdn_state", "aiko.gdn_scan"]
STEP_MS = {"aiko.gdn_proj": 9, "aiko.gdn_conv": 3, "aiko.gdn_state": 24,
           "aiko.attn_proj": 2, "aiko.attn_core": 12, "aiko.mlp": 20,
           "aiko.head": 4, "aiko.kv_merge": 2}


def a_trace():
    """One chip: two rounds of `jit_step` of four steps each, every region
    once a round, and between them one `jit_admit` with 30 ms under
    `aiko.gdn_scan` and 50 under `aiko.mlp`, then one `jit_extend` with 18
    under `aiko.gdn_scan`."""
    ops_, modules, at = [], [], 0
    for round_ in range(2):
        start = at
        for scope, ms in STEP_MS.items():
            ops_.append([at * MS, ms * MS, SCOPES.index(scope), 0])
            at += ms
        modules.append(["jit_step(7)", start * MS, (at - start) * MS])
        at += 3
        if round_ == 0:
            for program, index, parts in (
                    ("jit_admit(8)", 1, (("aiko.gdn_scan", 30),
                                         ("aiko.mlp", 50))),
                    ("jit_extend(9)", 2, (("aiko.gdn_scan", 18),))):
                start = at
                for scope, ms in parts:
                    ops_.append([at * MS, ms * MS, SCOPES.index(scope),
                                 index])
                    at += ms
                modules.append([program, start * MS, (at - start) * MS])
                at += 3
    return {"scopes": SCOPES,
            "programs": ["jit_step(7)", "jit_admit(8)", "jit_extend(9)"],
            "devices": [{"name": "/device:TPU:0", "modules": modules,
                         "ops": ops_}],
            "host": [["bench.traced", 0.0, at * MS]]}


def a_run(sizes, traced=True):
    """8 steps, 2 requests admitted whole and 1 chunk in the traced span;
    counters as the driver hands them out."""
    before = {"steps": 100, "useful_steps": 6000, "tokens_decode": 6000,
              "prefills": 10, "prefill_chunks": 0, "tokens_prefill": 3000,
              "gdn_states_moved": 70_000, "gdn_states_held": 76_800,
              "max_slots": 64}
    after = {"steps": 108, "useful_steps": 6480, "tokens_decode": 6480,
             "prefills": 12, "prefill_chunks": 1, "tokens_prefill": 4200,
             "gdn_states_moved": 75_760, "gdn_states_held": 82_944,
             "max_slots": 64}
    return {"trace": {"devices": 1, "window_s": 2.0,
                      "programs": {"jit_step": {"seconds": 0.152},
                                   "jit_admit": {"seconds": 0.08},
                                   "jit_extend": {"seconds": 0.018}}}
            if traced else None,
            "trace_counters": {"before": before, "after": after}
            if traced else {},
            "counters": {"before": before, "after": after},
            "all_records": {}, "requests": {},
            "seconds": 4.0, "config": sizes, "peaks": PEAKS}


def read(name, of):
    return run.load_module("layer_metrics", name).read(of)


def test_region_readers_split_the_step(monkeypatch, tmp_path, sizes):
    trace = a_trace()
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    for name, scope in REGIONS.items():
        assert read(name, a_run(sizes)) == \
            pytest.approx(2 * STEP_MS[scope] / 8), name
    # the admit's and the extend's scans, a piece: 2 admitted + 1 chunk
    assert read("gdn_extend_scan_ms", a_run(sizes)) == \
        pytest.approx((30.0 + 18.0) / 3)
    with open(tmp_path / "program_spans.json") as f:
        noted = json.load(f)["decode_step_regions_ms"]["seconds"]
    assert noted["aiko.gdn_state"] == pytest.approx(2 * 24 / 8)


def test_counter_readers(sizes):
    of = a_run(sizes)
    assert read("gdn_state_moved_share", of) == \
        pytest.approx(100 * 5760 / 6144)
    assert read("slot_occupancy.gdn", of) == \
        pytest.approx(100 * 480 / (8 * 64))
    assert read("prefill_device_ms_per_ktok.gdn", of) == \
        pytest.approx(1e3 * 0.098 / 1.2)
    assert read("decode_step_device_ms.gdn", of) == pytest.approx(19.0)


def test_roofline_readers_stay_under_the_peaks(monkeypatch, tmp_path, sizes):
    trace = a_trace()
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    of = a_run(sizes)
    # 720 states a step, over 6 ms a step under aiko.gdn_state
    state = ops.state_step(sizes, 720)
    assert read("gdn_state_roofline", of) == pytest.approx(
        100 * state["bytes"] / 819e9 / 6e-3)
    # 1,200 prompt tokens in 3 pieces, over 48 ms under aiko.gdn_scan
    scan = ops.scan(sizes, 1200, 3)
    assert read("gdn_scan_roofline", of) == pytest.approx(
        100 * max(scan["bytes"] / 819e9, scan["flops"] / 197e12) / 48e-3)
    # no request held anything by the benchmark's stamps: 60 slots decode
    step = ops.decode_step(sizes, 2, 60, 0, 720)
    assert read("gdn_decode_step_roofline", of) == pytest.approx(
        100 * step["bytes"] / 819e9 / 19e-3)
    for name in ROOFLINES:
        assert 0 < read(name, of) < 100


@pytest.mark.parametrize("name", OWN + ["prefill_device_ms_per_ktok.gdn",
                                        "decode_step_device_ms.gdn",
                                        "slot_occupancy.gdn"])
def test_nothing_to_read_reads_none(name, sizes):
    assert read(name, a_run(sizes, traced=False)) is None


@pytest.mark.parametrize("name", OWN)
def test_a_program_without_the_counters_reads_none(
        name, monkeypatch, tmp_path, sizes):
    """Another program under this benchmark (the parent's overlay, or
    another configuration's driver): no counter of the recurrence and no
    operation under the new scopes.  The new readers return nothing and do
    not raise."""
    trace = a_trace()
    trace["devices"][0]["ops"] = [
        op for op in trace["devices"][0]["ops"]
        if not SCOPES[op[2]].startswith("aiko.gdn_")]
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    of = a_run(sizes)
    for group in ("counters", "trace_counters"):
        for span in of[group].values():
            for key in [k for k in span if k.startswith("gdn_")]:
                del span[key]
    assert read(name, of) is None


# -- the manifest, by name ------------------------------------------------------

def test_the_manifest_entries(sizes):
    manifest = run.load_json("BENCHMARK.json")
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == CELL and len(cell["why"]) <= 200
    assert "all 64 slots live" in cell["why"]
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(sizes["reduced"])
    assert len(entry["why"]) <= 200
    rate = next(m for m in manifest["end_to_end"]
                if m["name"] == "llm_tokens_per_s")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    assert rate["workloads"][0] == "decode_saturated"
    per_layer = manifest["per_layer"]
    assert len({m["name"] for m in per_layer}) == len(per_layer)
    mine = {m["name"]: m for m in per_layer if m.get("workloads") == [CELL]}
    assert sorted(mine) == sorted([name + ".gdn" for name in SUFFIXED] + OWN)
    for name, m in mine.items():
        assert m["moves"] == "llm_tokens_per_s"
        assert not name.startswith("step_")
        if "roofline" in name:
            assert m["unit"] == "%" and m["better"] == "higher"
            assert m["source"] == "device_trace" and m["layer"] == "kernel"
        assert callable(run.load_module("layer_metrics", name).read)
    for name in OWN + ["slot_occupancy.gdn", "prefill_device_ms_per_ktok.gdn"]:
        assert os.path.exists(os.path.join(
            run.ROOT, "benchmark", "layer_metrics",
            name.replace(".", "_") + ".py"))
    # the cell reports its own metrics and no metric of another cell's
    assert {m["name"] for m in run.resolve(CELL, False)["per_layer"]} == \
        set(mine)
    assert {m["name"] for m in run.resolve(CELL, False)["end_to_end"]} == \
        {"llm_tokens_per_s", "setup_s"}
    traffic = run.load_json("benchmark", "traffic", CELL + ".json")
    assert traffic["generator"] == "backlog"
    parameters = traffic["parameters"]
    assert parameters["max_outstanding"] == 256 == \
        4 * sizes["serving"]["max_slots"]
    assert parameters["supply_per_s"] == 16 and parameters["preroll_s"] == 12
    fields = parameters["fields"]
    assert fields["prompt_tokens"] == {"dist": "uniform", "min": 128,
                                       "max": 512}
    assert fields["output_tokens"] == {"dist": "fixed", "value": 384}
    assert fields["output_tokens"]["value"] == \
        sizes["serving"]["max_slots"] * sizes["serving"]["steps_per_sync"]
    # a context never passes 896 of the window, and every prompt is
    # admitted whole in a bucket
    assert fields["prompt_tokens"]["max"] + fields["output_tokens"]["value"] \
        <= sizes["serving"]["max_seq"]
    assert fields["prompt_tokens"]["max"] <= \
        sizes["serving"]["prefill_buckets"][-1]
    assert traffic["reference_samples"] == 25


def test_the_saturated_cell_before_this_one_keeps_its_entries():
    """What the older cell that shares `llm_tokens_per_s` had, it has: the
    list BEGINS with it and its own metrics are the ones it reported."""
    manifest = run.load_json("BENCHMARK.json")
    cells = {c["name"]: c for c in manifest["workloads"]}
    assert cells["decode_saturated"]["config"] == "mistral-7b-v0.3-d16"
    own = [m for m in manifest["per_layer"]
           if m.get("workloads") == ["decode_saturated"]]
    assert len(own) == 14
    assert {m["name"] for m in own if m["name"].startswith("step_")} == {
        "step_kv_view_ms", "step_attn_proj_ms", "step_attn_core_ms",
        "step_mlp_ms", "step_head_ms", "step_kv_merge_ms"}
    assert {m["name"] for m in
            run.resolve("decode_saturated", False)["per_layer"]} == \
        {m["name"] for m in own}
