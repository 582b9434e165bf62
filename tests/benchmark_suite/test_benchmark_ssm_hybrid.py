"""What PR 45 adds to the benchmark for `granite-4.0-h-micro`: the
configuration file against the published keys (nothing cut), the operations
and bytes against hand counts, the plain reference's control and planted
faults, each new reader on a built trace or built counters (and on runs with
nothing to read), and the manifest's entries found BY NAME: no position, no
"last", no exact length of a list that later cells share."""

import functools
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import ops_bytes_ssm_hybrid as ops
from benchmark import run
from benchmark.reference import ssm_hybrid_lm
from benchmark.trace import regions as G

MS = 1e6        # ns
CONFIG = "granite-4.0-h-micro"
CELL = "ssm_chat_open_loop"
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
          "config.json")
MAMBA, ATTN = "mamba", "attention"
# the catalog's entry for the source, key for key
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": ([MAMBA] * 5 + [ATTN] + [MAMBA] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SUFFIXED = ["decode_step_device_ms", "prefill_device_ms_per_ktok",
            "attend_width", "device_idle_share", "device_wait_on_host_ms",
            "round_host_ms", "round_longest_ms", "rounds_with_prefill",
            "prefill_round_penalty_ms", "tpot_mean_ms", "tpot_p95_ms"]
SHARED = ["tpot_mid_ms", "tpot_mid_host_ms", "tpot_mid_sync_clean_ms",
          "tpot_mid_sync_behind_ms", "tpot_mid_rounds_behind",
          "prefill_prefix_depth"]
REGIONS = {"ssm_step_proj_ms": "aiko.ssm_proj",
           "ssm_step_conv_ms": "aiko.ssm_conv",
           "ssm_step_state_ms": "aiko.ssm_state",
           "ssm_step_attn_core_ms": "aiko.attn_core",
           "ssm_step_mlp_ms": "aiko.mlp", "ssm_step_head_ms": "aiko.head"}
ROOFLINES = ["ssm_state_roofline", "ssm_scan_roofline",
             "ssmchat_decode_step_roofline"]
OWN = sorted(REGIONS) + ["ssm_extend_scan_ms", "ssm_state_moved_share"] + \
    ROOFLINES
LISTED = ["decode_step_device_ms.ssmchat", "ssm_state_roofline"]


@pytest.fixture(scope="module")
def sizes():
    return run.load_json("benchmark", "configs", CONFIG + ".json")


def test_the_file_carries_every_published_key_and_cuts_none(sizes):
    assert sizes["reduced"] == []
    for key, value in PUBLISHED.items():
        assert sizes[key] == value, key
    assert [i for i, kind in enumerate(sizes["layer_types"])
            if kind == ATTN] == [5, 15, 25, 35]
    assert "6.38 GB" in sizes["reduced_why"]
    assert "nothing is cut" in sizes["reduced_why"]
    assert sizes["source"] == SOURCE
    assert "ONE chip: no pipeline stage, no share of a layer" in \
        sizes["stands_for"]
    for assumed in ("head_dim", "mamba_chunk_size", "weights", "embedding",
                    "eos_token", "max_slots", "pool_row"):
        assert assumed in sizes["assumed"]
    assert "forced to length" in sizes["assumed"]["eos_token"]
    assert "TIED" in sizes["assumed"]["embedding"]
    serve = sizes["serving"]
    assert serve["max_slots"] % 8 == 0
    assert serve["max_seq"] == serve["t_block"] == 2048
    assert serve["prefill_chunk"] == serve["prefill_budget"] == 512
    assert serve["max_seq"] % serve["prefill_chunk"] == 0
    assert serve["kv_block"] == 32
    assert serve["prefill_buckets"] == [128, 256, 512]
    assert serve["steps_per_sync"] == 4
    assert sizes["driver"] == "ssm_hybrid_decoder"
    assert sizes["reference"] == "ssm_hybrid_lm"
    assert sizes["trace"]["programs"] == {
        "decode_step": ["jit_step"], "prefill": ["jit_admit", "jit_extend"]}
    assert sizes["correctness"]["limits"] == {
        "served_token_gap_mean_std": 0.03, "served_token_gap_std": 0.5}
    assert sorted(sizes["correctness"]["limits"]) == [
        "served_token_gap_mean_std", "served_token_gap_std"]
    # the rehearsal's preset: a pattern that is not periodic, heads that
    # are no multiple of 8, a K/V group of 4
    small = sizes["rehearse"]
    assert small["mamba_n_heads"] % 8
    assert small["num_attention_heads"] // small["num_key_value_heads"] == 4
    kinds = small["layer_types"]
    assert all(kinds != (kinds[:p] * 8)[:len(kinds)] for p in (1, 2, 3, 4))


def test_the_weights_pool_and_state_fill_what_the_file_says(sizes):
    """The arithmetic of ISSUE 45: 3.191 B parameters = 6.38 GB, slot state
    of 2,097,152 + 26,112 B a Mamba layer = 76.44 MB a slot, a pool of
    8,192 B a token: 9.4 GB at 32 slots, 59% of 15.75."""
    assert ops.mamba_params(sizes) == 25_847_232
    assert ops.attention_params(sizes) == 10_485_760
    assert ops.layer_params(sizes, MAMBA) == 76_182_976
    assert ops.layer_params(sizes, ATTN) == 60_821_504
    held = ops.params(sizes)
    assert held["total"] == held["streamed"] == \
        36 * 76_182_976 + 4 * 60_821_504 + 205_520_896 + 2048
    assert round(held["total"] / 1e9, 3) == 3.191
    assert round(held["total"] * 2 / 1e9, 2) == 6.38
    serve = sizes["serving"]
    assert ops.kv_bytes_per_token(sizes, 2) == 8_192
    pool = serve["max_slots"] * serve["max_seq"] * 8_192
    assert ops.state_bytes(sizes) == 2_097_152          # nothing padded
    assert ops.tail_bytes(sizes, 2) == 26_112
    a_slot = 36 * (2_097_152 + 26_112)
    assert round(a_slot / 1e6, 2) == 76.44
    everything = held["total"] * 2 + pool + serve["max_slots"] * a_slot
    if serve["max_slots"] == 32:
        assert round(pool / 1e9, 2) == 0.54
        assert round(everything / 1e9, 1) == 9.4
    assert 0.25 * 16.9e9 < everything < 15.75 * 2 ** 30


def test_a_state_that_is_not_whole_tiles_counts_its_padding(sizes):
    odd = sizes | {"mamba_n_heads": 6, "mamba_d_head": 16,
                   "mamba_d_state": 10}
    assert ops.state_bytes(odd) == 16 * 128 * 4


def test_operations_and_bytes_against_hand_counts(sizes):
    # 700 slot-layer states a step: each 2,097,152 B in and out; a head
    # decays S, adds the write and reads it: 5 x 128 x 64
    state = ops.state_step(sizes, 700)
    assert state == {"bytes": 2 * 2_097_152 * 700,
                     "flops": 5 * 64 * 64 * 128 * 700}
    assert ops.roofline_seconds(state, PEAKS)["bound"] == "bytes"


def _small(sizes):
    small = run.merged(sizes, sizes["rehearse"]) | dict(
        vocab_size=2048, hidden_size=128, shared_intermediate_size=256,
        mamba_expand=0.375)
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
    control = ssm_hybrid_lm.check(samples, _small(sizes), 9, jnp.bfloat16,
                                  control=True)["control"]
    assert any(max(control[name]) > limit for name, limit in limits.items()), \
        control


@functools.cache
def _forward(patched: bool):
    """The program's full forward pass as ONE program for every case that
    changes weights alone; a case that patches the module traces anew."""
    import jax
    from aiko_services_tpu.models.ssm_hybrid import ssm_hybrid_forward
    return jax.jit(lambda params, config, tokens: ssm_hybrid_forward(
        params, config, tokens), static_argnums=1)


def _greedy(params, config, prompt, count: int, patched: bool):
    """`count` tokens decoded greedily by the PROGRAM's full forward pass
    in float32 (no pool, no cache): what a sound or a faulty decoder
    would serve."""
    width = len(prompt) + count
    row = np.zeros((width,), np.int32)
    row[:len(prompt)] = prompt
    for at in range(len(prompt), width):
        logits = _forward(patched)(params, config, jnp.asarray(row)[None])
        row[at] = int(jnp.argmax(logits[0, at - 1]))
    return row[len(prompt):].tolist()


@pytest.mark.parametrize("fault", ["sound", "norm-before-the-gate",
                                   "taps-turned-round", "no-bias",
                                   "softmax-at-root-d", "no-skip"])
def test_a_planted_fault_comes_out_as_not_correct(fault, sizes):
    """What only this configuration has, broken one thing at a time in what
    is SERVED (the program's forward pass in float32, so a sound run reads
    0.0) and held to the reference by the harness's own comparison and the
    configuration's limits, at a size a test holds: the norm taken BEFORE
    the gate; the convolution's taps in the other order; its bias dropped;
    the softmax scaled at D^-0.5 where `attention_multiplier` is 1 / D; the
    skip D x left out."""
    import dataclasses
    import sys
    import jax
    sys.path.insert(0, os.path.join(run.ROOT, "benchmark", "drivers"))
    from ssm_hybrid_decoder import model_config
    from aiko_services_tpu.models import ssm_hybrid as M
    from benchmark import weights_ssm_hybrid as W
    limits = sizes["correctness"]["limits"]
    small = _small(sizes)
    seed = 9
    params = W.decoder_weights(W.key_for(seed), small, jnp.float32)
    config = model_config(small, 256, jnp.float32)
    patched = pytest.MonkeyPatch()
    mambas = [layer["mamba"] for layer in params["layers"]
              if "mamba" in layer]
    if fault == "softmax-at-root-d":
        config = dataclasses.replace(
            config, attention_multiplier=config.head_dim ** -0.5)
    if fault == "taps-turned-round":
        for mamba in mambas:
            mamba["conv"]["w"] = mamba["conv"]["w"][::-1]
    if fault == "no-bias":
        for mamba in mambas:
            mamba["conv"]["b"] = jnp.zeros_like(mamba["conv"]["b"])
    if fault == "no-skip":
        for mamba in mambas:
            mamba["d"] = jnp.zeros_like(mamba["d"])
    if fault == "norm-before-the-gate":
        def swapped(mamba, config, out, inputs, gate, dtype):
            rows, t = out.shape[:2]
            out = (out + mamba["d"][:, None] * inputs).reshape(rows, t, -1)
            normed = out * jax.lax.rsqrt(jnp.mean(
                out * out, axis=-1, keepdims=True) + config.norm_eps) * \
                mamba["norm"]["scale"]
            return M.L.linear(mamba["out"],
                              (normed * jax.nn.silu(gate)).astype(dtype))

        patched.setattr(M, "_mamba_output", swapped)
    rng = np.random.default_rng(5)
    samples = []
    try:
        for _ in range(2):
            prompt = rng.integers(1, 2048, size=64).tolist()
            samples.append({"prompt": prompt, "served": _greedy(
                params, config, prompt, 64,
                fault == "norm-before-the-gate")})
    finally:
        patched.undo()
    found = ssm_hybrid_lm.check(samples, small, seed, jnp.float32)["numbers"]
    over = any(max(found[name]) > limit for name, limit in limits.items())
    assert over == (fault != "sound"), (fault, found)


# -- the readers --------------------------------------------------------------

SCOPES = [G.UNSCOPED, G.COMPILER, "aiko.attn_proj", "aiko.attn_core",
          "aiko.mlp", "aiko.head", "aiko.kv_merge", "aiko.ssm_proj",
          "aiko.ssm_conv", "aiko.ssm_state", "aiko.ssm_scan"]
STEP_MS = {"aiko.ssm_proj": 9, "aiko.ssm_conv": 3, "aiko.ssm_state": 24,
           "aiko.attn_proj": 2, "aiko.attn_core": 12, "aiko.mlp": 20,
           "aiko.head": 4, "aiko.kv_merge": 2}


def a_trace():
    """One chip: two rounds of `jit_step` of four steps each, every region
    once a round, and between them one `jit_admit` with 30 ms under
    `aiko.ssm_scan` and 50 under `aiko.mlp`, then one `jit_extend` with 18
    under `aiko.ssm_scan`."""
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
                    ("jit_admit(8)", 1, (("aiko.ssm_scan", 30),
                                         ("aiko.mlp", 50))),
                    ("jit_extend(9)", 2, (("aiko.ssm_scan", 18),))):
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
    counters as the driver hands them out: 20 of 32 slots decode a step."""
    before = {"steps": 100, "useful_steps": 2000, "tokens_decode": 2000,
              "prefills": 10, "prefill_chunks": 0, "tokens_prefill": 3000,
              "ssm_states_moved": 72_000, "ssm_states_held": 115_200,
              "max_slots": 32}
    after = {"steps": 108, "useful_steps": 2160, "tokens_decode": 2160,
             "prefills": 12, "prefill_chunks": 1, "tokens_prefill": 4200,
             "ssm_states_moved": 77_760, "ssm_states_held": 124_416,
             "max_slots": 32}
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


def test_the_state_roofline_stays_under_the_peak(monkeypatch, tmp_path,
                                                 sizes):
    trace = a_trace()
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    of = a_run(sizes)
    # 720 states a step, over 6 ms a step under aiko.ssm_state
    state = ops.state_step(sizes, 720)
    assert state["bytes"] == 720 * 2 * 2_097_152
    assert read("ssm_state_roofline", of) == pytest.approx(
        100 * state["bytes"] / 819e9 / 6e-3)
    assert 0 < read("ssm_state_roofline", of) < 100
    assert read("decode_step_device_ms.ssmchat", of) == pytest.approx(19.0)
    with open(tmp_path / "program_spans.json") as f:
        noted = json.load(f)["decode_step_regions_ms"]["seconds"]
    assert noted["aiko.ssm_state"] == pytest.approx(2 * 24 / 8)


@pytest.mark.parametrize("name", LISTED)
def test_nothing_to_read_reads_none(name, sizes):
    assert read(name, a_run(sizes, traced=False)) is None


@pytest.mark.parametrize("name", ["ssm_state_roofline"])
def test_a_program_without_the_counters_reads_none(
        name, monkeypatch, tmp_path, sizes):
    """Another program under this benchmark (the parent's overlay, or
    another configuration's driver): no counter of the recurrence and no
    operation under the new scopes.  The new readers return nothing and do
    not raise."""
    trace = a_trace()
    trace["devices"][0]["ops"] = [
        op for op in trace["devices"][0]["ops"]
        if not SCOPES[op[2]].startswith("aiko.ssm_")]
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    of = a_run(sizes)
    for group in ("counters", "trace_counters"):
        for span in of[group].values():
            for key in [k for k in span if k.startswith("ssm_")]:
                del span[key]
    assert read(name, of) is None


# -- the manifest, by name ------------------------------------------------------

def test_the_manifest_entries(sizes):
    manifest = run.load_json("BENCHMARK.json")
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == CELL and len(cell["why"]) <= 200
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE and entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    gap = next(m for m in manifest["end_to_end"]
               if m["name"] == "llm_tpot_p50_ms")
    assert CELL in gap["workloads"] and gap["bound"] == 0.045
    assert gap["workloads"][0] == "chat_open_loop"
    per_layer = manifest["per_layer"]
    assert len({m["name"] for m in per_layer}) == len(per_layer)
    by_name = {m["name"]: m for m in per_layer}
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["workloads"][0] == "chat_open_loop"
    mine = {m["name"]: m for m in per_layer if m.get("workloads") == [CELL]}
    # the manifest holds at most 128 per-layer metrics (the driver's check
    # refuses a longer list before any run) and had 126: TWO of the
    # twenty-two that ISSUE 45 names are entered (LISTED); the others wait
    # for the `benchmark` issue that makes room
    assert len(per_layer) <= 128
    assert sorted(mine) == LISTED
    assert set(mine) <= set([name + ".ssmchat" for name in SUFFIXED] + OWN)
    for name, m in mine.items():
        assert m["moves"] == "llm_tpot_p50_ms"
        assert not name.startswith("step_")
        if "roofline" in name:
            assert m["unit"] == "%" and m["better"] == "higher"
            assert m["source"] == "device_trace" and m["layer"] == "kernel"
        assert callable(run.load_module("layer_metrics", name).read)
    for name in SUFFIXED:
        if name + ".ssmchat" not in mine:
            continue
        # a suffixed entry is its `.chat` sibling's, but for name and cell
        sibling = by_name[name + ".chat"]
        assert {k: v for k, v in mine[name + ".ssmchat"].items()
                if k not in ("name", "workloads")} == \
            {k: v for k, v in sibling.items()
             if k not in ("name", "workloads")}
    assert os.path.exists(os.path.join(
        run.ROOT, "benchmark", "layer_metrics", "ssm_state_roofline.py"))
    # the cell reports its own metrics, those every open-loop cell shares,
    # and no metric of another cell's
    assert {m["name"] for m in run.resolve(CELL, False)["per_layer"]} == \
        set(mine) | set(SHARED)
    assert {m["name"] for m in run.resolve(CELL, False)["end_to_end"]} == \
        {"llm_tpot_p50_ms", "setup_s"}
    traffic = run.load_json("benchmark", "traffic", CELL + ".json")
    assert traffic["generator"] == "poisson_requests"
    parameters = traffic["parameters"]
    assert parameters["preroll_s"] == 8 and parameters["order_draw"] == 0
    assert f"{parameters['rate_per_s']:g} requests/s" in cell["why"]
    chat = run.load_json("benchmark", "traffic", "chat_open_loop.json")
    assert parameters["fields"] == chat["parameters"]["fields"]
    fields = parameters["fields"]
    assert fields["prompt_tokens"]["max"] + fields["output_tokens"]["max"] \
        <= sizes["serving"]["max_seq"]
    # prompts past a bucket go chunk by chunk over slot state
    assert fields["prompt_tokens"]["max"] > \
        sizes["serving"]["prefill_buckets"][-1]
    assert traffic["drain_s"] == 14 and traffic["reference_samples"] == 6
    assert traffic["promised_ms"] == {"first": [2000], "per_token": [100]}
    # some two hundred requests a window (PERF.md section 7, ROADMAP W3)
    assert parameters["rate_per_s"] * manifest["run_seconds"] >= 200
