"""What PR 31 adds to the benchmark for `ax-k1-ep16-d6`: the configuration
file against the published keys, the plain reference's control, the
operations and bytes, and each new reader on a built trace or built
counters (and on runs with nothing to read)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import ops_bytes_latent_moe as ops
from benchmark import run
from benchmark.reference import latent_moe_lm
from benchmark.trace import regions as G

MS = 1e6        # ns
CONFIG = "ax-k1-ep16-d6"
CELL = "doc_qa_open_loop"
# the catalog's entry for the source, number for number
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 8, "n_routed_experts": 192, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 64, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
    "v_head_dim": 128, "vocab_size": 163840}


@pytest.fixture(scope="module")
def sizes():
    return run.load_json("benchmark", "configs", CONFIG + ".json")


def test_the_file_carries_every_published_key_but_the_three_cut(sizes):
    reduced = {"num_hidden_layers": 6, "n_routed_experts": 12,
               "vocab_size": 20480}
    assert sorted(sizes["reduced"]) == sorted(reduced)
    for key, value in PUBLISHED.items():
        assert sizes[key] == reduced.get(key, value), key
        if key in reduced:
            assert sizes["published"][key] == value
            assert key in sizes["reduced_why"]
    assert sizes["deployment"]["expert_parallel"] == 16
    assert sizes["deployment"]["vocab_parallel"] == 8
    for assumed in ("topk_method", "weights", "rotary", "eos_token",
                    "pool_layout"):
        assert assumed in sizes["assumed"]
    # the floors of a model_config cut: a whole period and four sparse
    # layers, eight experts, an eighth of the vocabulary
    assert sizes["num_hidden_layers"] - sizes["first_k_dense_replace"] >= 4
    assert sizes["n_routed_experts"] >= 8
    assert sizes["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert sizes["serving"] == {
        "max_slots": 32, "max_seq": 8192, "t_block": 8192, "kv_block": 32,
        "prefill_buckets": [256, 512], "prefill_chunk": 512,
        "prefill_budget": 512, "steps_per_sync": 4}


def test_the_parameter_count_reproduces_the_published_size(sizes):
    """My own count from the keys: 518.98 B whole, 4.166 B held here."""
    whole = sizes | sizes["published"]
    attention = ops.attention_params(whole) - (1536 + 512 + 2 * 7168)
    assert attention == 101_122_048
    assert ops.expert_params(whole) == 44_040_192
    sparse = attention + 193 * 44_040_192 + 7168 * 192
    dense = attention + 3 * 7168 * 18432
    total = 60 * sparse + dense + 2 * 163840 * 7168
    assert round(total / 1e9, 2) == 518.98
    held = ops.params(sizes)
    assert round(held["total"] / 1e9, 3) == 4.166
    # what a step always streams: 2.75 GB in bfloat16
    assert round(held["always_streamed"] * 2 / 1e9, 2) == 2.75
    assert ops.row_values(sizes) * 2 == 1152


def test_operations_and_bytes_of_the_new_kernels(sizes):
    walk = ops.latent_attention(sizes, 2, 1000.0)
    assert walk["bytes"] == 1152 * 1000 * 6
    assert walk["flops"] == 2 * 64 * (576 + 512) * 1000 * 6
    # 121 operations a byte of cache: half the v5e's ridge of 240
    assert round(walk["flops"] / walk["bytes"]) == 121
    experts = ops.routed_experts(sizes, 2, experts_hit=20, pairs_here=33)
    assert experts["bytes"] == 20 * 44_040_192 * 2
    assert experts["flops"] == 33 * 2 * 44_040_192
    step = ops.decode_step(sizes, 2, 10, 1000.0, 20, 33)
    assert step["bytes"] == ops.always_streamed_params(sizes) * 2 \
        + walk["bytes"] + experts["bytes"] + 10 * 1152 * 6
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert ops.roofline_seconds(step, peaks)["bound"] == "bytes"


def test_the_float8_control_comes_out_as_not_correct(sizes):
    """The control at a size a test run can hold (PERF.md has the cell's
    own readings): the reference with float8 weights puts first, somewhere
    in a few hundred positions, a token that lies further below the
    full-precision best than the configuration's limit allows."""
    limits = sizes["correctness"]["limits"]
    small = sizes | sizes["rehearse"] | dict(
        vocab_size=2048, hidden_size=128, intermediate_size=256,
        moe_intermediate_size=64, num_hidden_layers=4,
        rope_scaling=sizes["rope_scaling"] | sizes["rehearse"]["rope_scaling"])
    rng = np.random.default_rng(3)
    samples = [{"prompt": rng.integers(1, 2048, size=96).tolist(),
                "served": rng.integers(1, 2048, size=96).tolist()}
               for _ in range(2)]
    control = latent_moe_lm.check(samples, small, 9, jnp.bfloat16,
                                  control=True)["control"]
    assert any(max(control[name]) > limit for name, limit in limits.items()), \
        control


# -- the readers --------------------------------------------------------------

SCOPES = [G.UNSCOPED, G.COMPILER, "aiko.attn_proj", "aiko.attn_core",
          "aiko.moe_route", "aiko.moe_shared", "aiko.moe_experts", "aiko.mlp",
          "aiko.head", "aiko.kv_merge", "aiko.mla_expand"]
STEP_MS = {"aiko.attn_proj": 12, "aiko.attn_core": 4, "aiko.moe_route": 2,
           "aiko.moe_shared": 6, "aiko.moe_experts": 10, "aiko.mlp": 3,
           "aiko.head": 1, "aiko.kv_merge": 2}
READERS = {"mla_step_attn_proj_ms": "aiko.attn_proj",
           "mla_step_attn_core_ms": "aiko.attn_core",
           "moe_step_route_ms": "aiko.moe_route",
           "moe_step_shared_ms": "aiko.moe_shared",
           "moe_step_experts_ms": "aiko.moe_experts",
           "mla_step_dense_mlp_ms": "aiko.mlp",
           "mla_step_head_ms": "aiko.head",
           "mla_step_kv_merge_ms": "aiko.kv_merge"}


def a_trace():
    """One chip: two rounds of `jit_step` of four steps each, every region
    once a round, and between them one `jit_extend` with 30 ms under
    `aiko.mla_expand` and 50 under `aiko.attn_core`."""
    ops_, modules, at = [], [], 0
    for round_ in range(2):
        start = at
        for scope, ms in STEP_MS.items():
            ops_.append([at * MS, ms * MS, SCOPES.index(scope), 0])
            at += ms
        modules.append(["jit_step(7)", start * MS, (at - start) * MS])
        at += 3
        if round_ == 0:
            start = at
            for scope, ms in (("aiko.mla_expand", 30), ("aiko.attn_core", 50)):
                ops_.append([at * MS, ms * MS, SCOPES.index(scope), 1])
                at += ms
            modules.append(["jit_extend(9)", start * MS, (at - start) * MS])
            at += 3
    return {"scopes": SCOPES, "programs": ["jit_step(7)", "jit_extend(9)"],
            "devices": [{"name": "/device:TPU:0", "modules": modules,
                         "ops": ops_}],
            "host": [["bench.traced", 0.0, at * MS]]}


def a_run(sizes, traced=True):
    """8 steps in the traced span; 10 sparse-layer steps a counter step...
    counters as the driver hands them out."""
    before = {"steps": 100, "prefill_chunks": 7, "useful_steps": 1000,
              "moe_layer_steps": 500, "moe_experts_hit": 2000,
              "moe_pairs_here": 3000, "moe_pairs_routed": 48000,
              "moe_experts_held": 12, "tokens_prefill": 0}
    after = {"steps": 108, "prefill_chunks": 9, "useful_steps": 1080,
             "moe_layer_steps": 540, "moe_experts_hit": 2200,
             "moe_pairs_here": 3320, "moe_pairs_routed": 53120,
             "moe_experts_held": 12, "tokens_prefill": 1024}
    records = {"r0": {"due": 0.0, "first": 0.1, "last": 3.9, "done": 4.0,
                      "tokens": 100, "failed": False}}
    return {"trace": {"devices": 1, "window_s": 2.0,
                      "programs": {"jit_step": {"seconds": 0.08},
                                   "jit_extend": {"seconds": 0.08}}}
            if traced else None,
            "trace_counters": {"before": before, "after": after}
            if traced else {},
            "counters": {"before": before, "after": after},
            "all_records": records,
            "requests": {"r0": {"prompt_tokens": 1900}},
            "seconds": 4.0, "config": sizes,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def read(name, of):
    return run.load_module("layer_metrics", name).read(of)


def test_region_readers_split_the_step_and_sum_to_it(
        monkeypatch, tmp_path, sizes):
    trace = a_trace()
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    total = 0.0
    for name, scope in READERS.items():
        value = read(name, a_run(sizes))
        assert value == pytest.approx(2 * STEP_MS[scope] / 8), name
        total += value
    assert total == pytest.approx(2 * sum(STEP_MS.values()) / 8)
    # the extend's regions are not the step's: its attn_core stays out
    assert read("mla_extend_expand_ms", a_run(sizes)) == \
        pytest.approx(30.0 / 2)
    with open(tmp_path / "program_spans.json") as f:
        noted = json.load(f)["decode_step_regions_ms"]["seconds"]
    assert "aiko.mla_expand" not in noted


def test_counter_readers(sizes):
    of = a_run(sizes)
    # 200 experts hit over 40 sparse-layer steps of 12 held
    assert read("moe_experts_hit_share.docqa", of) == \
        pytest.approx(100 * 200 / (40 * 12))
    assert read("moe_pairs_here_share.docqa", of) == \
        pytest.approx(100 * 320 / 5120)
    assert read("prefill_device_ms_per_ktok.docqa", of) == \
        pytest.approx(1e3 * 0.08 / 1.024)


def test_roofline_readers_stay_under_the_peaks(monkeypatch, tmp_path, sizes):
    trace = a_trace()
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    of = a_run(sizes)
    # one request holds 1,900 + its grown tokens over the last 2 s
    held = 1900 + 100 * (3.0 - 0.1) / 3.8
    walk = ops.latent_attention(sizes, 2, held)
    assert read("mla_attn_core_roofline", of) == pytest.approx(
        100 * max(walk["bytes"] / 819e9, walk["flops"] / 197e12) / 1e-3,
        rel=0.02)
    experts = ops.routed_experts(sizes, 2, 200 / 8, 320 / 8)
    assert read("moe_experts_roofline", of) == pytest.approx(
        100 * experts["bytes"] / 819e9 / 2.5e-3)
    step = read("docqa_decode_step_roofline", of)
    assert 0 < step and step == pytest.approx(
        100 * ops.decode_step(sizes, 2, 10, held, 25, 40)["bytes"] / 819e9
        / 10e-3, rel=0.02)


@pytest.mark.parametrize("name", sorted(READERS) + [
    "mla_extend_expand_ms", "mla_attn_core_roofline", "moe_experts_roofline",
    "docqa_decode_step_roofline", "prefill_device_ms_per_ktok.docqa"])
def test_nothing_to_read_reads_none(name, sizes):
    assert read(name, a_run(sizes, traced=False)) is None


@pytest.mark.parametrize("name", ["moe_experts_hit_share.docqa",
                                  "moe_pairs_here_share.docqa"])
def test_a_program_without_the_counters_reads_none(name, sizes):
    """Another program under this benchmark (the parent's overlay): its
    driver hands out no expert counter."""
    of = a_run(sizes)
    for span in of["counters"].values():
        for key in [k for k in span if k.startswith("moe_")]:
            del span[key]
    assert read(name, of) is None


def test_the_manifest_entries():
    manifest = run.load_json("BENCHMARK.json")
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    tpot = next(m for m in manifest["end_to_end"]
                if m["name"] == "llm_tpot_p50_ms")
    assert tpot["workloads"] == ["chat_open_loop", CELL]
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 25
    assert all(m["moves"] == "llm_tpot_p50_ms" for m in mine)
    assert not any(m["name"].startswith("step_") for m in mine)
    for name in list(READERS) + ["mla_extend_expand_ms",
                                 "mla_attn_core_roofline",
                                 "moe_experts_roofline",
                                 "docqa_decode_step_roofline"]:
        assert os.path.exists(os.path.join(
            run.ROOT, "benchmark", "layer_metrics", name + ".py"))
