"""What PR 33 adds to the benchmark for `glm-5.3-flash-ep8-d5`: the
configuration file against the published keys, the parameter count from
them, the operations and bytes, the plain reference's control, and each new
reader on a built trace or built counters (and on runs with nothing to
read)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import ops_bytes_hybrid_sparse as ops
from benchmark import run
from benchmark.reference import hybrid_sparse_lm
from benchmark.trace import regions as G

MS = 1e6        # ns
CONFIG = "glm-5.3-flash-ep8-d5"
CELL = "long_doc_open_loop"
LIN, DSA = "linear_attention", "deepseek_sparse_attention"
# the catalog's entry for the source, number for number (its three lists a
# layer, 45 long, are given by their rule)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3, "hc_eps": 1e-06,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "head_dim": 0,
    "hidden_act": "silu", "hidden_size": 4096, "index_head_dim": 128,
    "index_kpool": 4, "index_kpool_always_select_tail": True,
    "index_kpool_compress": True, "index_n_heads": 32, "index_topk": 2048,
    "index_share_for_mtp_iteration": True, "indexer_rope_interleave": True,
    "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 1048576, "mhc": True, "mla_use_nope": True,
    "model_type": "glm5_next_text", "moe_intermediate_size": 2048,
    "n_group": 1, "n_routed_experts": 288, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 45,
    "num_key_value_heads": 64, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_head_dim": 256, "qk_nope_head_dim": 256,
    "qk_rope_head_dim": 0, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "swiglu_limit": 10, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def sizes():
    return run.load_json("benchmark", "configs", CONFIG + ".json")


def test_the_file_carries_every_published_key_but_those_cut_with_depth(sizes):
    reduced = {"num_hidden_layers": 5, "n_routed_experts": 36,
               "vocab_size": 19360, "first_k_dense_replace": 1}
    patterns = {"layer_types", "mlp_layer_types", "indexer_types",
                "linear_attn_config"}
    assert set(sizes["reduced"]) == set(reduced) | patterns
    for key, value in PUBLISHED.items():
        assert sizes[key] == reduced.get(key, value), key
        if key in reduced:
            assert sizes["published"][key] == value
    # published layer 0, then published layers 4-7: a whole period
    assert sizes["layer_types"] == [LIN, LIN, LIN, LIN, DSA]
    assert sizes["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert sizes["indexer_types"] == ["full"] * 5
    # no width inside the group changed: only its two lists were cut
    assert sizes["linear_attn_config"] == {
        "num_heads": 64, "gate_lower_bound": -5, "head_dim": 128,
        "short_conv_kernel_size": 4, "kda_layers": [0, 1, 2, 3],
        "full_attn_layers": [4]}
    for key in ("num_hidden_layers", "n_routed_experts", "vocab_size"):
        assert key in sizes["reduced_why"]
    assert sizes["deployment"]["expert_parallel"] == 8
    assert sizes["deployment"]["vocab_parallel"] == 8
    for assumed in ("index_kpool", "gate_lower_bound", "swiglu_limit",
                    "indexer_rotary", "streams", "left_out", "weights",
                    "eos_token"):
        assert assumed in sizes["assumed"]
    assert "max-pooling" in sizes["assumed"]["index_kpool"]
    assert "8,192 positions" in sizes["assumed"]["index_kpool"]
    assert sizes["assumed_sizes"] == {
        "kda_gate_rank": 128, "index_rope_head_dim": 64,
        "index_rope_theta": 10000}
    # the floors of a model_config cut: a whole period of four layers after
    # the dense one, eight experts at least, an eighth of the vocabulary
    assert sizes["num_hidden_layers"] - sizes["first_k_dense_replace"] >= 4
    assert sizes["n_routed_experts"] * 8 == PUBLISHED["n_routed_experts"]
    assert sizes["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert sizes["serving"] == {
        "max_slots": 32, "max_seq": 32768, "t_block": 32768, "kv_block": 32,
        "prefill_buckets": [256, 512], "prefill_chunk": 512,
        "prefill_budget": 512, "steps_per_sync": 4}


def test_the_parameter_count_reproduces_the_published_size(sizes):
    """My own count from the keys: 313.3 B, 320.7 B with the multi-token-
    prediction layer, of the published 320 B; 4.718 B held here."""
    assert ops.kda_params(sizes) == 137_732_288
    assert ops.latent_params(sizes) == 117_442_560
    assert ops.indexer_params(sizes) == 6_947_072
    assert ops.expert_params(sizes) == 25_165_824
    assert ops.hyper_params(sizes) == 16384 + 16384 * 24 + 27
    assert round(ops.layer_params(sizes, "kda", "dense", 0) / 1e6, 2) == \
        289.55
    assert round(ops.layer_params(sizes, "kda", "sparse", 0) / 1e6, 2) == \
        164.91
    assert round(ops.layer_params(sizes, "dsa", "sparse", 0) / 1e6, 2) == \
        151.56
    whole = ops.published_parameters(sizes)
    assert round(whole["served_layers_and_ends"] / 1e9, 1) == 313.3
    assert round(whole["total"] / 1e9, 1) == 320.7
    assert round(whole["mtp"] / 1e9, 2) == 7.40
    held = ops.params(sizes)
    assert round(held["total"] / 1e9, 3) == 4.718
    assert round(held["experts_held"] / 4 * 2 / 1e9, 2) == 1.81  # a layer
    # what a step always streams: 2.03 GB in bfloat16
    assert round(held["always_streamed"] * 2 / 1e9, 2) == 2.03


def test_operations_and_bytes_of_the_new_kernels(sizes):
    state = 64 * 128 * 128 * 4
    # a step over 10 slots: each layer's state in and out once a slot
    step = ops.kda_recurrence(sizes, 10, 10)
    assert step["flops"] == 7 * 64 * 128 * 128 * 10 * 4
    assert step["bytes"] == (2 * state * 10 + 5 * 64 * 128 * 4 * 10) * 4
    # a chunk of 512 tokens of one sequence: its state once
    chunk = ops.kda_recurrence(sizes, 512, 1)
    assert chunk["bytes"] == (2 * state + 5 * 64 * 128 * 4 * 512) * 4
    assert ops.roofline_seconds(chunk, PEAKS)["bound"] == "bytes"
    # the sparse core: the chosen rows and the pooled keys, once
    core = ops.sparse_core(sizes, 2, attended=20_000, live=100_000)
    assert core["bytes"] == (512 * 20_000 + 128 * 25_000) * 2
    assert core["flops"] == 4 * 64 * 512 * 20_000 + 2 * 32 * 128 * 25_000
    everything = ops.sparse_core(sizes, 2, attended=100_000, live=100_000)
    assert everything["bytes"] > 4 * core["bytes"]
    experts = ops.routed_experts(sizes, 2, experts_hit=30, pairs_here=40)
    assert experts["bytes"] == 30 * 25_165_824 * 2
    whole = ops.decode_step(sizes, 2, 10, 20_000, 100_000, 30, 40)
    assert whole["bytes"] == ops.always_streamed_params(sizes) * 2 \
        + step["bytes"] + core["bytes"] + experts["bytes"] \
        + 10 * (512 + 32) * 2
    assert ops.roofline_seconds(whole, PEAKS)["bound"] == "bytes"


def test_the_float8_control_comes_out_as_not_correct(sizes):
    """The control at a size a test run can hold (PERF.md has the cell's
    own readings): the reference with float8 weights puts first, somewhere
    in some hundred positions, a token that lies further below the
    full-precision best than the configuration's limit allows."""
    limits = sizes["correctness"]["limits"]
    rehearse = sizes["rehearse"]
    small = sizes | rehearse | dict(
        vocab_size=2048, hidden_size=128, intermediate_size=256,
        moe_intermediate_size=64,
        linear_attn_config=sizes["linear_attn_config"]
        | rehearse["linear_attn_config"],
        assumed_sizes=sizes["assumed_sizes"] | rehearse["assumed_sizes"])
    rng = np.random.default_rng(3)
    samples = [{"prompt": rng.integers(1, 2048, size=64).tolist(),
                "served": rng.integers(1, 2048, size=64).tolist()}
               for _ in range(2)]
    control = hybrid_sparse_lm.check(samples, small, 9, jnp.bfloat16,
                                     control=True)["control"]
    assert any(max(control[name]) > limit for name, limit in limits.items()), \
        control


# -- the readers --------------------------------------------------------------

SCOPES = [G.UNSCOPED, G.COMPILER, "aiko.attn_proj", "aiko.attn_core",
          "aiko.moe_route", "aiko.moe_shared", "aiko.moe_experts", "aiko.mlp",
          "aiko.head", "aiko.kv_merge", "aiko.kda_core", "aiko.dsa_index",
          "aiko.mhc", "aiko.dsa_relayout"]
STEP_MS = {"aiko.attn_proj": 12, "aiko.attn_core": 4, "aiko.moe_route": 2,
           "aiko.moe_shared": 6, "aiko.moe_experts": 10, "aiko.mlp": 3,
           "aiko.head": 1, "aiko.kv_merge": 2, "aiko.kda_core": 16,
           "aiko.dsa_index": 5, "aiko.mhc": 7, "aiko.dsa_relayout": 8}
READERS = {"kda_step_core_ms": "aiko.kda_core",
           "dsa_step_index_ms": "aiko.dsa_index",
           "dsa_step_attn_core_ms": "aiko.attn_core",
           "dsa_step_relayout_ms": "aiko.dsa_relayout",
           "mhc_step_ms": "aiko.mhc",
           "moe_step_route_ms.longdoc": "aiko.moe_route",
           "moe_step_shared_ms.longdoc": "aiko.moe_shared",
           "moe_step_experts_ms.longdoc": "aiko.moe_experts"}
ROOFLINES = ["kda_scan_roofline", "dsa_attn_core_roofline",
             "longdoc_decode_step_roofline"]


def a_trace():
    """One chip: two rounds of `jit_step` of four steps each, every region
    once a round, and between them one `jit_extend` with 30 ms under
    `aiko.kda_core` and 50 under `aiko.attn_core`."""
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
            for scope, ms in (("aiko.kda_core", 30), ("aiko.attn_core", 50)):
                ops_.append([at * MS, ms * MS, SCOPES.index(scope), 1])
                at += ms
            modules.append(["jit_extend(9)", start * MS, (at - start) * MS])
            at += 3
    return {"scopes": SCOPES, "programs": ["jit_step(7)", "jit_extend(9)"],
            "devices": [{"name": "/device:TPU:0", "modules": modules,
                         "ops": ops_}],
            "host": [["bench.traced", 0.0, at * MS]]}


def a_run(sizes, traced=True):
    """8 steps and 2 chunks in the traced span; counters as the driver
    hands them out."""
    before = {"steps": 100, "prefill_chunks": 7, "useful_steps": 1000,
              "moe_layer_steps": 400, "moe_experts_hit": 2000,
              "moe_pairs_here": 3000, "moe_pairs_routed": 24000,
              "moe_experts_held": 36, "tokens_prefill": 0,
              "dsa_positions_live": 1_000_000,
              "dsa_positions_attended": 300_000, "slot_states_zeroed": 3}
    after = {"steps": 108, "prefill_chunks": 9, "useful_steps": 1080,
             "moe_layer_steps": 432, "moe_experts_hit": 2288,
             "moe_pairs_here": 3320, "moe_pairs_routed": 26560,
             "moe_experts_held": 36, "tokens_prefill": 1024,
             "dsa_positions_live": 1_800_000,
             "dsa_positions_attended": 460_000, "slot_states_zeroed": 4}
    return {"trace": {"devices": 1, "window_s": 2.0,
                      "programs": {"jit_step": {"seconds": 0.08},
                                   "jit_extend": {"seconds": 0.08}}}
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
    for name, scope in READERS.items():
        assert read(name, a_run(sizes)) == \
            pytest.approx(2 * STEP_MS[scope] / 8), name
    # the extend's regions are not the step's
    assert read("kda_extend_scan_ms", a_run(sizes)) == pytest.approx(30.0 / 2)
    with open(tmp_path / "program_spans.json") as f:
        noted = json.load(f)["decode_step_regions_ms"]["seconds"]
    assert noted["aiko.kda_core"] == pytest.approx(2 * 16 / 8)


def test_counter_readers(sizes):
    of = a_run(sizes)
    assert read("dsa_attended_share.longdoc", of) == \
        pytest.approx(100 * 160_000 / 800_000)
    # 288 experts hit over 32 sparse-layer steps of 36 held
    assert read("moe_experts_hit_share.longdoc", of) == \
        pytest.approx(100 * 288 / (32 * 36))
    assert read("moe_pairs_here_share.longdoc", of) == \
        pytest.approx(100 * 320 / 2560)
    assert read("prefill_device_ms_per_ktok.longdoc", of) == \
        pytest.approx(1e3 * 0.08 / 1.024)


def test_roofline_readers_stay_under_the_peaks(monkeypatch, tmp_path, sizes):
    trace = a_trace()
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    of = a_run(sizes)
    chunk = ops.kda_recurrence(sizes, 512, 1)
    assert read("kda_scan_roofline", of) == pytest.approx(
        100 * chunk["bytes"] / 819e9 / 15e-3)
    # 20,000 positions attended of 100,000 live a step, over the index's,
    # the core's and the leaf's copy's 2 x (5 + 4 + 8) ms of 8 steps
    core = ops.sparse_core(sizes, 2, 20_000, 100_000)
    assert read("dsa_attn_core_roofline", of) == pytest.approx(
        100 * core["bytes"] / 819e9 / (2 * 17e-3 / 8))
    step = ops.decode_step(sizes, 2, 10, 20_000, 100_000, 36, 40)
    assert read("longdoc_decode_step_roofline", of) == pytest.approx(
        100 * step["bytes"] / 819e9 / 10e-3)
    for name in ROOFLINES:
        assert 0 < read(name, of) < 100


@pytest.mark.parametrize("name", sorted(READERS) + ROOFLINES + [
    "kda_extend_scan_ms", "prefill_device_ms_per_ktok.longdoc"])
def test_nothing_to_read_reads_none(name, sizes):
    assert read(name, a_run(sizes, traced=False)) is None


@pytest.mark.parametrize("name", [
    "dsa_attended_share.longdoc", "kda_step_core_ms", "dsa_step_index_ms",
    "dsa_step_attn_core_ms", "dsa_step_relayout_ms", "mhc_step_ms",
    "dsa_attn_core_roofline", "longdoc_decode_step_roofline"])
def test_a_program_without_the_counters_reads_none(
        name, monkeypatch, tmp_path, sizes):
    """Another program under this benchmark (the parent's overlay, or
    another configuration's driver): no sparse-attention counter."""
    trace = a_trace()
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    of = a_run(sizes)
    for group in ("counters", "trace_counters"):
        for span in of[group].values():
            for key in [k for k in span if k.startswith("dsa_")]:
                del span[key]
    assert read(name, of) is None


def test_the_manifest_entries():
    manifest = run.load_json("BENCHMARK.json")
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell == manifest["workloads"][-1]
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == CELL and "0.8 of the knee" in cell["why"]
    entry = manifest["configs"][-1]
    assert entry["name"] == CONFIG and entry["source"] == \
        "https://huggingface.co/zai-org/GLM-5.3-Flash/blob/main/config.json"
    sizes = run.load_json(entry["file"])
    assert sorted(entry["reduced"]) == sorted(sizes["reduced"])
    tpot = next(m for m in manifest["end_to_end"]
                if m["name"] == "llm_tpot_p50_ms")
    assert tpot["workloads"][-1] == CELL and tpot["bound"] == 0.045
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 26 and mine == manifest["per_layer"][-26:]
    assert all(m["moves"] == "llm_tpot_p50_ms" for m in mine)
    assert not any(m["name"].startswith("step_") for m in mine)
    for m in mine:
        if "roofline" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
        run.load_module("layer_metrics", m["name"])
    traffic = run.load_json("benchmark", "traffic", CELL + ".json")
    assert traffic["generator"] == "poisson_requests"
    fields = traffic["parameters"]["fields"]
    assert fields["prompt_tokens"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.8, "min": 1024,
        "max": 30720}
    assert fields["output_tokens"] == {
        "dist": "lognormal", "median": 160, "sigma": 0.6, "min": 32,
        "max": 512}
    assert traffic["parameters"]["order_draw"] == 0
    assert traffic["parameters"]["preroll_s"] == 8
    assert traffic["drain_s"] == 14 and traffic["reference_samples"] == 3
    assert str(traffic["parameters"]["rate_per_s"]) in cell["why"]
    for name in list(READERS) + ROOFLINES:
        stem = name.rpartition(".")[0] or name
        assert os.path.exists(os.path.join(
            run.ROOT, "benchmark", "layer_metrics", stem + ".py"))


def test_the_cells_before_this_one_keep_their_entries():
    """`test_benchmark_latent_moe.py::test_the_manifest_entries` holds
    `llm_tpot_p50_ms` to EXACTLY the two cells of PR 31's day and is red
    since ISSUE 33 appended a third (that file is a `benchmark` PR's to
    edit).  What else it asserts of A.X-K1's cell is held here meanwhile,
    and the list is held to BEGIN with what it was."""
    manifest = run.load_json("BENCHMARK.json")
    cell = next(c for c in manifest["workloads"]
                if c["name"] == "doc_qa_open_loop")
    assert cell["config"] == "ax-k1-ep16-d6" and cell["chips"] == 1
    tpot = next(m for m in manifest["end_to_end"]
                if m["name"] == "llm_tpot_p50_ms")
    assert tpot["workloads"] == ["chat_open_loop", "doc_qa_open_loop", CELL]
    theirs = [m for m in manifest["per_layer"]
              if m.get("workloads") == ["doc_qa_open_loop"]]
    assert len(theirs) == 25
    assert all(m["moves"] == "llm_tpot_p50_ms" for m in theirs)
    assert not any(m["name"].startswith("step_") for m in theirs)
    for m in theirs:           # a reader file for each, as the harness finds it
        assert callable(run.load_module("layer_metrics", m["name"]).read)
