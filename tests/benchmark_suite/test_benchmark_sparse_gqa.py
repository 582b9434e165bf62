"""What PR 38 adds to the benchmark for `keye-vl-2.0-30b-a3b-ep8-d12`: the
configuration file against the published keys, the operations and bytes
against hand counts, the plain reference's control, each new reader on a
built trace or built counters (and on runs with nothing to read), and the
manifest's entries found BY NAME: no position, no "last", no exact length
of a list that later cells share."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import ops_bytes_sparse_gqa as ops
from benchmark import run
from benchmark.reference import sparse_gqa_lm
from benchmark.trace import regions as G

MS = 1e6        # ns
CONFIG = "keye-vl-2.0-30b-a3b-ep8-d12"
CELL = "long_ctx_open_loop"
SOURCE = ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
          "config.json")
# the catalog's entry for the source, key for key
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# PR 36's six, one entry each, read in every open-loop cell
JOURNEYS = ["tpot_mid_ms", "tpot_mid_host_ms", "tpot_mid_sync_clean_ms",
            "tpot_mid_sync_behind_ms", "tpot_mid_rounds_behind",
            "prefill_prefix_depth"]
SUFFIXED = ["decode_step_device_ms", "prefill_device_ms_per_ktok",
            "attend_width", "device_idle_share", "device_wait_on_host_ms",
            "round_host_ms", "round_longest_ms", "rounds_with_prefill",
            "prefill_round_penalty_ms", "tpot_mean_ms", "tpot_p95_ms",
            "moe_step_route_ms", "moe_step_experts_ms",
            "moe_experts_hit_share", "moe_pairs_here_share",
            "dsa_attended_share", "dsa_step_index_ms",
            "dsa_step_attn_core_ms"]
OWN = ["dsa_step_select_ms", "dsa_extend_select_ms",
       "dsa_fetch_amplification", "dsa_index_select_roofline",
       "dsa_sparse_attn_roofline", "longctx_decode_step_roofline"]
ROOFLINES = [name for name in OWN if "roofline" in name]


@pytest.fixture(scope="module")
def sizes():
    return run.load_json("benchmark", "configs", CONFIG + ".json")


def test_the_file_carries_every_published_key_but_the_four_cut(sizes):
    reduced = {"num_hidden_layers": 12, "num_experts": 16,
               "num_local_experts": 16, "vocab_size": 18992}
    assert sorted(sizes["reduced"]) == sorted(reduced)
    for key, value in PUBLISHED.items():
        assert sizes[key] == reduced.get(key, value), key
        if key in reduced:
            assert sizes["published"][key] == value
    # the floors of a model_config cut: every layer is one kind (four of
    # them at least), eight experts at least, an eighth of the vocabulary
    assert sizes["num_hidden_layers"] >= 4
    assert sizes["num_experts"] * 8 == PUBLISHED["num_experts"]
    assert sizes["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert sizes["source"] == SOURCE
    deployment = sizes["deployment"]
    assert deployment["chips_sharing_a_layer"] == 8
    assert deployment["pipeline_stages"] == 4
    assert deployment["expert_parallel"] == deployment["vocab_parallel"] == 8
    assert deployment["experts_first"] == 0
    for key in reduced:
        assert key in sizes["reduced_why"]
    for assumed in ("qk_norm", "rotary", "indexer", "selection",
                    "chunk_sizes", "indexer_key_leaf", "left_out", "unused",
                    "weights", "eos_token"):
        assert assumed in sizes["assumed"]
    assert "four blocks a query" in sizes["assumed"]["chunk_sizes"]
    assert "lower position" in sizes["assumed"]["selection"]
    assert "vision tower" in sizes["assumed"]["left_out"]
    assert "an eighth of the pairs" in sizes["assumed"]["weights"]
    assert sizes["assumed_sizes"] == {"index_rope_head_dim": 32,
                                      "index_rope_theta": 10000000}
    assert "32 chips" in sizes["stands_for"]
    serve = sizes["serving"]
    assert serve["max_seq"] == serve["t_block"] == 32768
    assert serve["prefill_chunk"] == serve["prefill_budget"] == 512
    assert serve["kv_block"] == 32 and serve["steps_per_sync"] == 4
    # the limit is this configuration's own (PERF.md, correctness): the
    # embedding is drawn wide so that served tokens do not repeat, and
    # sound and float8 runs then read 0.0001-0.0005 and 0.0035-0.014
    assert sizes["correctness"]["limits"] == {
        "served_token_gap_mean_std": 0.002}
    assert "2.0" in sizes["assumed"]["weights"]


def test_the_pool_and_the_weights_fill_what_the_file_says(sizes):
    """The arithmetic of the cut: 2.48 GB of weights, and the pool of the
    file's slots at 2,304 B a token and layer (the indexer key padded to a
    lane tile; 2,176 unpadded)."""
    held = ops.params(sizes)
    assert round(held["total"] * 2 / 1e9, 2) == 2.48
    assert round(ops.layer_params(sizes, 16) * 2 / 1e6, 1) == 193.8
    serve = sizes["serving"]
    tokens = serve["max_slots"] * serve["max_seq"]
    layers = sizes["num_hidden_layers"]
    assert ops.token_row_bytes(sizes, 2) == 2176
    padded = tokens * layers * (2176 + 128)
    # sized to what the cell's traffic holds at once (4 slots), not to the
    # chip: over the driver's floor of a quarter, under ISSUE 38's half
    assert 0.25 * 16.9e9 < padded + held["total"] * 2 < 0.5 * 16.9e9
    assert serve["max_slots"] == 4
    assert round(padded / 1e9, 2) == 3.62
    assert round(tokens * layers * 2176 / 1e9, 2) == 3.42


def test_operations_and_bytes_against_hand_counts(sizes):
    # 100,000 live positions a step (all layers and slots): a 64-lane key
    # each, 16 heads' dots with it
    index = ops.index_select(sizes, 2, live=100_000)
    assert index == {"bytes": 128 * 100_000,
                     "flops": 2 * 16 * 64 * 100_000}
    # 20,000 attended: K and V rows of 4 heads of 128, 32 heads' score and
    # output
    core = ops.sparse_attention(sizes, 2, attended=20_000)
    assert core == {"bytes": 2048 * 20_000,
                    "flops": 4 * 32 * 128 * 20_000}
    experts = ops.routed_experts(sizes, 2, experts_hit=30, pairs_here=40)
    assert experts == {"bytes": 30 * 4_718_592 * 2,
                       "flops": 2 * 4_718_592 * 40}
    always = ops.always_streamed_params(sizes)
    assert always == 12 * (18_874_624 + 2_261_120 + 4096 + 262_144) \
        + 2048 + 2048 * 18992
    whole = ops.decode_step(sizes, 2, 10, 20_000, 100_000, 30, 40)
    assert whole["bytes"] == always * 2 + index["bytes"] + core["bytes"] \
        + experts["bytes"] + 10 * 12 * 2176
    assert whole["flops"] == 2 * always * 10 + index["flops"] \
        + core["flops"] + experts["flops"]
    for work in (index, core, whole):
        assert ops.roofline_seconds(work, PEAKS)["bound"] == "bytes"


def test_the_float8_control_comes_out_as_not_correct(sizes):
    """The control at a size a test run can hold (PERF.md has the cell's
    own readings): the reference with float8 weights puts first, somewhere
    in some hundred positions, a token that lies further below the
    full-precision best than the configuration's limit allows."""
    limits = sizes["correctness"]["limits"]
    rehearse = sizes["rehearse"]
    small = run.merged(sizes, rehearse) | dict(
        vocab_size=2048, hidden_size=128, moe_intermediate_size=64)
    small.pop("serving")
    rng = np.random.default_rng(3)
    samples = [{"prompt": rng.integers(1, 2048, size=64).tolist(),
                "served": rng.integers(1, 2048, size=64).tolist()}
               for _ in range(2)]
    control = sparse_gqa_lm.check(samples, small, 9, jnp.bfloat16,
                                  control=True)["control"]
    assert any(max(control[name]) > limit for name, limit in limits.items()), \
        control


def _greedy(params, config, prompt, count: int):
    """`count` tokens decoded greedily by the PROGRAM's full forward pass
    in float32 (no pool, no cache): what a sound or a faulty decoder
    would serve."""
    import jax
    from aiko_services_tpu.models.sparse_gqa import sparse_gqa_forward
    width = len(prompt) + count
    forward = jax.jit(lambda tokens: sparse_gqa_forward(
        params, config, tokens[None])[0])
    row = np.zeros((width,), np.int32)
    row[:len(prompt)] = prompt
    for at in range(len(prompt), width):
        row[at] = int(jnp.argmax(forward(jnp.asarray(row))[at - 1]))
    return row[len(prompt):].tolist()


@pytest.mark.parametrize("fault", ["sound", "sigmoid-scores",
                                   "top-k-of-the-least"])
def test_a_planted_fault_comes_out_as_not_correct(fault, sizes):
    """The two mechanisms that only this configuration has, broken one at
    a time in what is SERVED (the program's forward pass in float32, so a
    sound run reads 0.0) and held to the reference by the harness's own
    comparison and the configuration's limit, at a size a test holds
    (PERF.md has the readings at the published widths): the router's
    scores a sigmoid's where the model's are a softmax's; the index
    scores' order turned round in every layer (the indexer's head weights
    negated), so each query attends the `topk` positions it should have
    left out."""
    import dataclasses
    import sys
    import jax
    sys.path.insert(0, os.path.join(run.ROOT, "benchmark", "drivers"))
    from sparse_gqa_decoder import model_config
    from benchmark import weights_sparse_gqa as W
    limit = sizes["correctness"]["limits"]["served_token_gap_mean_std"]
    small = run.merged(sizes, sizes["rehearse"]) | dict(
        vocab_size=2048, hidden_size=128, moe_intermediate_size=64,
        num_hidden_layers=4)
    small.pop("serving")
    seed = 9
    params = W.decoder_weights(W.key_for(seed), small, jnp.float32)
    config = model_config(small, 256, jnp.float32)
    if fault == "sigmoid-scores":
        config = dataclasses.replace(config, router_scores="sigmoid")
    if fault == "top-k-of-the-least":
        for layer in params["layers"]:
            layer["indexer"]["w"] = jax.tree.map(
                lambda w: -w, layer["indexer"]["w"])
    rng = np.random.default_rng(5)
    samples = []
    for _ in range(2):
        prompt = rng.integers(1, 2048, size=96).tolist()
        samples.append({"prompt": prompt,
                        "served": _greedy(params, config, prompt, 96)})
    found = sparse_gqa_lm.check(samples, small, seed, jnp.float32)
    (gap,) = found["numbers"]["served_token_gap_mean_std"]
    assert (gap > limit) == (fault != "sound"), (fault, gap)


# -- the readers --------------------------------------------------------------

SCOPES = [G.UNSCOPED, G.COMPILER, "aiko.attn_proj", "aiko.attn_core",
          "aiko.moe_route", "aiko.moe_experts", "aiko.head", "aiko.kv_merge",
          "aiko.dsa_index", "aiko.dsa_select"]
STEP_MS = {"aiko.attn_proj": 12, "aiko.attn_core": 6, "aiko.moe_route": 2,
           "aiko.moe_experts": 10, "aiko.head": 1, "aiko.kv_merge": 2,
           "aiko.dsa_index": 5, "aiko.dsa_select": 3}
READERS = {"dsa_step_index_ms.longctx": "aiko.dsa_index",
           "dsa_step_attn_core_ms.longctx": "aiko.attn_core",
           "dsa_step_select_ms": "aiko.dsa_select",
           "moe_step_route_ms.longctx": "aiko.moe_route",
           "moe_step_experts_ms.longctx": "aiko.moe_experts"}


def a_trace():
    """One chip: two rounds of `jit_step` of four steps each, every region
    once a round, and between them one `jit_extend` with 30 ms under
    `aiko.dsa_select` and 50 under `aiko.attn_core`."""
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
            for scope, ms in (("aiko.dsa_select", 30),
                              ("aiko.attn_core", 50)):
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
              "moe_layer_steps": 1200, "moe_experts_hit": 2000,
              "moe_pairs_here": 3000, "moe_pairs_routed": 24000,
              "moe_experts_held": 16, "tokens_prefill": 0,
              "dsa_positions_live": 1_000_000,
              "dsa_positions_attended": 300_000,
              "dsa_rows_fetched": 290_000, "dsa_slot_steps_dense": 0}
    after = {"steps": 108, "prefill_chunks": 9, "useful_steps": 1080,
             "moe_layer_steps": 1296, "moe_experts_hit": 2288,
             "moe_pairs_here": 3320, "moe_pairs_routed": 26560,
             "moe_experts_held": 16, "tokens_prefill": 1024,
             "dsa_positions_live": 1_800_000,
             "dsa_positions_attended": 460_000,
             "dsa_rows_fetched": 444_000, "dsa_slot_steps_dense": 0}
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
    assert read("dsa_extend_select_ms", a_run(sizes)) == \
        pytest.approx(30.0 / 2)
    with open(tmp_path / "program_spans.json") as f:
        noted = json.load(f)["decode_step_regions_ms"]["seconds"]
    assert noted["aiko.dsa_select"] == pytest.approx(2 * 3 / 8)


def test_counter_readers(sizes):
    of = a_run(sizes)
    assert read("dsa_attended_share.longctx", of) == \
        pytest.approx(100 * 160_000 / 800_000)
    assert read("dsa_fetch_amplification", of) == \
        pytest.approx(154_000 / 160_000)
    # 288 experts hit over 96 layer-steps of 16 held
    assert read("moe_experts_hit_share.longctx", of) == \
        pytest.approx(100 * 288 / (96 * 16))
    assert read("moe_pairs_here_share.longctx", of) == \
        pytest.approx(100 * 320 / 2560)
    assert read("prefill_device_ms_per_ktok.longctx", of) == \
        pytest.approx(1e3 * 0.08 / 1.024)


def test_roofline_readers_stay_under_the_peaks(monkeypatch, tmp_path, sizes):
    trace = a_trace()
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    of = a_run(sizes)
    # 100,000 positions live and 20,000 attended a step
    index = ops.index_select(sizes, 2, 100_000)
    assert read("dsa_index_select_roofline", of) == pytest.approx(
        100 * index["bytes"] / 819e9 / (2 * (5 + 3) * 1e-3 / 8))
    core = ops.sparse_attention(sizes, 2, 20_000)
    assert read("dsa_sparse_attn_roofline", of) == pytest.approx(
        100 * core["bytes"] / 819e9 / (2 * 6e-3 / 8))
    step = ops.decode_step(sizes, 2, 10, 20_000, 100_000, 36, 40)
    assert read("longctx_decode_step_roofline", of) == pytest.approx(
        100 * step["bytes"] / 819e9 / 10e-3)
    for name in ROOFLINES:
        assert 0 < read(name, of) < 100


@pytest.mark.parametrize("name", sorted(READERS) + OWN + [
    "prefill_device_ms_per_ktok.longctx"])
def test_nothing_to_read_reads_none(name, sizes):
    if name == "dsa_fetch_amplification":
        pytest.skip("a counter's ratio over the window: no trace is read")
    assert read(name, a_run(sizes, traced=False)) is None


REGION_READERS = ["dsa_step_select_ms", "dsa_extend_select_ms"]


@pytest.mark.parametrize("name", OWN + ["dsa_step_index_ms.longctx",
                                        "dsa_step_attn_core_ms.longctx",
                                        "dsa_attended_share.longctx"])
def test_a_program_without_the_counters_reads_none(
        name, monkeypatch, tmp_path, sizes):
    """Another program under this benchmark (the parent's overlay, or
    another configuration's driver): no sparse-attention counter, and no
    operation under `aiko.dsa_select`.  The new readers return nothing
    and do not raise (the step's region reads 0.0, as `step_kv_view_ms`
    does since PR 30: `regions.step_region_ms`'s answer for a scope that
    no operation of the step carries)."""
    trace = a_trace()
    if name in REGION_READERS:     # a region's reader reads the trace alone
        trace["ops"] = trace["devices"][0]["ops"] = [
            op for op in trace["devices"][0]["ops"]
            if SCOPES[op[2]] != "aiko.dsa_select"]
        trace["scopes"] = [scope if scope != "aiko.dsa_select" else "aiko.x"
                           for scope in SCOPES]
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    of = a_run(sizes)
    for group in ("counters", "trace_counters"):
        for span in of[group].values():
            for key in [k for k in span if k.startswith("dsa_")]:
                del span[key]
    assert read(name, of) == (0.0 if name == "dsa_step_select_ms" else None)


# -- the manifest, by name ------------------------------------------------------

def test_the_manifest_entries(sizes):
    manifest = run.load_json("BENCHMARK.json")
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == CELL and "0.8 of the knee" in cell["why"]
    assert "8x" in cell["why"] and len(cell["why"]) <= 200
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(sizes["reduced"])
    tpot = next(m for m in manifest["end_to_end"]
                if m["name"] == "llm_tpot_p50_ms")
    assert CELL in tpot["workloads"] and tpot["bound"] == 0.045
    per_layer = manifest["per_layer"]
    assert len({m["name"] for m in per_layer}) == len(per_layer)
    mine = {m["name"]: m for m in per_layer if m.get("workloads") == [CELL]}
    assert sorted(mine) == sorted([name + ".longctx" for name in SUFFIXED]
                                  + OWN)
    for name, m in mine.items():
        assert m["moves"] == "llm_tpot_p50_ms"
        assert not name.startswith("step_")
        if "roofline" in name:
            assert m["unit"] == "%" and m["better"] == "higher"
        assert callable(run.load_module("layer_metrics", name).read)
    for name in OWN + ["prefill_device_ms_per_ktok.longctx"]:
        assert os.path.exists(os.path.join(
            run.ROOT, "benchmark", "layer_metrics",
            name.replace(".", "_") + ".py"))
    # the cell reports these, PR 36's six that every open-loop cell
    # reports (the judged gap from inside), and no metric of another cell's
    shared = {m["name"] for m in per_layer
              if CELL in m.get("workloads", []) and m["name"] not in mine}
    assert shared == set(JOURNEYS)
    assert {m["name"] for m in run.resolve(CELL, False)["per_layer"]} == \
        set(mine) | shared
    traffic = run.load_json("benchmark", "traffic", CELL + ".json")
    assert traffic["generator"] == "poisson_requests"
    fields = traffic["parameters"]["fields"]
    assert fields["prompt_tokens"] == {
        "dist": "lognormal", "median": 12288, "sigma": 0.6, "min": 4096,
        "max": 30720}
    assert fields["output_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.6, "min": 64,
        "max": 1024}
    assert traffic["parameters"]["order_draw"] == 0
    assert traffic["parameters"]["preroll_s"] == 8
    assert traffic["drain_s"] == 14 and traffic["reference_samples"] == 2
    assert traffic["promised_ms"] == {"first": [20000], "per_token": [100]}
    assert str(traffic["parameters"]["rate_per_s"]) in cell["why"]
    # every prompt is at least twice topk: no decode step attends all
    assert fields["prompt_tokens"]["min"] >= 2 * sizes["sa_config"]["topk"]
    assert fields["prompt_tokens"]["max"] + fields["output_tokens"]["max"] \
        <= sizes["serving"]["max_seq"]


def test_the_cells_before_this_one_keep_their_entries():
    """`test_benchmark_hybrid_sparse.py::test_the_cells_before_this_one_
    keep_their_entries` and `test_benchmark_journeys.py::test_the_manifest_
    entries` hold `llm_tpot_p50_ms`'s `workloads` to EXACTLY the three cells
    of PR 36's day and are red since ISSUE 38 appended a fourth (those files
    are a `benchmark` PR's to edit, as the two `test_the_manifest_entries`
    that pin "last" were before them).  What they assert of the OLDER cells
    is held here meanwhile, and the list is held to BEGIN with what it
    was."""
    manifest = run.load_json("BENCHMARK.json")
    cells = {c["name"]: c for c in manifest["workloads"]}
    before = ["chat_open_loop", "doc_qa_open_loop", "long_doc_open_loop"]
    assert cells["doc_qa_open_loop"]["config"] == "ax-k1-ep16-d6"
    assert cells["long_doc_open_loop"]["config"] == "glm-5.3-flash-ep8-d5"
    assert all(cells[name]["chips"] == 1 for name in before + [CELL])
    tpot = next(m for m in manifest["end_to_end"]
                if m["name"] == "llm_tpot_p50_ms")
    assert tpot["workloads"][:3] == before
    per_layer = manifest["per_layer"]
    own = {cell: [m for m in per_layer if m.get("workloads") == [cell]]
           for cell in before}
    assert [len(own[cell]) for cell in before] == [12, 25, 26]
    for cell in before[1:]:
        assert all(m["moves"] == "llm_tpot_p50_ms" for m in own[cell])
        assert not any(m["name"].startswith("step_") for m in own[cell])
        for m in own[cell]:
            assert callable(run.load_module("layer_metrics", m["name"]).read)
    # PR 36's six, one entry each for the three cells they were made for
    journeys = JOURNEYS
    shared = {m["name"]: m for m in per_layer if m["name"] in journeys}
    assert sorted(shared) == sorted(journeys)
    for m in shared.values():
        assert m["workloads"][:3] == before and m["layer"] == "scheduling"
        assert m["moves"] == "llm_tpot_p50_ms" and m["better"] == "lower"
    for cell in before:
        names = {m["name"] for m in run.resolve(cell, False)["per_layer"]}
        assert names == set(journeys) | {m["name"] for m in own[cell]}
    saturated = run.resolve("decode_saturated", False)["per_layer"]
    assert not set(journeys) & {m["name"] for m in saturated}
