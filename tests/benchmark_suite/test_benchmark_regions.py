"""benchmark/trace/regions.py: the wire reader on a hand-made `.xplane.pb`,
the charging of device operations to regions and of idle gaps to the
decoder's phases by hand, the metric files over them on a synthetic `run`,
and all of it on a recording cut from a chip trace of PR 24."""

import json
import os
import struct

import pytest

from benchmark import run
from benchmark.trace import reduce as R
from benchmark.trace import regions as G

MS = 1e6        # ns


# -- a hand-made .xplane.pb -------------------------------------------------------

def varint(n: int) -> bytes:
    n %= 1 << 64
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, float):
        return varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def plane(name, stat_names, metadata, lines) -> bytes:
    """metadata: {id: (name, {stat name: value})}; lines: {name: (stamp_ns,
    [(metadata id, offset_ps, duration_ps)])}."""
    stat_ids = {stat: i + 1 for i, stat in enumerate(stat_names)}
    out = field(1, 7) + field(2, name)
    for line_name, (stamp, events) in lines.items():
        body = field(1, 3) + field(2, line_name) + field(3, stamp)
        for ident, offset, duration in events:
            body += field(4, field(1, ident) + field(2, offset)
                          + field(3, duration)
                          + field(4, field(1, 1) + field(2, 0.5)))
        out += field(3, body)
    for ident, (label, stats) in metadata.items():
        entry = field(1, ident) + field(2, label) + field(4, "short")
        for stat, value in stats.items():
            entry += field(5, field(1, stat_ids[stat]) + field(
                5 if isinstance(value, str) else 3, value))
        out += field(4, field(1, ident) + field(2, entry))
    for stat, ident in stat_ids.items():
        out += field(5, field(1, ident)
                     + field(2, field(1, ident) + field(2, stat)))
    return out


@pytest.fixture
def xplane(tmp_path):
    step, admit = 8934567298056791977, (1 << 64) - 5
    device = plane(
        "/device:TPU:0", ["tf_op", "program_id", "hlo_category"],
        {1: (f"jit_step({step})", {}), 2: (f"jit_admit({admit})", {}),
         10: ("%fusion.1 = bf16[8]{0} fusion(...)", {
             "tf_op": "jit(step)/while/body/closed_call/aiko.mlp/"
                      "...i,io->...o/dot_general:",
             "program_id": step, "hlo_category": "convolution fusion"}),
         11: ("%copy.9 = bf16[8]{0} copy(...)", {
             "tf_op": "k_pools[3]:", "program_id": step}),
         12: ("%while.2 = (...) while(...)", {"program_id": step}),
         13: ("%fusion.7", {"tf_op": "jit(admit)/aiko.head/argmax:",
                            "program_id": admit - (1 << 64)})},
        {"XLA Modules": (1000, [(1, 0, 40_000_000), (2, 50_000_000,
                                                     10_000_000)]),
         "XLA Ops": (1000, [(12, 0, 30_000_000), (10, 1_000_000, 20_000_000),
                            (11, 31_000_000, 9_000_000),
                            (13, 50_000_000, 10_000_000)]),
         "Steps": (1000, [])})
    host = plane(
        "/host:CPU", ["_pt"],
        {1: ("aiko.decoder.round", {}), 2: ("aiko.decoder.sync", {}),
         3: ("bench.traced", {}), 4: ("$serving.py:1 pump", {})},
        {"python3": (900, [(3, 0, 70_000_000), (1, 100_000, 50_000_000),
                           (2, 200_000, 30_000_000), (4, 0, 1_000_000)]),
         "other thread": (900, [(4, 0, 5_000_000)])})
    other = plane("/host:metadata", [], {5: ("jit_step", {})}, {})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, device) + field(1, host) + field(1, other)
                     + field(2, "an error the reader skips"))
    return str(path)


def test_the_wire_reader_on_a_hand_made_trace(xplane):
    trace = G.load(xplane)
    assert trace["scopes"] == [G.UNSCOPED, G.COMPILER, "aiko.mlp",
                               "aiko.head"]
    assert trace["programs"] == ["jit_step(8934567298056791977)",
                                 f"jit_admit({(1 << 64) - 5})"]
    (device,) = trace["devices"]
    assert device["name"] == "/device:TPU:0"
    assert device["modules"] == [
        ["jit_step(8934567298056791977)", 1000.0, 40_000.0],
        [f"jit_admit({(1 << 64) - 5})", 51_000.0, 10_000.0]]
    # [start, duration, scope, program], ns on the line's clock
    assert device["ops"] == [[1000.0, 30_000.0, 1, 0], [2000.0, 20_000.0, 2, 0],
                             [32_000.0, 9_000.0, 1, 0],
                             [51_000.0, 10_000.0, 3, 1]]
    assert trace["host"] == [["bench.traced", 900.0, 70_000.0],
                             ["aiko.decoder.round", 1000.0, 50_000.0],
                             ["aiko.decoder.sync", 1100.0, 30_000.0]]
    # and jax's own reader sees the same events at the same times
    from jax.profiler import ProfileData
    with open(xplane, "rb") as f:
        planes = {p.name: p for p in
                  ProfileData.from_serialized_xspace(f.read()).planes}
    ops = next(line for line in planes["/device:TPU:0"].lines
               if line.name == "XLA Ops")
    assert [(e.start_ns, e.duration_ns) for e in ops.events] == \
        [(s, d) for s, d, _, _ in device["ops"]]
    found = G.region_seconds(trace, ["jit_step"])
    # the while keeps its own 10 us; the copy after it is adopted by the
    # scoped operation that ran before it
    assert found["seconds"] == {G.UNSCOPED: pytest.approx(10e-6),
                                "aiko.mlp": pytest.approx(29e-6)}
    assert found["adopted"] == {"aiko.mlp": pytest.approx(9e-6)}


def test_scope_of_a_path():
    assert G.scope_of("jit(step)/while/body/closed_call/aiko.mlp/"
                      "...i,io->...o/dot_general:") == "aiko.mlp"
    assert G.scope_of("jit(step)/aiko.kv_view/jit(_take)/select_n:") == \
        "aiko.kv_view"
    assert G.scope_of("jit(f)/aiko.mlp/aiko.head/add:") == "aiko.head"
    # the compiler's own: no path, an argument's name, a loop and no more
    for path in ("", "k_pools[3]:", "jit(step)/while:",
                 "jit(step)/while/body/closed_call:"):
        assert G.scope_of(path) == G.COMPILER, path
    # an operation of the program outside every region
    assert G.scope_of("jit(admit)/jit(main)/bhqd,bhkd->bhqk/dot_general:") \
        is None
    assert G.scope_of("jit(step)/while/body/closed_call/jit(_take)/gather:") \
        is None


# -- charging by hand ---------------------------------------------------------------

def a_trace():
    """One chip, two runs of jit_step and one of jit_admit between them."""
    scopes = [G.UNSCOPED, G.COMPILER, "aiko.kv_view", "aiko.mlp",
              "aiko.kv_merge"]
    ops = [
        # run 1 of jit_step: 0-100 ms
        [0 * MS, 10 * MS, 2, 0],        # kv_view
        [10 * MS, 2 * MS, 1, 0],        # a copy after it: adopted by kv_view
        [12 * MS, 58 * MS, 1, 0],       # the while: holds others, 2 ms its own
        [13 * MS, 50 * MS, 3, 0],       # mlp, inside the while
        [63 * MS, 5 * MS, 1, 0],        # the compiler's op after it: mlp's
        [68 * MS, 1 * MS, 0, 0],        # the program's own, outside a region
        [72 * MS, 1 * MS, 4, 0],        # kv_merge: the scatter's indices,
        [73 * MS, 7 * MS, 1, 0],        # the copy of the pool leaf ...
        [80 * MS, 1 * MS, 4, 0],        # ... the scatter into it ...
        [81 * MS, 9 * MS, 1, 0],        # ... and the copy back
        # jit_admit: 100-120 ms, no scope at all in it
        [100 * MS, 20 * MS, 1, 1],
        # run 2 of jit_step: 120-140 ms; it begins with a copy
        [120 * MS, 4 * MS, 1, 0],       # adopted by the NEXT one, of this run
        [124 * MS, 16 * MS, 3, 0]]
    modules = [["jit_step(11)", 0 * MS, 100 * MS],
               ["jit_admit(22)", 100 * MS, 20 * MS],
               ["jit_step(11)", 120 * MS, 20 * MS]]
    host = [["bench.traced", 0.0, 150 * MS]]
    return {"scopes": scopes, "programs": ["jit_step(11)", "jit_admit(22)"],
            "devices": [{"name": "/device:TPU:0", "modules": modules,
                         "ops": ops}], "host": host}


def test_regions_by_hand():
    found = G.region_seconds(a_trace(), ["jit_step"])
    assert found["seconds"] == {
        G.UNSCOPED: pytest.approx(0.002 + 0.001),  # the while's own, the gather
        "aiko.kv_view": pytest.approx(0.012),
        "aiko.mlp": pytest.approx(0.050 + 0.005 + 0.004 + 0.016),
        "aiko.kv_merge": pytest.approx(0.018)}
    assert found["adopted"] == {
        "aiko.kv_view": pytest.approx(0.002),
        "aiko.mlp": pytest.approx(0.005 + 0.004),
        "aiko.kv_merge": pytest.approx(0.016)}
    # every nanosecond of the program's operations is charged once
    assert sum(found["seconds"].values()) == pytest.approx(0.088 + 0.020)
    # a program with no scope in it reads nothing, and so does none
    assert G.region_seconds(a_trace(), ["jit_admit"]) is None
    assert G.region_seconds(a_trace(), ["jit_fused"]) is None


def test_regions_clip_to_the_span_and_average_over_chips():
    trace = a_trace()
    found = G.region_seconds(trace, ["jit_step"], (5 * MS, 130 * MS))
    assert found["seconds"]["aiko.kv_view"] == pytest.approx(0.005 + 0.002)
    assert found["seconds"]["aiko.mlp"] == pytest.approx(0.055 + 0.004 + 0.006)
    trace["devices"].append({"name": "/device:TPU:1", "modules": [],
                             "ops": []})
    halved = G.region_seconds(trace, ["jit_step"])
    assert halved["seconds"]["aiko.kv_merge"] == pytest.approx(0.009)


def spans(trace):
    """Two rounds over the trace above: 0-95 ms and 118-145 ms."""
    def round_of(start, plan, step, prefill, sync, deliver):
        rows, at = [], start
        for name, length in (("plan", plan), ("dispatch_step", step),
                             ("dispatch_prefill", prefill), ("sync", sync),
                             ("deliver", deliver)):
            rows.append([G.SPAN_PREFIX + name, at * MS, length * MS])
            at += length
        return [[G.SPAN_ROUND, start * MS, (at - start) * MS]] + rows
    trace["host"] += round_of(0, 1, 2, 1, 90, 1) + \
        round_of(118, 1, 2, 1, 20, 3)
    trace["host"].sort(key=lambda row: row[1])
    return trace


def test_idle_by_phase_by_hand():
    trace = spans(a_trace())
    # idle between operations: 70-72 (2 ms, sync), 90-100 (its middle,
    # 95, is where the first round ends: between the rounds), 140-150
    # (past the second round)
    table = G.idle_by_phase(trace)
    assert table["rounds"] == 2 and table["window_s"] == pytest.approx(0.150)
    assert table["idle_s"] == pytest.approx(0.002 + 0.010 + 0.010)
    assert table["by_phase"] == {
        "sync": {"seconds": pytest.approx(0.002), "gaps": 1},
        G.BETWEEN: {"seconds": pytest.approx(0.020), "gaps": 2}}
    # no program ran in 140-150 only
    assert table["no_program"] == {
        G.BETWEEN: {"seconds": pytest.approx(0.010), "gaps": 1}}
    # a gap whose middle lies in a phase other than sync
    trace["devices"][0]["ops"][0] = [2 * MS, 8 * MS, 2, 0]      # begins late
    table = G.idle_by_phase(trace)
    assert table["by_phase"]["dispatch_step"] == {
        "seconds": pytest.approx(0.002), "gaps": 1}
    no_decoder = a_trace()
    assert G.idle_by_phase(no_decoder) is None
    assert G.idle_by_phase(spans({**a_trace(), "devices": []})) is None


# -- the metric files -----------------------------------------------------------------

@pytest.fixture
def a_run(monkeypatch, tmp_path):
    trace = spans(a_trace())
    trace["devices"][0]["ops"][0] = [2 * MS, 8 * MS, 2, 0]
    monkeypatch.setattr(G, "of_run", lambda run: (trace, str(tmp_path)))
    return {"trace": {"devices": 1, "idle_gaps": [["pump", 0.02]]},
            "trace_counters": {"before": {"steps": 100, "rounds": 25},
                               "after": {"steps": 108, "rounds": 27}},
            "config": {"trace": {"programs": {"decode_step": ["jit_step"]}}}}


def test_the_metric_files_on_a_synthetic_run(a_run, tmp_path):
    def read(name):
        return run.load_module("layer_metrics", name).read(a_run)

    assert read("step_kv_view_ms") == pytest.approx(10.0 / 8)
    assert read("step_mlp_ms") == pytest.approx(75.0 / 8)
    assert read("step_kv_view_ms") + read("step_mlp_ms") + 18.0 / 8 + \
        3.0 / 8 == pytest.approx(106.0 / 8)
    assert read("step_attn_core_ms") == 0.0     # scoped program, none of it
    assert read("step_head_ms") == 0.0 and read("step_attn_proj_ms") == 0.0
    # idle 2 + 2 + 10 + 10 ms, 2 of it in sync, over two rounds
    for name in ("device_wait_on_host_ms.chat", "device_wait_on_host_ms.decode"):
        assert read(name) == pytest.approx(22.0 / 2)
    with open(tmp_path / "program_spans.json") as f:
        notes = json.load(f)
    regions = notes["decode_step_regions_ms"]
    assert regions["steps"] == 8
    assert sum(regions["seconds"].values()) == pytest.approx(106.0 / 8)
    assert regions["adopted"]["aiko.kv_merge"] == pytest.approx(16.0 / 8)
    assert notes["device_idle_by_phase"]["idle_gaps_of_reduce"] == [
        ["pump", 0.02]]
    assert notes["device_idle_by_phase"]["by_phase"]["sync"]["gaps"] == 1


def test_nothing_to_read_reads_none(monkeypatch, tmp_path):
    names = ["device_wait_on_host_ms.decode", "step_kv_view_ms",
             "step_attn_proj_ms", "step_attn_core_ms", "step_mlp_ms",
             "step_head_ms"]
    untraced = {"trace": None, "trace_counters": {}}
    on_the_cpu = {"trace": {"devices": 0}, "trace_counters": {}}
    for a_run in (untraced, on_the_cpu):
        for name in names:
            assert run.load_module("layer_metrics", name).read(a_run) is None
    # the parent of PR 24: a device trace with neither scopes nor spans
    bare = a_trace()
    for op in bare["devices"][0]["ops"]:
        op[2] = min(op[2], 1)
    monkeypatch.setattr(G, "of_run", lambda run: (bare, str(tmp_path)))
    parent = {"trace": {"devices": 1}, "trace_counters": {
        "before": {"steps": 0}, "after": {"steps": 8}},
        "config": {"trace": {"programs": {"decode_step": ["jit_step"]}}}}
    for name in names:
        assert run.load_module("layer_metrics", name).read(parent) is None
    assert not os.path.exists(tmp_path / "program_spans.json")


def test_of_run_takes_the_newest_trace_under_bench_out(monkeypatch, tmp_path,
                                                       xplane):
    import shutil
    for cell, age in (("old_cell", 100), ("new_cell", 0)):
        folder = tmp_path / ".bench_out" / cell / "trace" / "plugins" / \
            "profile" / "2026_01_01"
        folder.mkdir(parents=True)
        shutil.copy(xplane, folder / "host.xplane.pb")
        stamp = os.path.getmtime(xplane) - age
        os.utime(folder / "host.xplane.pb", (stamp, stamp))
    monkeypatch.setattr(G, "newest_trace", lambda root=None, real=G.newest_trace:
                        real(str(tmp_path)))
    trace, out_dir = G.of_run({"trace": {"devices": 1}})
    assert out_dir == str(tmp_path / ".bench_out" / "new_cell")
    assert trace["scopes"][2] == "aiko.mlp"
    assert G.of_run({"trace": {"devices": 1}})[0] is trace    # once a process
    assert G.of_run({"trace": None}) == (None, None)


# -- a recording from the chip ---------------------------------------------------------

def test_recorded_regions_from_the_chip():
    """0.2 s of `decode_saturated` on a v5e (PR 24), cut by
    `benchmark/trace/regions.py <trace> <out> 0.5 0.2`: rounds of four
    steps, 94 ms each, whole or in part."""
    with open(os.path.join(run.ROOT, "benchmark", "tests", "data",
                           "decode_saturated_regions_v5e.json")) as f:
        trace = json.load(f)
    assert trace["scopes"][:2] == [G.UNSCOPED, G.COMPILER]
    assert set(trace["scopes"][2:]) == {
        "aiko.kv_view", "aiko.attn_proj", "aiko.attn_core", "aiko.mlp",
        "aiko.head", "aiko.kv_merge"}
    (device,) = trace["devices"]
    span = G.traced_span(trace)
    assert span == (0, 0.2e9)
    step_seconds = sum(d for name, _, d in device["modules"]
                       if R.program_name(name) == "jit_step") / 1e9
    assert 0.15 < step_seconds < 0.2
    found = G.region_seconds(trace, ["jit_step"])
    seconds = found["seconds"]
    # the regions and the rest are the program's device time, within 2%
    assert sum(seconds.values()) == pytest.approx(step_seconds, rel=0.02)
    assert seconds.get(G.UNSCOPED, 0.0) < 0.10 * step_seconds
    # the MLP streams most of the weights and takes most of the time
    assert max(seconds, key=seconds.get) == "aiko.mlp"
    assert 0.25 < seconds["aiko.mlp"] / step_seconds < 0.40
    # the merge is the compiler's copies around its scatters
    assert found["adopted"]["aiko.kv_merge"] > 0.9 * seconds["aiko.kv_merge"]
    assert found["adopted"].get("aiko.mlp", 0.0) < 0.01 * seconds["aiko.mlp"]
    table = G.idle_by_phase(trace)
    assert table["rounds"] in (2, 3)
    assert set(table["by_phase"]) <= {
        "plan", "dispatch_step", "dispatch_prefill", "sync", "deliver",
        G.IN_ROUND, G.BETWEEN}
    busy, _ = R.union_seconds([(s, s + d) for s, d, _, _ in device["ops"]],
                              *span)
    assert table["idle_s"] == pytest.approx(0.2 - busy, rel=1e-6)
    for label, entry in table["no_program"].items():
        assert entry["seconds"] <= table["by_phase"][label]["seconds"] + 1e-9
