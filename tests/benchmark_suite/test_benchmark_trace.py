"""The reduction from a trace to busy and idle time, device time by
program and by operation, and idle gaps by what the host was doing."""

import json
import os

import pytest

from benchmark import run
from benchmark.trace import reduce as R

MS = 1e6


def _planes():
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_step(111)", 10 * MS, 30 * MS],
            ["jit_step(222)", 50 * MS, 20 * MS],
            ["jit_admit(333)", 80 * MS, 10 * MS]]},
        {"name": "XLA Ops", "events": [
            ["%while.1", 10 * MS, 30 * MS],      # holds the two fusions
            ["%fusion.1", 12 * MS, 10 * MS],
            ["%fusion.2", 25 * MS, 10 * MS],
            ["%fusion.1", 50 * MS, 20 * MS],
            ["%copy.3", 80 * MS, 10 * MS]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["bench.traced", 0.0, 100 * MS],
        ["bench.pump", 5 * MS, 42 * MS],
        ["bench.generator", 44 * MS, 2 * MS],     # nested: the narrower wins
        ["bench.pump", 72 * MS, 20 * MS],
        ["$something.py:1 else", 0.0, 100 * MS]]}]}
    other = {"name": "/device:CUSTOM:Megascale Trace", "lines": []}
    return [device, host, other]


def test_reduction_by_hand():
    out = R.reduce(_planes(), {"bench.pump": "pump",
                               "bench.generator": "generator"})
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.060)
    assert out["programs"]["jit_step"] == {
        "count": 2, "seconds": pytest.approx(0.050)}
    assert out["programs"]["jit_admit"]["seconds"] == pytest.approx(0.010)
    ops = dict(out["device_ops"])
    assert ops["%fusion.1"] == pytest.approx(0.030)
    assert ops["%while.1"] == pytest.approx(0.010)    # its own time only
    gaps = dict(out["idle_gaps"])
    # idle, each gap named by the narrowest host span over its middle:
    # 0-10 and 70-80 pump, 40-50 generator (inside a pump), 90-100 none
    assert sum(gaps.values()) == pytest.approx(0.100 - 0.060)
    assert gaps == {"pump": pytest.approx(0.020),
                    "generator": pytest.approx(0.010),
                    "unlabelled": pytest.approx(0.010)}
    assert out["host_spans"]["bench.pump"]["count"] == 2


def test_reduction_clips_to_the_traced_span_and_averages_over_chips():
    planes = _planes()
    planes[1]["lines"][0]["events"][0] = ["bench.traced", 20 * MS, 40 * MS]
    second = json.loads(json.dumps(planes[0]))
    second["name"] = "/device:TPU:1"
    second["lines"][1]["events"] = [["%fusion.9", 20 * MS, 40 * MS]]
    second["lines"][0]["events"] = [["jit_step(111)", 20 * MS, 40 * MS]]
    out = R.reduce(planes + [second])
    assert out["devices"] == 2 and out["window_s"] == pytest.approx(0.040)
    # chip 0 is busy 20-40 and 50-60, chip 1 throughout
    assert out["busy_s"] == pytest.approx((0.030 + 0.040) / 2)
    assert out["programs"]["jit_step"]["seconds"] == pytest.approx(
        (0.030 + 0.040) / 2)


def test_a_trace_with_no_device_plane_reads_nothing():
    out = R.reduce([_planes()[1]])
    assert out["devices"] == 0 and out["busy_s"] == 0.0
    from benchmark import readers
    assert readers.idle_share({"trace": out}) is None
    assert R.program_name("jit_fused(12862814909338201934)") == "jit_fused"


SPEECH_LABELS = {"bench.asr_batch": "collate_upload_fetch",
                 "bench.engine_step": "pipeline",
                 "bench.generator": "generator"}
RECORDED = ["chat_open_loop_v5e.trace.json", "speech_live_1s_v5e.trace.json"]


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_trace_from_the_chip(name):
    """A fraction of a second on a v5e (PR 23), cut by
    benchmark/trace/inspect.py, which leaves the shortest device
    operations out: chat_open_loop, and the speech pipeline under the
    live-stream traffic that this PR withdrew (PERF.md, section 4), kept
    for its nested operations (the decode tail is one `%while`)."""
    with open(os.path.join(run.ROOT, "benchmark", "tests", "data",
                           name)) as f:
        planes = json.load(f)
    if name.startswith("speech"):
        programs, labels, length = ["jit_fused"], SPEECH_LABELS, 0.25
    else:
        trace = run.load_json("benchmark", "configs",
                              "mistral-7b-v0.3-d16.json")["trace"]
        programs, labels, length = trace["programs"]["decode_step"], \
            trace["idle_labels"], 0.6
    out = R.reduce(planes, labels)
    assert out["devices"] == 1
    assert 0.0 < out["busy_s"] < out["window_s"] <= length + 1e-6
    for program in programs:
        assert out["programs"][program]["count"] >= 1
        assert out["programs"][program]["seconds"] <= out["busy_s"] * 1.05
    idle = sum(seconds for _, seconds in out["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
    assert {label for label, _ in out["idle_gaps"]} <= set(
        labels.values()) | {"unlabelled"}
    assert out["device_ops"][0][1] >= out["device_ops"][-1][1] > 0.0
