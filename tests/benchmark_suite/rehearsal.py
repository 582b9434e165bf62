"""Drives benchmark/run.py's `main` in-process under --rehearse."""

import json

from benchmark import run

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(cell, trace, capsys, hook=None, expect_correct=True, seconds=3):
    manifest = run.load_json("BENCHMARK.json")
    if cell not in [c["name"] for c in manifest["workloads"]]:
        import pytest
        pytest.skip(f"{cell} is not in BENCHMARK.json (PERF.md, open "
                    f"questions)")
    code = run.main(["--workload", cell, "--seed", str(2**31 + 17),
                     "--seconds", str(seconds), "--trace", str(trace),
                     "--rehearse"], hook=hook)
    assert code == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    extra = {"rehearsal"} | ({"breakdown"} if trace else set())
    assert KEYS <= set(result) <= KEYS | extra
    assert result["rehearsal"] is True and result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["correct"] is expect_correct, lines[-12:]
    assert any("check " in line and " limit " in line for line in lines)
    found = run.resolve(cell, rehearse=True)
    expected = found["per_layer"] if trace else found["end_to_end"]
    names = {m["name"] for m in expected if trace == 0
             or m["source"] != "device_trace"}
    assert set(result["metrics"]) == names
    for metric in expected:
        if metric["name"] in result["metrics"]:
            entry = result["metrics"][metric["name"]]
            assert entry["unit"] == metric["unit"]
            # no time, rate or share from a CPU run under a metric's name
            assert (entry["value"] is None) == \
                (metric["source"] != "program_counter")
    if expect_correct:
        assert result["attempted"] > 0 and result["failed"] == 0
    return result
