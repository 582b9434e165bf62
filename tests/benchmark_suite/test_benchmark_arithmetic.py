"""Generators, percentile and failure arithmetic, operations and bytes."""

import collections
import json
import os

import pytest

from benchmark import draws, loop, ops_bytes, run, stats

ROOT = run.ROOT


def _traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
        return json.load(f)


TRAFFIC = sorted(os.listdir(os.path.join(ROOT, "benchmark", "traffic")))


@pytest.mark.parametrize("name", TRAFFIC)
def test_generators_repeat_in_the_seed_and_differ_across_seeds(name):
    traffic = _traffic(name)
    generate = run.load_module("generators", traffic["generator"]).generate
    first = generate(traffic["parameters"], 2**31 + 11, 20.0)
    again = generate(traffic["parameters"], 2**31 + 11, 20.0)
    other = generate(traffic["parameters"], 12, 20.0)
    assert first == again
    # a mix that fixes its order offers every seed the same requests
    assert (first["requests"] == other["requests"]) == \
        ("order_draw" in traffic["parameters"])
    assert len(first["requests"]) == len(other["requests"])
    ids = [r["id"] for r in first["requests"]]
    assert len(set(ids)) == len(ids)


@pytest.mark.parametrize("name", TRAFFIC)
def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order(name):
    traffic = _traffic(name)
    generate = run.load_module("generators", traffic["generator"]).generate
    plans = [generate(traffic["parameters"], seed, 20.0)["requests"]
             for seed in (1, 2)]
    for field in traffic["parameters"].get("fields", {}):
        spec = traffic["parameters"]["fields"][field]
        counts = [collections.Counter(r[field] for r in plan)
                  for plan in plans]
        assert counts[0] == counts[1]
        low = spec.get("min", spec.get("value"))
        high = spec.get("max", spec.get("value"))
        assert all(low <= r[field] <= high for r in plans[0])
    preroll = traffic["parameters"].get("preroll_s", 0.0)
    gaps = [sorted([plan[0]["due"] + preroll] + [
        b["due"] - a["due"] for a, b in zip(plan, plan[1:])])
        for plan in plans]
    assert gaps[0] == pytest.approx(gaps[1], abs=1e-6)
    assert all(a["due"] <= b["due"] for a, b in zip(plans[0], plans[0][1:]))


def test_quantiles_keep_the_distribution():
    values = draws.quantiles({"dist": "lognormal", "median": 384,
                              "sigma": 0.9, "min": 32, "max": 1536}, 201)
    assert values[100] == 384 and values.min() >= 32 and values.max() <= 1536
    gaps = draws.quantiles({"dist": "exponential", "mean": 0.25}, 1000)
    assert gaps.mean() == pytest.approx(0.25, rel=0.01)
    with pytest.raises(ValueError):
        draws.quantiles({"dist": "zipf"}, 3)


def test_percentile_and_misses():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.percentile([10.0], 95) == 10.0
    assert stats.percentile([0.0, 10.0], 95) == pytest.approx(9.5)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    # a failed request is a miss: it stays in the denominator, at the time
    # the run gave up, and so weighs on the tail
    served = [0.1] * 18
    assert stats.percentile(stats.with_misses(served + [None, None], 35.0),
                            95) > 30.0
    assert stats.share_within(served + [None, None], 0.15) == 0.9
    assert stats.halves_ratio([1, 2, 11, 12], [1.0, 1.0, 2.0, 2.0], 20.0) == 2.0


def test_token_gaps_over_all_tokens_and_by_request():
    from benchmark import readers
    record = lambda first, last, tokens, failed=False: {
        "first": first, "last": last, "tokens": tokens, "failed": failed}
    records = [record(1.0, 2.0, 11), record(0.0, 9.0, 91),
               record(3.0, 3.0, 1)]
    run_ = {"records": records, "miss_s": 50.0}
    # 10 s over 100 gaps, the request of one token left out; by request
    # both read 100 ms a token
    assert readers.mean_token_gap_ms(run_) == pytest.approx(100.0)
    assert readers.per_token_seconds(records) == pytest.approx([0.1, 0.1])
    # a stalled round weighs by its length over all the gaps, while it
    # moves the short request's own reading by a tenth of itself
    records[0]["last"] += 0.1
    assert readers.mean_token_gap_ms(run_) == pytest.approx(101.0)
    assert readers.per_token_seconds(records)[0] == pytest.approx(0.11)
    # a failed request is one gap as long as the time the run gave up
    records.append(record(None, None, 0, failed=True))
    assert readers.mean_token_gap_ms(run_) == pytest.approx(60_100 / 101)
    assert readers.mean_token_gap_ms({"records": [], "miss_s": 1.0}) is None
    # the median over requests hardly sees the stalled one; a failed
    # request is in it as a miss
    median = run.load_module("end_to_end", "llm_tpot_p50_ms").read
    assert median(run_) == pytest.approx(110.0)
    records.append(record(None, None, 0, failed=True))
    assert median(run_) == pytest.approx(25_055.0)
    assert median({"records": [], "miss_s": 1.0}) is None


def _plan(requests, outstanding=None):
    return {"requests": requests, "max_outstanding": outstanding,
            "preroll_s": 0.0}


def test_window_counts_a_request_without_a_result_as_failed():
    from benchmark import readers
    window = loop.Window(_plan([{"id": "a", "due": 0.0},
                                {"id": "b", "due": 0.0},
                                {"id": "late", "due": 0.0},
                                {"id": "refused", "due": 0.0}]),
                         seconds=0.05, drain_s=0.05)
    window.start()
    assert [r["id"] for r in window.due()] == ["a", "b", "late", "refused"]
    for rid in ("a", "b", "late"):
        window.sent(rid)
    window.refused("refused")
    window.token("a")
    window.done("a")
    window.token("b")
    window.done("b")
    while not window.finished():
        pass
    records = window.close()
    assert len(records) == 4
    assert sorted(r["failed"] for r in records) == [False, False, True, True]
    run_ = {"records": records, "miss_s": 0.1}
    assert readers.p95_with_misses_ms(
        run_, readers.latencies(records, "due", "done")) == pytest.approx(
        100.0, rel=0.2)


def test_backlog_hands_over_as_requests_finish_and_counts_what_finished():
    requests = [{"id": f"r{i}", "due": 0.0} for i in range(6)]
    window = loop.Window(_plan(requests, outstanding=2), seconds=0.05)
    window.start()
    first = window.due()
    assert [r["id"] for r in first] == ["r0", "r1"]
    for request in first:
        window.sent(request["id"])
    assert window.due() == []
    window.done("r0")
    assert [r["id"] for r in window.due()] == ["r2"]
    window.sent("r2")
    while not window.finished():
        pass
    assert len(window.close()) == 1 and not window.exhausted


def test_operations_and_bytes_by_hand():
    mistral = run.load_json("benchmark", "configs", "mistral-7b-v0.3-d16.json")
    # 4096 x (4096 + 2 x 1024) + 4096 x 4096 + 3 x 4096 x 14336 + 2 x 4096
    assert ops_bytes.decoder_layer_params(mistral) == 218_112_000
    params = ops_bytes.decoder_params(mistral)
    assert params["total"] == 16 * 218_112_000 + 2 * 32768 * 4096 + 4096
    assert params["total"] * 2 == pytest.approx(7.5e9, rel=0.01)
    assert ops_bytes.kv_bytes_per_token(mistral, 2) == 64 * 1024
    step = ops_bytes.decode_step(mistral, 2, slots=24, live_tokens=10_000)
    assert step["bytes"] == params["streamed"] * 2 + 65536 * 10_024
    peaks = run.load_json("benchmark", "peaks.json")["device_kinds"][
        "TPU v5 lite"]
    bound = ops_bytes.roofline_seconds(step, peaks)
    assert bound["bound"] == "bytes"
    assert bound["seconds"] == pytest.approx(step["bytes"] / 819e9)
