"""A later PR adds files and entries and edits no file that is there: a new
configuration, a new traffic mix and a new per-layer metric, dropped in as
files beside a copy of the benchmark, run under --rehearse."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

from benchmark import run

ROOT = run.ROOT


def test_new_configuration_traffic_and_metric_run_with_no_edit(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.relpath(os.path.join(folder, name), copy)
              for folder, _, names in os.walk(copy) for name in names}

    donor = run.load_json("benchmark", "configs", "mistral-7b-v0.3-d16.json")
    config = run.merged(donor, donor["rehearse"])
    config["rehearse"] = {}
    (copy / "benchmark" / "configs" / "dropped-in.json").write_text(
        json.dumps(config))
    (copy / "benchmark" / "traffic" / "dropped_in_mix.json").write_text(
        json.dumps({"generator": "backlog", "why": "a test", "who": "a test",
                    "reference_samples": 2, "parameters": {
                        "max_outstanding": 8, "supply_per_s": 2000.0,
                        "preroll_s": 0.5, "fields": {
                            "prompt_tokens": {"dist": "uniform", "min": 4,
                                              "max": 20},
                            "output_tokens": {"dist": "fixed", "value": 6}}}}))
    (copy / "benchmark" / "layer_metrics" / "rounds_per_request_new.py"
     ).write_text(
        "from benchmark import readers\n\n\ndef read(run):\n"
        "    return readers.ratio(readers.delta(run, 'rounds'),\n"
        "                         readers.delta(run, 'completed'))\n")
    manifest = run.load_json("BENCHMARK.json")
    manifest["configs"].append({
        "name": "dropped-in", "source": config["source"], "reduced": [],
        "file": "benchmark/configs/dropped-in.json", "why": "a test"})
    manifest["workloads"].append({
        "name": "dropped_in", "config": "dropped-in",
        "traffic": "dropped_in_mix", "chips": 1, "why": "a test"})
    for metric in manifest["end_to_end"]:
        if metric["name"] == "llm_tokens_per_s":
            metric["workloads"].append("dropped_in")
    manifest["per_layer"].append({
        "name": "rounds_per_request.new", "unit": "rounds", "better": "lower",
        "source": "program_counter", "layer": "scheduling",
        "moves": "llm_tokens_per_s", "workloads": ["dropped_in"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))

    environment = os.environ | {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    done = subprocess.run(
        [sys.executable, str(copy / "benchmark" / "run.py"), "--workload",
         "dropped_in", "--seed", "5", "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=environment,
        cwd=copy, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["attempted"] > 0
    assert result["metrics"]["rounds_per_request.new"]["value"] > 0

    for name in before:        # what was there is as it was
        assert filecmp.cmp(copy / name, os.path.join(ROOT, name),
                           shallow=False), name


def test_refuses_to_measure_without_a_chip_and_without_the_program(tmp_path):
    """No result line: jax held to the CPU; and a directory that holds only
    BENCHMARK.json and the benchmark's own files."""
    environment = os.environ | {"JAX_PLATFORMS": "cpu"}
    command = ["benchmark/run.py", "--workload", "the-first-cell", "--seed",
               "1", "--seconds", "1", "--trace", "0"]
    manifest = run.load_json("BENCHMARK.json")
    command[2] = manifest["workloads"][0]["name"]
    done = subprocess.run([sys.executable, *command], capture_output=True,
                          text=True, env=environment, cwd=ROOT, timeout=300)
    assert done.returncode != 0 and "TPU" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())

    bare = tmp_path / "bare"
    shutil.copytree(os.path.join(ROOT, "benchmark"), bare / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    environment.pop("PYTHONPATH", None)
    done = subprocess.run([sys.executable, *command, "--rehearse"],
                          capture_output=True, text=True, env=environment,
                          cwd=bare, timeout=300)
    assert done.returncode != 0
    assert not any(line.startswith("{") for line in done.stdout.splitlines())
