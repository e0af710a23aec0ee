"""A later PR adds a configuration, a traffic mix, a per-layer metric and a
cell as NEW files and NEW entries of BENCHMARK.json — nothing that exists is
edited (perfbench/README.md). Done here in a temporary copy, and rehearsed."""

import json
import os
import shutil
import subprocess
import sys

from perfbench import harness

ROOT = harness.ROOT


def test_a_new_cell_needs_new_files_and_new_entries_only(tmp_path):
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for folder, _, files in os.walk(tmp_path / "perfbench"):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                before[path] = f.read()

    def write(rel, data):
        path = tmp_path / "perfbench" / rel
        assert not path.exists(), rel          # new files only
        path.write_text(data if isinstance(data, str) else json.dumps(data))

    config = harness.read_json(
        "perfbench/configs/mistral-7b-v0.3-serve-1chip.json")
    config["name"] = "dummy-config"
    write("configs/dummy-config.json", config)
    write("traffic/dummy-mix.json", {
        "loop": "batch", "warmup_requests": 1, "clients": 2,
        "requests_per_s": {"param": "batch_rps"},
        "prompt_tokens": {"dist": "uniform", "min": 8, "max": 24},
        "output_tokens": {"dist": "fixed", "value": 8}})
    write("cells/dummy.cell.json", {"params": {"batch_rps": 2}})
    write("testdata/rehearsal/dummy.cell.json", harness.read_json(
        "perfbench/testdata/rehearsal/m7b-1chip.longprompt-batch.json")
        | {"traffic": {}, "params": {}})
    write("layer_metrics/dummy.count.py",
          '"""Requests the run made."""\n\n\n'
          "def read(obs):\n    return len(obs.requests)\n")

    bench = harness.read_json("BENCHMARK.json")
    bench["configs"].append({
        "name": "dummy-config", "source": config["source"],
        "file": "perfbench/configs/dummy-config.json",
        "reduced": ["num_hidden_layers"], "why": "a dummy"})
    bench["workloads"].append({
        "name": "dummy.cell", "config": "dummy-config",
        "traffic": "dummy-mix", "chips": 1, "why": "a dummy"})
    bench["per_layer"].append({
        "name": "dummy.count", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "benchmark", "moves": "serve_tok_s",
        "workloads": ["dummy.cell"]})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].append("dummy.cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)   # the program
    lines = {}
    for trace in (0, 1):
        p = subprocess.run(
            [sys.executable, "perfbench/run.py", "--workload", "dummy.cell",
             "--seed", "1", "--seconds", "3", "--trace", str(trace),
             "--rehearse"], cwd=tmp_path, env=env, capture_output=True,
            text=True, timeout=240)
        assert p.returncode == 0, p.stderr[-2000:]
        out = [json.loads(x) for x in p.stdout.splitlines()
               if x.startswith("{")]
        assert out[-1]["correct"] is True and out[-1]["attempted"] == 7
        lines[trace] = [x for x in out if "rehearsal_only" in x][0][
            "rehearsal_only"]
    assert set(lines[0]) == {"serve_tok_s", "setup_s"}
    assert lines[1] == {"dummy.count": 7.0}
    for path, data in before.items():          # nothing existing was edited
        with open(path, "rb") as f:
            assert f.read() == data, path
