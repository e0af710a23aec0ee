"""run.py end to end: the CPU rehearsal of every cell's control flow (the
four-chip cell on four virtual devices), and the refusals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import harness

ROOT = harness.ROOT
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]


def run(args, cwd=ROOT, devices=1, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    full.update({"JAX_PLATFORMS": "cpu", **env})
    if devices > 1:
        full["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, os.path.join("perfbench", "run.py")] + args, cwd=cwd,
        env=full, capture_output=True, text=True, timeout=240)


def cell_args(name, trace=0, seconds="3", seed=5):
    return ["--workload", name, "--seed", str(seed), "--seconds", seconds,
            "--trace", str(trace)]


#: every cell of BENCHMARK.json, on as many virtual devices as it asks chips
#: for, every second one (in the file's order) traced: a cell a later PR adds
#: is rehearsed without a test file of its own
CELLS = [(w["name"], w["chips"], i % 2) for i, w in
         enumerate(harness.read_json("BENCHMARK.json")["workloads"])]


@pytest.mark.parametrize("name,devices,trace", CELLS)
def test_rehearsal_runs_the_control_flow_and_prints_no_metric(
        name, devices, trace):
    p = run(cell_args(name, trace) + ["--rehearse"], devices=devices)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    last = lines[-1]
    assert list(last) == RESULT_KEYS             # ``compared`` comes last
    assert last["compared"] and all(
        set(x) == {"value", "limit"} for x in last["compared"].values())
    assert [x.split()[2] for x in p.stderr.splitlines()[-len(last["compared"]):]
            ] == list(last["compared"])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["metrics"] == {}                 # never a CPU number
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == devices
    seen = [x for x in lines if "rehearsal_only" in x][0]["rehearsal_only"]
    if not trace:
        assert seen["setup_s"] > 0
    checked = [x for x in lines if x.get("phase") == "checked"][0]
    assert checked["problems"] == []
    if "reference" in checked:                   # serving: logits agree
        assert checked["reference"]["max_deficit"] < 1e-3
    else:                                        # training: loss agrees
        assert checked["loss"]["program"] == pytest.approx(
            checked["loss"]["reference"], rel=1e-4)
    out = os.path.join(ROOT, "perfbench_out", name, f"seed5-trace{trace}",
                       "records.json")
    assert os.path.exists(out)


@pytest.mark.parametrize("name", ["m7b-1chip.longprompt-batch",
                                  "m7b-train.pretrain-4k"])
def test_a_seed_beyond_int32_draws_weights_and_runs(name):
    """The driver's seeds are large; as the argument of the jitted weight
    initialisation 2**31 and above used to overflow."""
    p = run(cell_args(name, seed=2 ** 31 + 5) + ["--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.splitlines()[-1])
    assert last["correct"] is True and last["attempted"] > 0


def test_without_a_tpu_it_refuses_and_prints_no_result():
    p = run(cell_args("m7b-train.pretrain-4k"))
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(x.startswith("{") and "correct" in x
                   for x in p.stdout.splitlines())


def test_rehearsal_must_be_asked_for_explicitly():
    p = run(cell_args("m7b-train.pretrain-4k") + ["--rehearse"],
            JAX_PLATFORMS="")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_fewer_devices_than_the_cell_asks_for_is_refused():
    p = run(cell_args("m7b-tp4.chat-batch") + ["--rehearse"], devices=2)
    assert p.returncode != 0 and "needs 4 chip" in p.stderr
    assert not any("correct" in x for x in p.stdout.splitlines())


def test_unknown_workload_is_refused():
    p = run(cell_args("no-such-cell"))
    assert p.returncode != 0 and "no workload" in p.stderr


def test_alone_with_benchmark_json_it_exits_nonzero(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no program to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(cell_args("m7b-train.pretrain-4k") + ["--rehearse"],
            cwd=str(tmp_path))
    assert p.returncode != 0
    assert not any("correct" in x for x in p.stdout.splitlines())
