"""BENCHMARK.json against the benchmark's contract, and every name in it
resolved to the data file or reader it stands for."""

import copy
import json
import os
import re

import pytest

from perfbench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_./-]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: a key that matches is a width: never under ``reduced``, and stated a second
#: time in the configuration's ``testdata/published`` file
WIDTH = re.compile(r"(hidden_size|intermediate|_dim$|_rank$|head_dim|per_tok|"
                   r"window|state)")
#: what only the program may decide: a configuration that pins one of these
#: hides a changed default from the benchmark
ENGINE_DEFAULTS = {"chunk", "step_tokens", "page_size", "num_pages",
                   "unified", "fused_tail", "speculative", "spec_k",
                   "check_invariants", "k_steps", "remat", "remat_policy",
                   "zero_gather", "pipeline_schedule", "virtual_pp"}


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)       # read at collection: it names the cases


@pytest.fixture(scope="module")
def bench():
    return copy.deepcopy(BENCH)


def cells_of(metric, bench):
    return metric.get("workloads", [w["name"] for w in bench["workloads"]])


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PLAIN_PATH.match(p) and len(p) <= 200 and ".." not in p
               and not p.startswith("/") for p in bench["paths"])
    assert len(bench["command"]) <= 32
    for arg in bench["command"]:
        assert not arg.startswith("/") and ".." not in arg
        if "/" in arg:
            assert any(arg.startswith(p + "/") for p in bench["paths"])
    n = len(bench["workloads"])
    assert 2 <= n <= 24 and 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, n // 4)
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])


def test_names_are_plain_and_used_once(bench):
    names = [x["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in bench[group]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for x in bench["configs"] + bench["workloads"]:
        assert len(x["why"]) <= 200, x["name"]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_files_under_paths_have_plain_names(bench):
    for path in bench["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in folder:
                continue
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert PLAIN_PATH.match(rel), rel


def check_configuration(c, config, published, bench):
    """One entry ``c`` of ``configs``, the file it names (``config``) and the
    second statement of its published widths (``published``:
    ``perfbench/testdata/published/<name>.json``)."""
    assert c["name"] in {w["config"] for w in bench["workloads"]}
    assert c["source"].startswith("https://")
    assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    assert config["name"] == c["name"] and config["source"] == c["source"]
    assert sorted(config["reduced"]) == sorted(c["reduced"])
    for key in c["reduced"]:
        assert not WIDTH.search(key), f"{key} is a width: never reduced"
        assert config["reduced"][key]["to"] == config[key]
    pinned = ENGINE_DEFAULTS & (set(config) | set(config.get("serving", {}))
                                | set(config.get("train", {})))
    assert not pinned, f"{c['name']} pins {pinned}"
    assert os.path.exists(os.path.join(ROOT, config["reference"]))
    assert os.path.exists(os.path.join(
        ROOT, "perfbench", "adapters", config["adapter"] + ".py"))
    # the widths are the published ones, stated a second time beside the
    # tests: a width edited in the configuration's file alone shows here
    assert published["source"] == c["source"]
    widths = published["widths"]
    must = {"hidden_size", "num_attention_heads", "num_key_value_heads"} \
        | {k for k in config if WIDTH.search(k)}
    assert must <= set(widths), f"no published {sorted(must - set(widths))}"
    for key, value in widths.items():
        assert key in config, f"{key} is not at the file's top level"
        assert config[key] == value, \
            f"{key}: the file runs {config[key]!r}, published {value!r}"
        assert key not in c["reduced"], f"{key} is published AND reduced"


def configuration(name, bench):
    c = {c["name"]: c for c in bench["configs"]}[name]
    return c, harness.read_json(c["file"]), harness.read_json(
        "perfbench", "testdata", "published", name + ".json")


def test_each_configuration_has_a_file_of_its_own(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_configs_hold_what_is_run_and_pin_no_engine_default(bench, name):
    check_configuration(*configuration(name, bench), bench)


def test_a_window_or_state_size_under_reduced_is_refused(bench):
    c, config, published = configuration(bench["configs"][0]["name"], bench)
    for key, to in (("sliding_window", 1024), ("ssm_state_size", 8)):
        one, cfg = copy.deepcopy((c, config))
        one["reduced"] = c["reduced"] + [key]
        cfg[key] = to
        cfg["reduced"][key] = {"from": 4096, "to": to, "why": "to fit"}
        with pytest.raises(AssertionError, match=f"{key} is a width"):
            check_configuration(one, cfg, published, bench)


def test_a_width_that_differs_from_the_published_one_is_refused(bench):
    c, config, published = configuration(bench["configs"][0]["name"], bench)
    check_configuration(c, config, published, bench)
    for key, value in (("hidden_size", 2048), ("num_key_value_heads", 4),
                       ("rope_theta", 10000.0), ("sliding_window", 4096)):
        with pytest.raises(AssertionError, match=f"{key}: the file runs"):
            check_configuration(c, config | {key: value}, published, bench)
    # a width the file has and the published statement leaves out
    with pytest.raises(AssertionError, match="no published .*head_dim"):
        check_configuration(c, config | {"head_dim": 64}, published, bench)


def test_every_cell_resolves_and_reports_enough(bench):
    for w in bench["workloads"]:
        for rehearse in (False, True):
            cell = harness.load_cell(w["name"], rehearse=rehearse)
            assert cell.chips == w["chips"] == cell.config["chips"]
            assert cell.traffic["loop"] in ("open", "batch", "train")
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_metrics_are_well_formed_and_have_readers(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for group, folder in (("end_to_end", "e2e_metrics"),
                          ("per_layer", "layer_metrics")):
        for m in bench[group]:
            assert m["source"] in SOURCES
            assert m["better"] in ("lower", "higher")
            assert set(cells_of(m, bench)) <= cells
            path = os.path.join("perfbench", folder,
                                m["name"].split("-")[0] + ".py")
            assert callable(harness.load_module(path).read), m["name"]
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])
    # a layer's name is a plain word, and PERF.md section 3 has it
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf_md = f.read()
    for m in bench["per_layer"]:
        assert LAYER.match(m["layer"]), (m["name"], m["layer"])
        assert f"`{m['layer']}`" in perf_md, m["layer"]
    roofs = [m for m in bench["per_layer"] if "roofline" in m["name"]]
    assert all(m["name"].endswith("_roofline") and m["unit"] == "%"
               for m in roofs)


def test_every_reader_file_is_listed(bench):
    listed = {(g, m["name"].split("-")[0]) for g in ("end_to_end", "per_layer")
              for m in bench[g]}
    for group, folder in (("end_to_end", "e2e_metrics"),
                          ("per_layer", "layer_metrics")):
        for name in os.listdir(os.path.join(ROOT, "perfbench", folder)):
            if name.endswith(".py"):
                assert (group, name[:-3]) in listed, name


def test_only_adapters_import_the_program(bench):
    pattern = re.compile(r"^\s*(from|import)\s+paddle_tpu\b", re.M)
    for path in bench["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, path)):
            for name in files:
                if not name.endswith(".py"):
                    continue
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                allowed = rel.startswith(("perfbench/adapters/",
                                          "tests/perfbench/"))
                with open(os.path.join(folder, name)) as f:
                    if pattern.search(f.read()):
                        assert allowed, f"{rel} imports paddle_tpu"
