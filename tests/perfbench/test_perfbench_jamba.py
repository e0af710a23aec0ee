"""The Jamba configuration's benchmark files: the selective scan's required
work by hand, the three new readers over a hand-written trace (and their
silence on a program that writes no state record, as the parent commit), the
configuration's file against the catalog's sizes, the traffic mix as the
issue names it. (The plain reference against the program, logit by logit:
``tests/test_jamba_model.py``.)"""

import pytest

from perfbench import harness, loadgen, program_trace, state_work

from test_perfbench_program_trace import (dispatch_spans, observe,
                                          plant_xplane, record, summary)

CELL = "jamba2-1chip.reason-batch"
READERS = ["kernel.mamba_ragged_scan.time_share", "mamba_ragged_scan_roofline",
           "state.rows_per_round"]


def reader(name):
    return harness.load_module(f"perfbench/layer_metrics/{name}.py")


def state_record(n, prefill, decode, row_rounds, resets):
    return dict(record(n, prefill, decode, 70_000, 1_100_000),
                token_slots=2048, state_row_rounds=row_rounds,
                state_resets=resets, state_bytes_per_row=358_400)


def test_scan_required_work_by_hand():
    config = harness.load_cell(CELL).config
    assert state_work.mamba_layers(config) == 26
    # a dispatch of 16 micro-rounds, 128 rows decoding: 2,048 row-rounds
    stats = {"state_row_rounds": 2048, "prefill_tokens": 0,
             "decode_tokens": 2048}
    w = state_work.required_work(stats, config)
    # 26 layers x (2,048 x a float32 state of 16 x 5,120 in and out
    # + 2,048 tokens x (u, delta, y of 5,120 + B, C of 16) x bf16)
    assert w["bytes"] == 26 * (2048 * 2 * 16 * 5120 * 4
                               + 2048 * (3 * 5120 + 32) * 2) == 36_535_795_712
    assert w["flops"] == 26 * 6.0 * 2048 * 16 * 5120
    # memory-bound by far
    assert w["flops"] / 197e12 < 0.01 * w["bytes"] / 819e9
    twice = state_work.required_work({k: 2 * v for k, v in stats.items()},
                                     config)
    assert twice["bytes"] == 2 * w["bytes"] and twice["flops"] == 2 * w["flops"]
    # a prefill chunk moves the state once for its 16 tokens
    chunk = state_work.required_work(
        {"state_row_rounds": 1, "prefill_tokens": 16, "decode_tokens": 0},
        config)
    assert chunk["bytes"] == 26 * (2 * 16 * 5120 * 4 + 16 * 15392 * 2)


def state_trace(with_state=True):
    """Two complete dispatches of a model with a state a row, and a cut one."""
    rec_a = state_record(7, 300, 1700, 1900, 3)
    rec_b = state_record(8, 100, 1948, 2040, 1)
    if not with_state:
        for rec in (rec_a, rec_b):
            for key in ("state_row_rounds", "state_resets",
                        "state_bytes_per_row"):
                del rec[key]
    spans = dispatch_spans(100, rec_a) + dispatch_spans(1200, rec_b)
    spans += [["cbe.fence", 2300, 100, {}], ["cbe.unpack", 2400, 20, {}]]
    ops = [["mamba_ragged_scan.2", 210, 390], ["fusion.1", 600, 390],
           ["mamba_ragged_scan.2", 1310, 390], ["fusion.1", 1700, 390],
           ["fusion.1", 2300, 90]]
    return {"ops": ops, "spans": spans, "window": [0, 2500]}


def state_summary(kernel_s=0.9):
    s = summary()
    s.op_seconds = {"mamba_ragged_scan.2": kernel_s, "fusion.1": 1.2}
    s.busy_s, s.dispatches = 3.0, 3.0
    return s


def test_readers_over_a_hand_trace(tmp_path, monkeypatch, capsys):
    cell = harness.load_cell(CELL)
    plant_xplane(tmp_path, cell.name)
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(program_trace, "load", lambda p: state_trace())
    monkeypatch.setattr(program_trace, "_CACHE", {})
    obs = observe(cell, state_summary())
    got = {name: reader(name).read(obs) for name in READERS}
    capsys.readouterr()
    assert got["kernel.mamba_ragged_scan.time_share"] == \
        pytest.approx(100 * 0.9 / 3.0)
    assert got["state.rows_per_round"] == pytest.approx(3940 / 32)
    work = state_work.required_work(
        {"state_row_rounds": 3940, "prefill_tokens": 400,
         "decode_tokens": 3648}, cell.config)
    least = work["bytes"] / 819e9 / 2               # a dispatch
    assert got["mamba_ragged_scan_roofline"] == \
        pytest.approx(100 * least / (0.9 / 3.0))
    # a kernel that is not in the trace: no share, no roofline
    bare = observe(cell, state_summary(kernel_s=0.0))
    assert reader("mamba_ragged_scan_roofline").read(bare) is None
    assert reader("kernel.mamba_ragged_scan.time_share").read(bare) is None


def test_new_readers_are_silent_on_a_program_without_a_state_record(
        tmp_path, monkeypatch, capsys):
    """The parent commit, or any other family: ``cbe.dispatch`` carries its
    ten keys and nothing of the state; a run without a trace likewise."""
    cell = harness.load_cell(CELL)
    plant_xplane(tmp_path, cell.name)
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(program_trace, "load",
                        lambda p: state_trace(with_state=False))
    monkeypatch.setattr(program_trace, "_CACHE", {})
    obs = observe(cell, state_summary())
    assert reader("state.rows_per_round").read(obs) is None
    assert reader("mamba_ragged_scan_roofline").read(obs) is None
    capsys.readouterr()
    untraced = observe(cell, None)
    assert all(reader(name).read(untraced) is None for name in READERS)


def test_the_configuration_is_the_catalogs_row_whole():
    import json
    config = harness.load_cell(CELL).config
    assert config["reduced"] == {}
    assert (config["num_hidden_layers"], config["hidden_size"],
            config["vocab_size"]) == (28, 2560, 65536)
    assert config["serving"] == {
        "dtype": "bfloat16", "state_dtype": "float32", "num_slots": 128,
        "max_seq_len": 4096, "kv_pool_tokens": 524288, "prefix_cache": False,
        "max_queue_depth": 128}
    # the catalog's copy of the published config.json, where this machine
    # has it: every key at the top level with its value
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("no catalog on this machine")
    row, = [r for r in rows if r["name"] == "AI21-Jamba2-3B"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == value, key


def test_the_mix_is_the_issues():
    cell = harness.load_cell(CELL)
    t = cell.traffic
    assert (t["loop"], t["schedule_seed"], t["warmup_requests"]) == \
        ("batch", 33, 8)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 192,
                                  "sigma": 0.8, "min": 16, "max": 2048}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 0.6, "min": 64, "max": 1536}
    assert t["length_sampling"] == {"kind": "stratified", "block": 16}
    assert "shared_prefix" not in t
    assert cell.params["clients"] == 128 == cell.config["serving"]["num_slots"]
    # every request fits the served context
    assert 2048 + 1536 <= cell.config["serving"]["max_seq_len"]
