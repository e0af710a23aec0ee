"""The Trinity-Mini configuration's benchmark files: the expert layer's
required work by hand, its readers over a hand-written trace (and their
silence on a program that writes no such counters, as the parent commit),
and the comparison that decides ``correct`` catching planted faults of the
program on the CPU. (The plain reference against the program, logit by
logit: ``tests/test_afmoe_serving.py``.)"""

import time

import pytest

from perfbench import checks, expert_work, harness, loadgen, program_trace

from test_perfbench_program_trace import (dispatch_spans, observe,
                                          plant_xplane, record, summary)

CELL = "tm-1chip.mixedlen-batch"
READERS = ["moe.experts_hit_share", "moe.max_expert_load",
           "kernel.ragged_paged_attention.window_skipped_share",
           "kernel.moe_grouped_matmul.time_share",
           "moe_grouped_matmul_roofline"]


def reader(name):
    return harness.load_module(f"perfbench/layer_metrics/{name}.py")


def test_required_work_by_hand():
    config = harness.load_cell(CELL).config
    stats = {"experts_hit": 111, "expert_assignments": 256}
    w = expert_work.required_work(stats, config)
    # 111 experts x (gate, up, down) x 2048 x 1024 x bf16, and 256 rows in
    # and out of each product: (2048 + 1024) x 3 x bf16
    assert w["bytes"] == 111 * 3 * 2048 * 1024 * 2 + 256 * 3 * 3072 * 2 \
        == 1_401_421_824
    assert w["flops"] == 2 * 256 * 3 * 2048 * 1024 == 3_221_225_472
    twice = expert_work.required_work(
        {k: 2 * v for k, v in stats.items()}, config)
    assert twice["bytes"] == 2 * w["bytes"]             # linear


def routed_trace():
    """Two complete dispatches of a model with window layers and experts,
    and a cut one whose ``cbe.unpack`` still carries its stats."""
    rec_a = dict(record(7, 496, 16, 2000, 598432), window_skipped_pages=500)
    rec_b = dict(record(8, 500, 12, 1000, 401568), window_skipped_pages=0)
    spans = dispatch_spans(100, rec_a) + dispatch_spans(1200, rec_b)
    spans += [["cbe.fence", 2300, 100, {}], ["cbe.unpack", 2400, 20, {}]]
    stats = [{"expert_calls": 64, "experts_hit": 7000,
              "expert_assignments": 16000, "max_expert_load": 448},
             {"expert_calls": 64, "experts_hit": 6000,
              "expert_assignments": 15000, "max_expert_load": 400},
             {"expert_calls": 64, "experts_hit": 5000,
              "expert_assignments": 14000, "max_expert_load": 352}]
    unpacks = [s for s in spans if s[0] == "cbe.unpack"]
    for span, st in zip(unpacks, stats):
        span[3] = st
    ops = [["ragged_paged_attention.3", 210, 390],
           ["moe_grouped_matmul.5", 600, 390],
           ["ragged_paged_attention.3", 1310, 390],
           ["moe_grouped_matmul.5", 1700, 390], ["fusion.1", 2300, 90]]
    return {"ops": ops, "spans": spans, "window": [0, 2500]}


def routed_summary(kernel_s=0.9):
    s = summary()
    s.op_seconds = {"ragged_paged_attention.3": 0.6,
                    "moe_grouped_matmul.5": kernel_s, "fusion.1": 0.3}
    s.busy_s, s.dispatches = 3.0, 3.0
    return s


def test_readers_over_a_hand_trace(tmp_path, monkeypatch, capsys):
    cell = harness.load_cell(CELL)
    plant_xplane(tmp_path, cell.name)
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(program_trace, "load", lambda p: routed_trace())
    monkeypatch.setattr(program_trace, "_CACHE", {})
    monkeypatch.setattr(expert_work, "_CACHE", {})
    obs = observe(cell, routed_summary())
    got = {name: reader(name).read(obs) for name in READERS}
    capsys.readouterr()
    # every unpack span of the trace counts, the cut dispatch's too
    assert got["moe.experts_hit_share"] == \
        pytest.approx(100 * 18000 / (192 * 128))
    assert got["moe.max_expert_load"] == pytest.approx(1200 / 192)
    # complete dispatches only: 500 skipped of 500 + 2000 + 1000
    assert got["kernel.ragged_paged_attention.window_skipped_share"] == \
        pytest.approx(100 * 500 / 3500)
    assert got["kernel.moe_grouped_matmul.time_share"] == \
        pytest.approx(100 * 0.9 / 3.0)
    work = expert_work.required_work(
        {"experts_hit": 18000, "expert_assignments": 45000}, cell.config)
    least = work["bytes"] / 819e9               # memory-bound
    assert work["flops"] / 197e12 < least
    # three spans' work over three dispatches' kernel time
    assert got["moe_grouped_matmul_roofline"] == \
        pytest.approx(100 * (least / 3) / (0.9 / 3.0))
    bare = observe(cell, routed_summary(kernel_s=0.0))
    assert reader("moe_grouped_matmul_roofline").read(bare) is None
    assert reader("kernel.moe_grouped_matmul.time_share").read(bare) is None


def test_readers_are_silent_on_a_program_without_the_counters(
        tmp_path, monkeypatch, capsys):
    """The parent commit under these files: ``cbe.dispatch`` without
    ``window_skipped_pages``, ``cbe.unpack`` without stats, no
    ``moe_grouped_matmul`` event. Every new reader returns None and does
    not raise; so it does without a trace at all."""
    cell = harness.load_cell("m7b-1chip.chat-batch")
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(program_trace, "_CACHE", {})
    monkeypatch.setattr(expert_work, "_CACHE", {})
    for name in READERS:
        assert reader(name).read(observe(cell, None)) is None
        assert reader(name).read(observe(cell, summary())) is None
    plant_xplane(tmp_path, cell.name)
    spans = dispatch_spans(100, record(7, 496, 16, 2047, 598432))
    monkeypatch.setattr(program_trace, "load", lambda p: {
        "ops": [["ragged_paged_attention.3", 210, 390]], "spans": spans,
        "window": [0, 1200]})
    for name in READERS:
        assert reader(name).read(observe(cell, summary())) is None
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``, against planted faults
# ---------------------------------------------------------------------------
def _serve_and_check(seed=11):
    """The cell's own path at its rehearsal size: the adapter's server, the
    mix's batch, ``checks.check_serving`` with the rehearsal's limit."""
    cell = harness.load_cell(CELL, rehearse=True)
    adapter = harness.load_module("perfbench/adapters/serve_afmoe.py")
    server = adapter.Server(cell.config, 1, seed)
    traffic = loadgen.Traffic(cell.traffic, cell.params, server.vocab_size,
                              seed, 1.2)
    clock = time.perf_counter
    records, _ = harness.serve_batch(
        server, traffic, harness.Spans(clock), harness.Tracer(None, 1.0),
        clock)
    config = dict(cell.config, correct=dict(
        cell.config["correct"], max_deficit=1e-3, mean_deficit=1e-4))
    ok, facts = checks.check_serving(server, records, config, on_chip=False)
    return ok, facts


def _no_window(monkeypatch):
    from paddle_tpu.ops import paged_attention as pa
    real = pa.ragged_paged_attention
    monkeypatch.setattr(
        pa, "ragged_paged_attention",
        lambda *a, window=None, **kw: real(*a, **kw))


def _dropped_assignment(monkeypatch):
    from paddle_tpu.models import afmoe
    real = afmoe.grouped_expert_ffn
    # the router's last choice is computed by nobody
    monkeypatch.setattr(
        afmoe, "grouped_expert_ffn",
        lambda x, idx, weight, *a, **kw: real(
            x, idx, weight.at[:, -1].set(0.0), *a, **kw))


@pytest.mark.parametrize("fault", [None, _no_window, _dropped_assignment],
                         ids=["as_written", "no_window_bound",
                              "dropped_assignment"])
def test_correct_catches_planted_faults(fault, monkeypatch):
    """As written the served tokens are the reference's argmax (float32,
    deficits ~0); with the window's lower bound left out of the mask, or the
    router's last assignment dropped, the sample (which holds the longest
    completed request: ``correct.longest``) lies beyond the limit, and
    ``correct`` is false."""
    if fault is not None:
        fault(monkeypatch)
    ok, facts = _serve_and_check()
    seen = facts["reference"]
    assert seen["tokens"] > 50 and len(seen["requests"]) == 5
    if fault is None:
        assert ok and facts["problems"] == []
        assert seen["max_deficit"] < 1e-3
    else:
        assert not ok
        assert seen["max_deficit"] > 1e-3
        assert any("below the reference maximum" in p
                   for p in facts["problems"])
