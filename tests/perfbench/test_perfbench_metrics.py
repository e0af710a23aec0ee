"""The metric readers on hand-made observations: what counts, from which
clock, and that a reader with nothing to read returns nothing."""

import math

import numpy as np
import pytest

from perfbench import harness, trace_reduce


def reader(folder, name):
    return harness.load_module(f"perfbench/{folder}/{name}.py")


def request(index, n_prompt, n_out, due=None, sent=0.0, tokens=(),
            outcome="ok"):
    return harness.RequestRecord(index, np.zeros(n_prompt, np.int32), n_out,
                                 due, sent=sent, token_times=list(tokens),
                                 outcome=outcome)


class FakeSpans:
    def __init__(self, records):
        self.records = records

    durations = harness.Spans.durations


def observe(requests=(), steps=(), window=(0.0, 10.0), spans=None,
            counters=None, trace=None, cell=None, tokens_per_step=0):
    return harness.Observations(
        cell=cell, window=window, setup_s=12.5, requests=list(requests),
        steps=list(steps), tokens_per_step=tokens_per_step,
        spans=FakeSpans(spans or {}), counters=counters or {},
        device={"memory_peak_bytes": 13_000_000_000},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, trace=trace)


def test_ttft_counts_from_due_time_and_censors():
    reqs = [
        request(0, 8, 4, due=1.0, sent=1.4, tokens=[2.0, 2.1]),    # 1.0 s
        request(1, 8, 4, due=2.0, sent=2.0, tokens=[5.0]),         # 3.0 s
        request(2, 8, 4, due=9.0, sent=9.5),                       # none yet
        request(3, 8, 4, due=-1.0, sent=-1.0, tokens=[0.5]),       # warm-up
        request(4, 8, 4, due=3.0, sent=3.0, tokens=[3.1], outcome="boom"),
    ]
    p50 = reader("layer_metrics", "sched.ttft_p50_ms")
    assert p50.read(observe(reqs)) == pytest.approx(3000.0)
    assert p50.read(observe(reqs), q=25.0) == pytest.approx(2000.0)
    assert reader("layer_metrics", "sched.ttft_p90_ms").read(
        observe(reqs)) is None                                     # inf
    assert p50.read(observe([])) is None
    late = reader("layer_metrics", "gen.late_ms_p90")
    assert late.read(observe(reqs[:2])) == pytest.approx(360.0)
    assert late.read(observe([request(0, 8, 4, sent=1.0)])) is None


def test_tpot_needs_32_tokens_and_completion_in_the_window():
    long_ok = request(0, 8, 33, tokens=np.linspace(1.0, 4.2, 33))   # 100 ms
    short = request(1, 8, 16, tokens=np.linspace(1.0, 1.1, 16))
    running = request(2, 8, 64, tokens=np.linspace(1.0, 9.0, 40),
                      outcome=None)
    late = request(3, 8, 33, tokens=np.linspace(8.0, 11.0, 33))
    tpot = reader("e2e_metrics", "tpot_p50_ms")
    assert tpot.read(observe([long_ok, short, running, late])) == \
        pytest.approx(100.0)
    assert tpot.read(observe([short, running])) is None


def test_serve_tok_s_is_the_batch_over_its_time():
    reqs = [request(0, 100, 4, sent=0.0, tokens=[1, 2, 3, 4]),
            request(1, 50, 2, sent=0.5, tokens=[5, 6]),
            request(2, 70, 2, sent=-3.0, tokens=[-2, -1]),          # warm-up
            request(3, 30, 2, sent=1.0, tokens=[2], outcome="boom")]
    obs = observe(reqs, spans={"bench.sched_step": [(0, 2), (2, 5), (5, 9),
                                                    (-4, -1)]})
    assert reader("e2e_metrics", "serve_tok_s").read(obs) == \
        pytest.approx(156 / 10.0)
    assert reader("layer_metrics", "engine.tokens_per_dispatch").read(obs) \
        == pytest.approx(156 / 3)
    assert reader("e2e_metrics", "serve_tok_s").read(observe([])) is None
    assert reader("layer_metrics", "engine.step_wall_ms_p50").read(obs) == \
        pytest.approx(3000.0)


def test_train_metrics():
    cell = harness.load_cell("m7b-train.pretrain-4k")
    # one step a second, and one stall of 7 s that the median does not see
    steps = [harness.StepRecord(t, 9.0) for t in (1.0, 2.0, 10.0, 11.0, 12.0)]
    obs = observe(steps=steps, window=(0.0, 12.0), cell=cell,
                  tokens_per_step=16384)
    assert reader("e2e_metrics", "train_tok_s").read(obs) == 16384.0
    assert reader("e2e_metrics", "setup_s").read(obs) == 12.5
    flops = 6.0 * (4 * 218_103_808 + 4096 * 8192) + 6.0 * 4 * 4096 * 4096
    assert reader("layer_metrics", "train.mfu").read(obs) == pytest.approx(
        100 * 16384 * flops / 197e12)
    assert reader("e2e_metrics", "train_tok_s").read(observe()) is None
    assert reader("layer_metrics", "device.peak_hbm_gb").read(obs) == 13.0


def test_trace_readers():
    cell = harness.load_cell("m7b-train.pretrain-4k")
    # 2 train steps in a 2 s window; flash kernels took 0.2 s in all
    summary = trace_reduce.TraceSummary(
        window_s=2.0, chips=1, busy_s=1.9, idle_share=0.05,
        idle_share_worst=0.05,
        op_seconds={"flash_attention_fwd": 0.08, "flash_attention_bwd_dq": 0.05,
                    "flash_attention_bwd_dkv": 0.07, "fusion.1": 1.7},
        collective_s=0.0, collective_exposed_s=0.0, idle_gaps=[],
        dispatch_s=0.95, dispatches=2.0)
    obs = observe(cell=cell, trace=summary, tokens_per_step=16384)
    need = 4 * 6.0 * 4 * 32 * 4096 * 4096 * 128 / 197e12    # compute-bound
    assert reader("layer_metrics", "flash_attention_roofline").read(obs) == \
        pytest.approx(100 * need / 0.1)
    assert reader("layer_metrics", "device.idle_share").read(obs) == 5.0
    assert reader("layer_metrics", "step.device_ms_per_dispatch").read(obs) \
        == pytest.approx(950.0)
    assert reader("layer_metrics", "collective.exposed_share").read(obs) \
        is None                                             # one chip
    for name in ("flash_attention_roofline", "device.idle_share",
                 "step.device_ms_per_dispatch",
                 "kernel.ragged_paged_attention.time_share"):
        assert reader("layer_metrics", name).read(observe(cell=cell)) is None


def test_counter_readers_return_nothing_without_their_counter():
    obs = observe(counters={"recompiles_in_window": 0.0,
                            "queue_wait_p50_ms": 3.5})
    assert reader("layer_metrics", "engine.recompiles_in_window").read(obs) \
        == 0.0
    assert reader("layer_metrics", "sched.queue_wait_p50_ms").read(obs) == 3.5
    assert reader("layer_metrics", "sched.queue_wait_p50_ms").read(
        observe()) is None


def test_read_metrics_leaves_out_what_was_not_read():
    metrics = [{"name": "setup_s", "unit": "s"},
               {"name": "train_tok_s", "unit": "tokens/s"}]
    got = harness.read_metrics(observe(), metrics, "e2e_metrics")
    assert got == {"setup_s": {"value": 12.5, "unit": "s"}}
    assert math.isfinite(got["setup_s"]["value"])
