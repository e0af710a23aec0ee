"""program_trace: the program's spans and work records out of a profiler
trace — the arithmetic on a hand-written trace, the kernel's required work
by hand, the readers, and ``load`` on a trace the real engine wrote on the
CPU (which has the spans and no device plane)."""

import json
import os

import numpy as np
import pytest

from perfbench import harness, program_trace, trace_reduce

READERS = ["engine.host_ms_per_dispatch", "idle.attributed_share",
           "engine.prefill_tokens_per_dispatch",
           "engine.decode_tokens_per_dispatch",
           "kernel.ragged_paged_attention.grid_live_share",
           "ragged_paged_attention_roofline",
           "tput.ragged_paged_attention_roofline"]
RECORD_KEYS = {"n", "rounds", "token_slots", "prefill_tokens",
               "decode_tokens", "live_rows", "attended_pages", "grid_steps",
               "causal_pairs", "page_size"}
NS = 1e-9


def reader(name):
    return harness.load_module(f"perfbench/layer_metrics/{name}.py")


def record(n, prefill, decode, pages, pairs):
    return {"n": n, "rounds": 16, "token_slots": 512,
            "prefill_tokens": prefill, "decode_tokens": decode,
            "live_rows": 30, "attended_pages": pages, "grid_steps": 131072,
            "causal_pairs": pairs, "page_size": 16}


def dispatch_spans(t0, rec):
    """One scheduler round from ``t0``: 1,000 long, the engine step 10
    inside it, the device busy from 10 into ``cbe.dispatch`` to 10 before
    the fence returns."""
    at = lambda name, a, b, stats=None: [name, t0 + a, b - a, stats or {}]
    return [at("paddle_serving.step", 0, 1000), at("cbe.step", 10, 990),
            at("cbe.admit", 10, 20), at("cbe.plan", 20, 50),
            at("cbe.upload", 50, 100), at("cbe.dispatch", 100, 120, rec),
            at("cbe.fence", 120, 900), at("cbe.unpack", 900, 950),
            at("cbe.audit", 950, 980)]


REC_A = record(7, 496, 16, 2047, 598432)
REC_B = record(8, 500, 12, 1953, 401568)


def hand_trace():
    spans = dispatch_spans(100, REC_A) + dispatch_spans(1200, REC_B)
    # a session that stopped mid-step keeps the phases that had ended and
    # neither the fence nor the ``cbe.step`` around them; one that started
    # mid-step keeps the tail
    spans += [["cbe.fence", 2300, 100, {}], ["cbe.unpack", 2400, 20, {}]]
    ops = [["ragged_paged_attention.3", 210, 390], ["fusion.1", 600, 390],
           ["ragged_paged_attention.3", 1310, 390], ["fusion.1", 1700, 390],
           ["fusion.1", 2300, 90]]
    return {"ops": ops, "spans": spans, "window": [0, 2500]}


def test_reduce_phase_times_records_and_the_dropped_dispatch():
    t = program_trace.reduce(hand_trace())
    assert len(t["dispatches"]) == 2            # the cut one is dropped
    for d, rec in zip(t["dispatches"], (REC_A, REC_B)):
        assert d["record"] == rec
        assert d["step_s"] == pytest.approx(980 * NS)
        assert d["host_s"] == pytest.approx(200 * NS)   # step less fence
        assert {k: round(v / NS) for k, v in d["phases_s"].items()} == {
            "cbe.admit": 10, "cbe.plan": 30, "cbe.upload": 50,
            "cbe.dispatch": 20, "cbe.fence": 780, "cbe.unpack": 50,
            "cbe.audit": 30}
    assert t["record_mean"]["prefill_tokens"] == 498.0
    assert t["record_mean"]["decode_tokens"] == 14.0
    assert t["record_mean"]["attended_pages"] == 2000.0
    assert t["record_mean"]["grid_steps"] == 131072.0
    assert t["page_size"] == 16
    # a step that reaches past the window's edge is no complete dispatch
    cut = dict(hand_trace(), window=[0, 2000])
    assert [d["record"]["n"] for d in
            program_trace.reduce(cut)["dispatches"]] == [7]


def test_reduce_splits_idle_by_the_innermost_span():
    t = program_trace.reduce(hand_trace())
    # busy [210, 990] [1310, 2090] [2300, 2390] of a window [0, 2500]
    assert t["window_s"] == pytest.approx(2500 * NS)
    assert t["idle_s"] == pytest.approx(850 * NS)
    assert {k: round(v / NS) for k, v in t["idle_by_phase_s"].items()} == {
        "cbe.admit": 20, "cbe.plan": 60, "cbe.upload": 100,
        "cbe.dispatch": 20,             # enqueued, the device not yet busy
        "cbe.fence": 30, "cbe.unpack": 120, "cbe.audit": 60,
        "cbe.step": 20}                 # the step's own time, phases out
    assert t["idle_in_round_outside_engine_s"] == pytest.approx(40 * NS)
    assert t["idle_outside_program_s"] == pytest.approx(380 * NS)
    assert (sum(t["idle_by_phase_s"].values())
            + t["idle_in_round_outside_engine_s"]
            + t["idle_outside_program_s"]) == pytest.approx(t["idle_s"])


def test_reduce_finds_nothing_without_a_device_or_a_dispatch():
    t = hand_trace()
    assert program_trace.reduce(dict(t, ops=[])) is None
    assert program_trace.reduce(dict(t, spans=[
        s for s in t["spans"] if s[0] != "cbe.dispatch"])) is None
    assert program_trace.reduce({"ops": [], "spans": []}) is None
    # the window left out: the extent of what the trace holds
    del t["window"]
    assert program_trace.reduce(t)["window_s"] == pytest.approx(2320 * NS)


def test_required_work_by_hand():
    one = harness.load_cell("m7b-1chip.longprompt-batch")
    w = program_trace.required_work(REC_A, one.config, 1)
    # 16 layers x (2047 pages x 16 tokens x 8 KV heads x 128 x bf16 x (K, V)
    #              + 512 token-slots x 32 heads x 128 x bf16 x (q, o))
    assert w["bytes"] == 16 * (134_152_192 + 8_388_608) == 2_280_652_800
    assert w["flops"] == 16 * 4 * 598432 * 32 * 128 == 156_875_358_208
    four = harness.load_cell("m7b-tp4.chat-batch")
    w4 = program_trace.required_work(REC_A, four.config, 4)
    assert w4["bytes"] == 2 * w["bytes"] / 4        # 32 layers, heads / 4
    assert w4["flops"] == 2 * w["flops"] / 4


def summary(kernel_s=0.6, dispatches=2.0):
    return trace_reduce.TraceSummary(
        window_s=2.5e-6, chips=1, busy_s=1.65e-6, idle_share=0.34,
        idle_share_worst=0.34,
        op_seconds={"ragged_paged_attention.3": kernel_s, "fusion.1": 0.3},
        collective_s=0.0, collective_exposed_s=0.0, idle_gaps=[],
        dispatch_s=0.5, dispatches=dispatches)


def observe(cell, trace):
    return harness.Observations(
        cell=cell, window=(0.0, 10.0), setup_s=12.5, requests=[], steps=[],
        tokens_per_step=0, spans=None, counters={}, device={},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, trace=trace)


def plant_xplane(root, cell_name, seed="seed1"):
    folder = root / cell_name / f"{seed}-trace1" / "trace" / "plugins" / \
        "profile" / "2026_01_01"
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(b"")
    return folder / "host.xplane.pb"


def test_readers_over_the_hand_trace(tmp_path, monkeypatch, capsys):
    cell = harness.load_cell("m7b-1chip.longprompt-batch")
    path = plant_xplane(tmp_path, cell.name)
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    loaded = []
    monkeypatch.setattr(program_trace, "load",
                        lambda p: loaded.append(p) or hand_trace())
    obs = observe(cell, summary())
    got = {name: reader(name).read(obs) for name in READERS}
    assert loaded == [str(path)]                # reduced once, then cached
    assert got["engine.host_ms_per_dispatch"] == pytest.approx(200e-6)
    assert got["idle.attributed_share"] == pytest.approx(100 * 470 / 850)
    assert got["engine.prefill_tokens_per_dispatch"] == 498.0
    assert got["engine.decode_tokens_per_dispatch"] == 14.0
    assert got["kernel.ragged_paged_attention.grid_live_share"] == \
        pytest.approx(100 * 2000 / 131072)
    # memory-bound: the mean record's bytes at the HBM peak, over 0.3 s of
    # kernel time a dispatch
    need = 16 * (2000 * 16 * 8 * 128 * 4 + 512 * 32 * 128 * 4) / 819e9
    assert got["ragged_paged_attention_roofline"] == \
        pytest.approx(100 * need / 0.3)
    assert got["tput.ragged_paged_attention_roofline"] == \
        got["ragged_paged_attention_roofline"]
    # kept beside the trace, and said once on stdout
    kept = json.loads((tmp_path / cell.name / "seed1-trace1"
                       / "program_spans.json").read_text())
    assert kept["dispatches"] == 2 and len(kept["per_dispatch"]) == 2
    assert kept["required"]["bound"] == "memory"
    assert kept["idle_ms_per_dispatch_by_phase"]["cbe.upload"] == \
        pytest.approx(1e3 * 100 * NS / 2)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [set(x) for x in lines] == [{"program_spans"}]
    # no kernel time in the trace: no roofline share, the rest stands
    bare = observe(cell, summary(kernel_s=0.0))
    assert reader("ragged_paged_attention_roofline").read(bare) is None
    assert reader("engine.host_ms_per_dispatch").read(bare) is not None


def test_readers_return_nothing_without_a_trace_or_the_programs_spans(
        tmp_path, monkeypatch):
    cell = harness.load_cell("m7b-1chip.chat-poisson")
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    no_trace = observe(cell, None)              # --trace 0, or a rehearsal
    no_file = observe(cell, summary())          # nothing under OUT_DIR
    for name in READERS:
        assert reader(name).read(no_trace) is None
        assert reader(name).read(no_file) is None
    # the parent commit: device operations, bench spans, no ``cbe.*``
    plant_xplane(tmp_path, cell.name)
    monkeypatch.setattr(program_trace, "load", lambda p: {
        "ops": hand_trace()["ops"], "spans": [], "window": [0, 2500]})
    for name in READERS:
        assert reader(name).read(no_file) is None


def test_load_on_a_cpu_trace_of_the_engine(tmp_path, monkeypatch):
    """The real engine under ``jax.profiler.start_trace`` on the CPU: the
    spans and the record's stats are found; there is no device plane, so
    ``reduce`` and every reader give nothing."""
    import jax
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.models import llama as L
    from paddle_tpu.serving import SchedulerConfig, ServingScheduler

    cell = harness.load_cell("m7b-1chip.chat-poisson")
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=3)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=4, seed=3), num_slots=2,
        page_size=4, max_seq_len=32, chunk=2, prefix_cache=True)
    sched = ServingScheduler(eng, SchedulerConfig(max_queue_depth=8))
    rng = np.random.RandomState(0)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    trace_dir = tmp_path / cell.name / "seed9-trace1" / "trace"
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        for n in (5, 9, 3):
            sched.submit(rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32),
                         max_new_tokens=4)
        rounds = 0
        while sched.pending:
            sched.step(params)
            rounds += 1
    finally:
        jax.profiler.stop_trace()

    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    path = program_trace.find_xplane(cell.name)
    assert path is not None and path.startswith(str(trace_dir))
    trace = program_trace.load(path)
    assert trace["ops"] == [] and "window" not in trace
    names = [s[0] for s in trace["spans"]]
    assert names.count("paddle_serving.step") == rounds == \
        names.count("cbe.step")
    records = [s[3] for s in trace["spans"] if s[0] == "cbe.dispatch"]
    assert records and all(set(r) == RECORD_KEYS for r in records)
    assert sum(r["prefill_tokens"] for r in records) == 5 + 9 + 3
    assert [r["n"] for r in records] == list(range(len(records)))
    for phase in ("cbe.admit", "cbe.plan", "cbe.upload", "cbe.fence",
                  "cbe.unpack", "cbe.audit"):
        assert names.count(phase) >= len(records), phase
    assert program_trace.reduce(trace) is None
    obs = observe(cell, summary())
    for name in READERS:
        assert reader(name).read(obs) is None
