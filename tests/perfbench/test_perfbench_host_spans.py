"""host_spans: the scheduler's admission, the prefix index's walks and the
collector's pauses out of a profiler trace: the sums, the collector's
precedence and the idle split on a hand-written trace by hand-computed
numbers; the readers; an older program's trace, which holds none of them."""

import json
import os

import pytest

from perfbench import harness, host_spans, program_trace, trace_reduce

READERS = ["sched.admit_ms_per_dispatch", "prefix.walk_ms_per_dispatch",
           "prefix.blocks_walked_per_dispatch", "engine.gc_ms_per_dispatch"]
NEW_ENTRIES = {
    "sched.admit_ms_per_dispatch": 4, "sched.admit_ms_per_dispatch-tput": 3,
    "prefix.walk_ms_per_dispatch": 3, "prefix.walk_ms_per_dispatch-tput": 3,
    "prefix.blocks_walked_per_dispatch": 1,
    "engine.gc_ms_per_dispatch": 4, "engine.gc_ms_per_dispatch-tput": 3}
NS = 1e-9
P = "paddle_serving."
RECORDED = os.path.join(harness.ROOT, "perfbench", "testdata",
                        "recorded.xplane.pb")


def reader(name):
    return harness.load_module(f"perfbench/layer_metrics/{name}.py")


def hand_trace():
    """One scheduler round [0, 1000] of a window [0, 1200], the device busy
    in [300, 500] and [900, 1000]:

      admit    [10, 110] with its peek [20, 80] inside, before the engine
      lookup   [130, 180] nested in ``cbe.admit`` [120, 200]
      insert   [600, 760] in ``cbe.unpack`` [580, 800], cut by a collection
               [700, 740] that ran on ANOTHER thread (no nesting to go by)
      gc       [840, 960], between two phases and half under a busy device
      evict    [1150, 1230]: starts inside the window, ends past it
      a peek at 1300 and a collection at -50: outside, not counted
    """
    at = lambda name, a, b, **stats: [name, a, b - a, stats]
    spans = [
        at("paddle_serving.step", 0, 1000), at("cbe.step", 115, 990),
        at(P + "admit", 10, 110, queued=3, handed=1, deferred=1),
        at(P + "prefix_peek", 20, 80, tokens=1040, blocks=64),
        at("cbe.admit", 120, 200),
        at(P + "prefix_lookup", 130, 180, tokens=1040, blocks=64),
        at("cbe.dispatch", 200, 220, n=1, page_size=16),
        at("cbe.fence", 220, 560), at("cbe.unpack", 580, 800),
        at(P + "prefix_insert", 600, 760, tokens=1100, pages=4),
        at(P + "gc", 700, 740, generation=0),
        at(P + "gc", 840, 960, generation=2),
        at(P + "prefix_evict", 1150, 1230, asked=5, pages=3),
        at(P + "prefix_peek", 1300, 1320, tokens=9, blocks=9),
        at(P + "gc", -50, -10, generation=1)]
    ops = [["fusion.1", 300, 200], ["fusion.2", 900, 100]]
    return {"ops": ops, "spans": spans, "window": [0, 1200]}


def test_reduce_sums_by_name_with_the_collectors_precedence():
    t = host_spans.reduce(hand_trace())
    assert t["window_s"] == pytest.approx(1200 * NS)
    # idle: [0, 300] [500, 900] [1000, 1200]
    assert t["idle_s"] == pytest.approx(900 * NS)
    got = {name[len(P):]: (v["count"], round(v["s"] / NS),
                           round(v["gc_s"] / NS), round(v["idle_s"] / NS))
           for name, v in t["by_name"].items()}
    assert got == {
        # name: (spans, their time, a collection's part of it, idle inside)
        "admit": (1, 100, 0, 100),
        "prefix_peek": (1, 60, 0, 60),          # also inside ``admit``
        "prefix_lookup": (1, 50, 0, 50),
        # 160 long, 40 of it the collection's: the idle time in those 40 is
        # the collector's, on whatever thread it ran
        "prefix_insert": (1, 160, 40, 120),
        "prefix_evict": (1, 80, 0, 50),         # idle: the window ends
        # [700, 740] all idle, [840, 960] idle until the device starts at
        # 900
        "gc": (2, 160, 0, 100)}
    stats = {name[len(P):]: v["stats"] for name, v in t["by_name"].items()}
    assert stats == {
        "admit": {"queued": 3, "handed": 1, "deferred": 1},
        "prefix_peek": {"tokens": 1040, "blocks": 64},
        "prefix_lookup": {"tokens": 1040, "blocks": 64},
        "prefix_insert": {"tokens": 1100, "pages": 4},
        "prefix_evict": {"asked": 5, "pages": 3},
        "gc": {"generation0": 1, "generation2": 1}}
    assert t["walk_s"] == pytest.approx((60 + 50 + 160 + 80) * NS)
    assert t["page_size"] == 16
    assert t["blocks_walked"] == 64 + 64 + 1100 / 16
    assert [(x["name"][len(P):], round(x["s"] / NS)) for x in t["longest"]] \
        == [("prefix_insert", 160), ("gc", 120), ("admit", 100),
            ("prefix_evict", 80), ("prefix_peek", 60), ("prefix_lookup", 50),
            ("gc", 40)]
    assert t["longest"][1]["stats"] == {"generation": 2}
    assert t["longest"][1]["at_s"] == pytest.approx(840 * NS)


def test_reduce_takes_the_traces_extent_without_a_window_and_no_page_size():
    t = hand_trace()
    del t["window"]
    t["spans"] = [s for s in t["spans"] if s[0] != "cbe.dispatch"]
    got = host_spans.reduce(t)
    assert got["window_s"] == pytest.approx(1370 * NS)      # [-50, 1320]
    assert got["by_name"][P + "gc"]["count"] == 3
    assert got["by_name"][P + "prefix_peek"]["count"] == 2
    assert got["page_size"] is None and got["blocks_walked"] is None


def test_a_trace_with_none_of_the_spans_is_an_older_programs():
    t = hand_trace()
    old = dict(t, spans=[s for s in t["spans"]
                         if s[0] not in host_spans.NAMES])
    assert old["spans"] and host_spans.reduce(old) is None
    assert host_spans.reduce({"ops": [], "spans": []}) is None
    # one kind alone: the others read 0.0, not None
    only_gc = dict(t, spans=old["spans"] + [[P + "gc", 10, 5,
                                             {"generation": 0}]])
    got = host_spans.reduce(only_gc)
    assert got["by_name"][P + "admit"] == {
        "count": 0, "s": 0.0, "gc_s": 0.0, "idle_s": 0.0, "stats": {}}
    assert got["walk_s"] == 0.0 and got["blocks_walked"] == 0.0


def test_the_recorded_chip_trace_holds_none_of_them():
    """PR 22's program wrote neither phases nor these spans."""
    trace = program_trace.load(RECORDED)
    assert trace["ops"]
    assert host_spans.reduce(trace) is None


def test_reduce_on_a_cpu_trace_of_the_engine(tmp_path):
    """The real engine and scheduler under ``jax.profiler.start_trace`` on
    the CPU, the same prompt twice and a forced collection: ``load`` finds
    the six names with their stats and ``reduce`` adds them up (no device
    plane: all of the trace's extent is idle)."""
    import gc

    import jax
    import numpy as np
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.models import llama as L
    from paddle_tpu.serving import SchedulerConfig, ServingScheduler

    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=3)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=4, seed=3), num_slots=2,
        page_size=4, max_seq_len=32, chunk=2, prefix_cache=True)
    sched = ServingScheduler(eng, SchedulerConfig(max_queue_depth=8))
    prompt = np.arange(1, 10, dtype=np.int32)           # 9 tokens, 2 blocks
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(2):
            sched.submit(prompt, max_new_tokens=4)
            while sched.pending:
                sched.step(params)
        gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    trace = program_trace.load(trace_reduce.find_xplane(str(tmp_path)))
    got = host_spans.reduce(trace)
    by = got["by_name"]
    assert by[P + "admit"]["stats"] == {"queued": 2, "handed": 2,
                                        "deferred": 0}
    for walk in ("prefix_peek", "prefix_lookup"):
        assert by[P + walk]["count"] == 2
        assert by[P + walk]["stats"] == {"tokens": 18, "blocks": 2}
    # 13 tokens a sequence: three full blocks adopted once, none again
    assert by[P + "prefix_insert"]["stats"] == {"tokens": 26, "pages": 3}
    assert by[P + "gc"]["stats"].get("generation2", 0) >= 1
    assert got["page_size"] == 4
    assert got["blocks_walked"] == 2 + 2 + 26 / 4
    assert 0 < got["walk_s"] < got["window_s"] == got["idle_s"]
    assert by[P + "prefix_peek"]["s"] < by[P + "admit"]["s"]
    assert by[P + "admit"]["idle_s"] == pytest.approx(
        by[P + "admit"]["s"] - by[P + "admit"]["gc_s"])


def summary(dispatches=2.0):
    return trace_reduce.TraceSummary(
        window_s=1.2e-6, chips=1, busy_s=0.3e-6, idle_share=0.75,
        idle_share_worst=0.75, op_seconds={"fusion.1": 0.2e-6},
        collective_s=0.0, collective_exposed_s=0.0, idle_gaps=[],
        dispatch_s=0.2e-6, dispatches=dispatches)


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


@pytest.fixture
def planted(tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(host_spans, "_CACHE", {})
    loaded = []
    monkeypatch.setattr(program_trace, "load",
                        lambda p: loaded.append(p) or hand_trace())
    return loaded


def test_readers_over_the_hand_trace(tmp_path, planted, capsys):
    cell = harness.load_cell("axk1-1chip.docqa-batch")
    path = plant_xplane(tmp_path, cell.name)
    obs = observe(cell, summary())
    got = {name: reader(name).read(obs) for name in READERS}
    assert planted == [str(path)]               # reduced once, then cached
    # two dispatches in the window: ns / 2, in ms
    assert got["sched.admit_ms_per_dispatch"] == pytest.approx(50e-6)
    assert got["prefix.walk_ms_per_dispatch"] == pytest.approx(175e-6)
    assert got["prefix.blocks_walked_per_dispatch"] == \
        pytest.approx((128 + 1100 / 16) / 2)
    assert got["engine.gc_ms_per_dispatch"] == pytest.approx(80e-6)
    kept = json.loads((tmp_path / cell.name / "seed1-trace1"
                       / "host_spans.json").read_text())
    assert kept["dispatches_in_window"] == 2.0 and kept["page_size"] == 16
    insert = kept["per_dispatch"][P + "prefix_insert"]
    assert insert["ms"] == pytest.approx(80e-6)
    assert insert["gc_ms"] == pytest.approx(20e-6)
    assert insert["idle_ms"] == pytest.approx(60e-6)
    assert insert["count"] == 0.5 and insert["stats"]["tokens"] == 550.0
    assert kept["totals"][P + "gc"]["count"] == 2
    assert kept["longest"][0]["name"] == P + "prefix_insert"
    assert kept["longest"][0]["stats"] == {"tokens": 1100, "pages": 4}
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [set(x) for x in lines] == [{"host_spans"}]
    assert lines[0]["host_spans"]["longest"] == kept["longest"]


def test_a_cell_without_a_prefix_cache_reads_no_prefix_metric(
        tmp_path, planted):
    cell = harness.load_cell("jamba2-1chip.reason-batch")
    assert cell.config["serving"]["prefix_cache"] is False
    plant_xplane(tmp_path, cell.name)
    obs = observe(cell, summary())
    assert reader("prefix.walk_ms_per_dispatch").read(obs) is None
    assert reader("prefix.blocks_walked_per_dispatch").read(obs) is None
    assert reader("sched.admit_ms_per_dispatch").read(obs) == \
        pytest.approx(50e-6)
    assert reader("engine.gc_ms_per_dispatch").read(obs) == \
        pytest.approx(80e-6)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_nothing_without_a_trace_or_the_spans(
        name, tmp_path, monkeypatch):
    cell = harness.load_cell("axk1-1chip.docqa-batch")
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(host_spans, "_CACHE", {})
    read = reader(name).read
    assert read(observe(cell, None)) is None        # --trace 0, a rehearsal
    assert read(observe(cell, summary())) is None   # nothing under OUT_DIR
    plant_xplane(tmp_path, cell.name)
    assert read(observe(cell, summary(dispatches=0.0))) is None
    # the parent commit: device operations, the phases, none of the six
    old = hand_trace()
    old["spans"] = [s for s in old["spans"] if s[0] not in host_spans.NAMES]
    monkeypatch.setattr(program_trace, "load", lambda p: old)
    assert read(observe(cell, summary())) is None


def test_the_benchmark_lists_the_seven_entries_last():
    bench = harness.read_json("BENCHMARK.json")
    last = bench["per_layer"][-len(NEW_ENTRIES):]
    assert {m["name"]: len(m["workloads"]) for m in last} == NEW_ENTRIES
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: harness.read_json(c["file"])
               for c in bench["configs"]}
    for m in last:
        assert m["better"] == "lower"
        assert m["layer"] == ("engine_host_loop" if "gc_ms" in m["name"]
                              else "scheduler")
        for cell in m["workloads"]:     # no prefix metric without a cache
            serving = configs[cells[cell]["config"]]["serving"]
            assert serving["prefix_cache"] or not m["name"].startswith(
                "prefix.")
