"""Fleet sensor plane (ISSUE 11): MetricHistory ring sampling, robust
anomaly detection (shared z-score + CUSUM, cooldown, byte-determinism),
SignalBus signals through serving, /varz on the DiagServer, history.json
in flight bundles, the zero-cost disarmed gate, and the bench-trajectory
sentinel."""

import json
import os
import subprocess
import sys
import tarfile
import tracemalloc
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from paddle_tpu.core.histogram import Histogram
from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.models import llama as L
from paddle_tpu.observability import (AnomalyMonitor, CusumDetector,
                                      DiagServer, MetricHistory,
                                      RobustZScoreDetector, SignalBus,
                                      StragglerDetector, get_registry,
                                      robust_zscore)
from paddle_tpu.observability.anomaly import mad, median
from paddle_tpu.observability.flight import flight_recorder
from paddle_tpu.observability.timeseries import history_armed
from paddle_tpu.serving import SchedulerConfig, ServingScheduler

REPO = Path(__file__).resolve().parent.parent


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, s: float) -> None:
        self.t += s


@pytest.fixture()
def clean_plane():
    """Sensor-plane globals back to disarmed/unattached after each test."""
    yield
    history_armed[0] = False
    flight_recorder.disarm()
    flight_recorder.clear()
    flight_recorder._signals = None
    flight_recorder._dump_dir = None


def _setup_serving(max_new=4, num_slots=2, chunk=2, seed=3, clock=None,
                   **sched_kw):
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=seed)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=max_new, seed=seed),
        num_slots=num_slots, page_size=4, max_seq_len=32, chunk=chunk)
    kw = {}
    if clock is not None:
        kw = {"clock": clock, "sleep": lambda s: None}
    sched = ServingScheduler(eng, SchedulerConfig(**sched_kw), **kw)
    return params, eng, sched


# ---------------------------------------------------------------------------
# MetricHistory: windowed rates / slopes / quantiles on injected clocks
# ---------------------------------------------------------------------------

def test_history_counter_rate_and_gauge_slope():
    clk = FakeClock()
    h = MetricHistory(clock=clk, capacity=64, min_interval_s=1.0)
    ctr = [0.0]
    lvl = [2.0]
    h.track_counter("reqs", lambda: ctr[0])
    h.track_gauge("depth", lambda: lvl[0])
    for i in range(20):
        clk.advance(1.0)
        ctr[0] += 7.0               # 7 events/s
        lvl[0] = 2.0 + 0.5 * i      # +0.5/s
        assert h.sample()
    assert h.rate("reqs", 10.0) == pytest.approx(7.0)
    assert h.delta("reqs", 10.0) == pytest.approx(70.0)
    assert h.slope("depth", 10.0) == pytest.approx(0.5, rel=1e-6)
    assert h.latest("depth") == pytest.approx(2.0 + 0.5 * 19)


def test_history_windowed_quantile_from_bucket_deltas():
    clk = FakeClock()
    h = MetricHistory(clock=clk, capacity=64, min_interval_s=1.0)
    hist = Histogram(bounds=(1, 2, 5, 10, 20))
    h.track_histogram("lat", lambda: hist)
    # first window: all samples at ~4ms; later window: all at ~9ms —
    # a cumulative histogram would blend them, the windowed estimate
    # must see only the recent bucket deltas
    for i in range(30):
        clk.advance(1.0)
        hist.record(4.0 if i < 15 else 9.0)
        h.sample()
    q = h.window_quantile("lat", 0.5, 10.0)
    assert 5.0 <= q <= 10.0, q      # recent samples live in the (5,10] bucket
    assert h.window_mean("lat", 10.0) == pytest.approx(9.0)
    # full-history window blends both phases (deltas run from the first
    # RETAINED sample, so the very first observation is the baseline):
    # 14x4ms + 15x9ms over 29 observations
    assert h.window_mean("lat", None) == pytest.approx(191 / 29)


def test_history_ring_bounded_and_decimated():
    clk = FakeClock()
    h = MetricHistory(clock=clk, capacity=8, min_interval_s=1.0)
    h.track_gauge("g", lambda: 1.0)
    for _ in range(50):
        clk.advance(1.0)
        h.sample()
    assert len(h.series("g")) == 8          # ring bound
    clk.advance(0.25)
    assert not h.sample()                   # decimated: within interval
    assert h.snapshot_status()["series"]["g"] == 8
    snap = h.snapshot()
    assert set(snap) == {"g"} and len(snap["g"]["points"]) == 8


# ---------------------------------------------------------------------------
# shared robust z-score: the straggler detector delegates
# ---------------------------------------------------------------------------

def test_straggler_detector_delegates_to_shared_zscore():
    det = StragglerDetector(window=16, z_threshold=4.0, min_samples=8)
    vals = [0.1, 0.11, 0.1, 0.09, 0.1, 0.12, 0.1, 0.11, 0.1]
    for v in vals:
        det.observe(v, source="delegate_test")
    # identical math through either entry point
    assert det.zscore(0.5) == robust_zscore(0.5, det._samples,
                                            det.min_samples)
    # warmup semantics preserved: below min_samples -> 0
    assert robust_zscore(9.9, [1.0, 1.0], min_samples=8) == 0.0
    # MAD-of-zero fallback preserved (uniform window still scores)
    z = robust_zscore(0.2, [0.1] * 10)
    assert z == pytest.approx((0.2 - 0.1) / (0.1 * 0.05))


def test_median_mad_primitives():
    assert median([3.0, 1.0, 2.0]) == 2.0
    assert median([4.0, 1.0, 2.0, 3.0]) == 2.5
    assert mad([1.0, 2.0, 3.0, 4.0, 100.0]) == 1.0   # robust to the spike


# ---------------------------------------------------------------------------
# anomaly detection: level shift, slow drift, cooldown, determinism
# ---------------------------------------------------------------------------

def _level_series():
    # quiet baseline with mild deterministic jitter, then a 5x shift
    return [1.0 + 0.01 * (i % 3) for i in range(40)] + [5.0] * 20


def _drift_series():
    # per-sample increment far below the jitter, but accumulating: the
    # windowed z-score absorbs it, CUSUM must not
    out = []
    for i in range(120):
        base = 1.0 + 0.02 * ((i * 7) % 5)           # deterministic noise
        drift = 0.01 * max(0, i - 40)               # slow ramp after 40
        out.append(base + drift)
    return out


def test_level_shift_fires_exactly_once_with_cooldown(clean_plane):
    mon = AnomalyMonitor(
        cooldown_s=1000.0,
        detector_factory=lambda: [RobustZScoreDetector(
            window=32, z_threshold=6.0, min_samples=8)])
    fired = []
    for i, v in enumerate(_level_series()):
        fired += mon.observe("itl_ms", v, float(i))
    assert len(fired) == 1, fired
    assert fired[0]["series"] == "itl_ms"
    assert fired[0]["detector"] == "zscore"
    assert fired[0]["direction"] == "up"
    assert fired[0]["t"] == 40.0                    # the shift sample
    snap = mon.snapshot()["itl_ms"]
    assert snap["fired"] == 1
    assert snap["suppressed"] > 0                   # sustained shift held


def test_slow_drift_cusum_fires_once(clean_plane):
    mon = AnomalyMonitor(
        cooldown_s=1000.0,
        detector_factory=lambda: [CusumDetector(k=0.5, h=8.0,
                                                baseline=24)])
    zmon = AnomalyMonitor(
        cooldown_s=1000.0,
        detector_factory=lambda: [RobustZScoreDetector(
            window=16, z_threshold=8.0, min_samples=8)])
    fired, zfired = [], []
    for i, v in enumerate(_drift_series()):
        fired += mon.observe("burn", v, float(i))
        zfired += zmon.observe("burn", v, float(i))
    assert len(fired) == 1, fired
    assert fired[0]["detector"] == "cusum"
    assert fired[0]["direction"] == "up"
    assert fired[0]["t"] > 40.0                     # after the ramp starts
    # a SHORT-window z-score misses the drift entirely (each sample is
    # ordinary against its drifting window) — that's why CUSUM exists
    assert zfired == []


def test_anomaly_cooldown_expiry_pages_again(clean_plane):
    mon = AnomalyMonitor(cooldown_s=10.0, detector_factory=lambda: [
        RobustZScoreDetector(window=32, z_threshold=6.0, min_samples=8)])
    series = _level_series()
    fired = []
    for i, v in enumerate(series):
        fired += mon.observe("x", v, float(i))
    assert len(fired) == 2                          # 40, then 50 (cooldown)
    assert fired[1]["t"] == 50.0


def test_idle_zero_series_first_activity_scores_sanely(clean_plane):
    """A series idling at exactly 0 (queue depth, parked count) has no
    scale information — the MAD fallback would otherwise degenerate to
    ~1e-12 and score the first real sample at z~1e11, paging on every
    idle->active transition. The z-score detector must treat first
    activity as a level START (no fire); CUSUM may legitimately note
    the 0->busy regime change, but only with a sane standardized score,
    never the degenerate-scale explosion."""
    mon = AnomalyMonitor(cooldown_s=1000.0)
    fired = []
    for i in range(60):
        fired += mon.observe("queue_depth", 0.0, float(i))
    assert fired == []                       # idle never pages
    for i in range(60, 120):
        fired += mon.observe("queue_depth", 3.0 + 0.1 * (i % 4),
                             float(i))
    assert all(f["detector"] != "zscore" for f in fired), fired
    assert all(abs(f["score"]) < 1e3 for f in fired), fired
    # a REAL shift on the established busy baseline still pages
    mon2 = AnomalyMonitor(cooldown_s=1000.0)
    for i in range(60):
        mon2.observe("busy", 3.0 + 0.1 * (i % 4), float(i))
    later = []
    for i in range(60, 80):
        later += mon2.observe("busy", 30.0, float(i))
    assert len(later) >= 1


def test_spec_acceptance_reader_uses_snapshot_ratio(clean_plane):
    from paddle_tpu.observability.signals import _spec_acceptance

    class _Spec:
        def snapshot(self):
            return {"acceptance_ratio": 0.42, "drafted": 100}

    class _Eng:
        spec = _Spec()

    assert _spec_acceptance(_Eng()) == pytest.approx(0.42)
    assert _spec_acceptance(object()) == 1.0      # no speculation


def test_anomaly_detection_byte_deterministic(clean_plane):
    def run():
        mon = AnomalyMonitor(cooldown_s=25.0)
        out = []
        for i, v in enumerate(_level_series() + _drift_series()):
            out += mon.observe("s", v, float(i) * 0.5)
        return json.dumps(out, sort_keys=True)
    assert run() == run()


def test_anomaly_metrics_registered(clean_plane):
    mon = AnomalyMonitor(cooldown_s=1000.0)
    for i, v in enumerate(_level_series()):
        mon.observe("det_series", v, float(i))
    reg = get_registry()
    c = reg.get("paddle_anomaly_events_total")
    assert c is not None
    total = sum(v for k, v in c.snapshot().items()
                if "det_series" in k)
    assert total >= 1
    g = reg.get("paddle_anomaly_score")
    assert g is not None and g.value(series="det_series") >= 0.0


# ---------------------------------------------------------------------------
# SignalBus through serving + /varz + flight bundle
# ---------------------------------------------------------------------------

def test_signal_bus_serving_e2e(clean_plane):
    clk = FakeClock()
    params, eng, sched = _setup_serving(clock=clk, max_queue_depth=8)
    bus = sched.attach_signal_bus(interval_s=1.0, window_s=60.0)
    assert sched.signal_bus is bus
    bus.arm()
    assert history_armed[0]
    for i in range(6):
        sched.submit(np.array([2, 3, 4, 5], np.int32), priority=i % 2)
    while sched.pending:
        clk.advance(1.5)            # every step crosses the bus interval
        sched.step(params)
    v = bus.values()
    for name in ("queue_depth", "page_pressure", "slo_burn",
                 "spec_acceptance", "queue_wait_share"):
        assert name in v, sorted(v)
    assert v["queue_depth"]["value"] is not None
    assert 0.0 <= v["page_pressure"]["raw"] <= 1.0
    assert bus.ticks >= 3
    # the history tracked the sink's histograms + counters too
    assert bus.history.latest("tokens_total") > 0
    # statusz carries the signal summary
    assert "signals" in sched.statusz()
    doc = bus.varz()
    assert doc["armed"] and "anomalies" in doc and "history" in doc
    bus.disarm()
    assert not history_armed[0]


def test_signal_bus_disarmed_never_ticks(clean_plane):
    clk = FakeClock()
    params, eng, sched = _setup_serving(clock=clk)
    bus = sched.attach_signal_bus(interval_s=0.0)
    assert not history_armed[0]     # attach does NOT arm
    sched.submit(np.array([2, 3, 4], np.int32))
    while sched.pending:
        clk.advance(1.0)
        sched.step(params)
    assert bus.ticks == 0


def test_varz_endpoint_e2e(clean_plane):
    clk = FakeClock()
    bus = SignalBus(clock=clk, interval_s=1.0)
    depth = [3.0]
    bus.signal("queue_depth", lambda: depth[0])
    bus.arm()
    for i in range(10):
        clk.advance(1.0)
        depth[0] = 3.0 + i
        bus.tick()
    srv = DiagServer(port=0)
    srv.attach_signals(bus)
    port = srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/varz", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["signals"]["queue_depth"]["value"] is not None
        assert doc["signals"]["queue_depth"]["trend_per_s"] > 0
        assert doc["armed"] is True
        # /varz listed on the index; signals section joins /statusz
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/", timeout=10) as r:
            assert "/varz" in json.loads(r.read())["endpoints"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/statusz", timeout=10) as r:
            assert "signals" in json.loads(r.read())
    finally:
        srv.stop()
        bus.disarm()


def test_varz_404_without_bus(clean_plane):
    srv = DiagServer(port=0)
    port = srv.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/varz",
                                   timeout=10)
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_flight_bundle_embeds_history_json(tmp_path, clean_plane):
    clk = FakeClock()
    bus = SignalBus(clock=clk, interval_s=1.0, capacity=128)
    val = [1.0]
    bus.signal("sig", lambda: val[0])
    bus.arm()
    for i, v in enumerate(_level_series()):
        clk.advance(1.0)
        val[0] = v
        bus.tick()
    flight_recorder.arm(capacity=64, dump_dir=str(tmp_path))
    path = flight_recorder.dump_debug_bundle(
        str(tmp_path / "bundle.tar.gz"), reason="test")
    assert os.path.getsize(path) < 256 * 1024       # bounded bundle
    with tarfile.open(path) as tar:
        names = tar.getnames()
        assert "history.json" in names
        doc = json.loads(tar.extractfile("history.json").read())
    assert doc["schema_version"] == 1
    assert doc["kind"] == "paddle_tpu.history"
    assert "sig" in doc["series"]
    assert len(doc["series"]["sig"]["points"]) <= 128
    assert doc["signals"]["sig"]["value"] is not None
    # the level shift the bus watched landed in the bundle's anomalies
    assert any(a["series"] == "sig" for a in doc["anomalies"])


def test_history_gate_disarmed_inert(clean_plane):
    """The disarmed per-step cost is one list index — allocation-free,
    same contract (and same tracemalloc harness) as the flight/timeline
    gates in bench_obs_overhead."""
    assert not history_armed[0]
    tracemalloc.start()
    before = tracemalloc.get_traced_memory()[0]
    for _ in range(20_000):
        pass
    baseline = tracemalloc.get_traced_memory()[0] - before
    before = tracemalloc.get_traced_memory()[0]
    for _ in range(20_000):
        _ = history_armed[0]
    after = tracemalloc.get_traced_memory()[0]
    tracemalloc.stop()
    assert max(0, after - before - baseline) < 2048


# ---------------------------------------------------------------------------
# bench sentinel: trajectory replay passes, synthetic regression fails
# ---------------------------------------------------------------------------

def _run_sentinel(*args):
    return subprocess.run(
        [sys.executable, str(REPO / "scripts" / "bench_sentinel.py"),
         *args],
        capture_output=True, text=True, cwd=str(REPO))


@pytest.mark.skipif(not list(REPO.glob("BENCH_r*.json")),
                    reason="no checked-in trajectory")
def test_sentinel_replay_of_checked_in_trajectory_passes():
    r = _run_sentinel("--replay")
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["pass"] and doc["entries"] >= 2


def test_sentinel_flags_synthetic_itl_regression(tmp_path):
    # a judgeable trajectory needs >= 3 same-(metric, unit) entries with
    # tokens_per_sec; it is built here so the test does not depend on
    # which records happen to be checked in
    entries = [{"metric": "llama_877M_train_mfu_v5e", "unit": "MFU",
                "value": mfu, "tokens_per_sec": tps}
               for mfu, tps in ((0.5187, 19428.0), (0.5198, 19468.0),
                                (0.5287, 19801.0))]
    for i, e in enumerate(entries):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(
            json.dumps({"parsed": e}))
    glob_arg = str(tmp_path / "BENCH_r*.json")
    bad = tmp_path / "regressed.json"
    bad.write_text(json.dumps(dict(
        entries[-1],                        # 2x ITL == half throughput
        tokens_per_sec=entries[-1]["tokens_per_sec"] / 2.0)))
    r = _run_sentinel("--fresh", str(bad), "--trajectory", glob_arg)
    assert r.returncode == 1, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert not doc["pass"]
    assert any(row["field"] == "tokens_per_sec"
               for row in doc["regressions"])
    # the unmodified line sails through
    good = tmp_path / "fresh.json"
    good.write_text(json.dumps(entries[-1]))
    r = _run_sentinel("--fresh", str(good), "--trajectory", glob_arg)
    assert r.returncode == 0, r.stdout + r.stderr


def test_sentinel_bands_are_mad_based(tmp_path):
    """Unit-level: a fresh value inside median±max(k·1.4826·MAD,
    floor·median) passes, outside fails; direction respects the unit."""
    traj = []
    for i, tps in enumerate((1000.0, 1010.0, 990.0, 1005.0)):
        p = tmp_path / f"BENCH_r{i:02d}.json"
        p.write_text(json.dumps({"parsed": {
            "metric": "m", "unit": "MFU", "value": 0.5,
            "tokens_per_sec": tps}}))
        traj.append(p)
    glob_arg = str(tmp_path / "BENCH_r*.json")
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps({"metric": "m", "unit": "MFU",
                              "value": 0.5, "tokens_per_sec": 980.0}))
    r = _run_sentinel("--fresh", str(ok), "--trajectory", glob_arg)
    assert r.returncode == 0, r.stdout
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"metric": "m", "unit": "MFU",
                               "value": 0.5, "tokens_per_sec": 700.0}))
    r = _run_sentinel("--fresh", str(bad), "--trajectory", glob_arg)
    assert r.returncode == 1, r.stdout


def test_sentinel_renamed_metric_fails_loudly_not_vacuously(tmp_path):
    """A fresh line whose (metric, unit) has no trajectory peers must
    NOT report clean — exit 3 + no_comparable_history (a regression on
    a renamed workload would otherwise pass silently); opt out with
    --allow-new-metric."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({"parsed": {
        "metric": "old_name", "unit": "MFU", "value": 0.5,
        "tokens_per_sec": 1000.0}}))
    glob_arg = str(tmp_path / "BENCH_r*.json")
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps({"metric": "NEW_name", "unit": "MFU",
                                 "value": 0.5,
                                 "tokens_per_sec": 500.0}))
    r = _run_sentinel("--fresh", str(fresh), "--trajectory", glob_arg)
    assert r.returncode == 3, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["verdict"] == "no_comparable_history" and not doc["pass"]
    r = _run_sentinel("--fresh", str(fresh), "--trajectory", glob_arg,
                      "--allow-new-metric")
    assert r.returncode == 0, r.stdout + r.stderr


def test_bench_headers_carry_schema_version():
    sys.path.insert(0, str(REPO / "benchmarks"))
    try:
        from _telemetry import BENCH_SCHEMA_VERSION, run_header
    finally:
        sys.path.pop(0)
    h = run_header("unit")
    assert h["schema_version"] == BENCH_SCHEMA_VERSION >= 2
    assert h["bench"] == "unit"
    assert "python" in h and "jax_platforms" in h
