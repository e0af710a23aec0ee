"""Disaggregated prefill/decode fleet + signal-driven autoscaling
(ISSUE 19): replica roles, the wire-framed KV page handoff, the
SignalSnapshot contract, the AutoscalePolicy decision loop, the
controller's drain-based actuation, /scalez + autoscale.json, and the
diurnal chaos acceptance run.

Every fleet shares one fake clock; greedy decoding is
prefix-deterministic, so handoff and chaos byte-identity assertions
compare streams directly."""

import json
import tarfile
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from paddle_tpu.models import llama as L
from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.inference.sampling import SamplerConfig
from paddle_tpu.observability import get_registry
from paddle_tpu.observability.events import configure_event_log
from paddle_tpu.observability.flight import flight_recorder
from paddle_tpu.observability.memory import pool_occupancy
from paddle_tpu.observability.server import DiagServer
from paddle_tpu.observability.signals import (SIGNAL_SNAPSHOT_VERSION,
                                              SignalSnapshot)
from paddle_tpu.resilience import Fault, FaultInjector
from paddle_tpu.serving import (AutoscaleConfig, AutoscaleController,
                                AutoscalePolicy, Decision, DisaggRouter,
                                HealthConfig, ReplicaHandle, ReplicaRole,
                                RequestState, RouterConfig,
                                SchedulerConfig)

from _oracle import greedy_reforward as _greedy_ref

REPO = Path(__file__).resolve().parent.parent


class FakeClock:
    """Deterministic fleet clock; sleep() advances it."""

    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt

    def advance(self, dt):
        self.t += dt


def _disagg_fleet(n=2, roles=None, max_new=4, num_slots=2, chunk=2,
                  seed=3, page_size=4, eos=None, health_kw=None,
                  router_kw=None, sched_kw=None, injector=None,
                  grammar_states=0, handoff_min_streamed=1):
    """Role-tagged fleet whose engines carry a prefix cache (the handoff
    import target) plus the engine/handle factory pair the autoscale
    controller builds scale-ups from."""
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=seed)
    clock = FakeClock()
    sched_kw = dict(sched_kw or {})
    sched_kw.setdefault("max_step_retries", 1)
    sched_kw.setdefault("retry_backoff_s", 0.01)
    engines = []

    def make_engine():
        eng = ContinuousBatchingEngine(
            cfg, GenerationConfig(max_new_tokens=max_new, seed=seed,
                                  eos_token_id=eos),
            num_slots=num_slots, page_size=page_size, max_seq_len=32,
            chunk=chunk, prefix_cache=True,
            grammar_states=grammar_states)
        engines.append(eng)
        return eng

    def make_handle(rid, eng):
        return ReplicaHandle(
            rid, eng, config=SchedulerConfig(**sched_kw),
            health_config=HealthConfig(**(health_kw or {})),
            clock=clock, sleep=clock.sleep)

    replicas = [make_handle(i, make_engine()) for i in range(n)]
    router = DisaggRouter(replicas, roles=roles,
                          handoff_min_streamed=handoff_min_streamed,
                          config=RouterConfig(**(router_kw or {})),
                          clock=clock, sleep=clock.sleep,
                          fault_injector=injector)
    return (cfg, params, router, replicas, clock, engines,
            make_engine, make_handle)


def _drive(router, clock, params, dt=0.05, max_steps=400):
    steps = 0
    while router.pending:
        router.step(params)
        clock.advance(dt)
        steps += 1
        assert steps < max_steps, router.statusz()
    return steps


def _counter_total(name):
    m = get_registry().get(name)
    return 0.0 if m is None else m.total


def _abc_grammar(vocab_size):
    from paddle_tpu.inference.constrain import compile_regex
    vocab = ["<eos>"] + list("abcde") + [
        f"tok{i}" for i in range(6, vocab_size)]
    return compile_regex("(ab|cd)(ab|cd)(ab|cd)e", vocab, eos_token_id=0)


# ---------------------------------------------------------------------------
# satellite: the SignalSnapshot contract
# ---------------------------------------------------------------------------

def test_signal_snapshot_round_trips_and_versions():
    """One versioned document shared by the bus, history.json and the
    policy: as_dict -> JSON -> from_dict is loss-free, a drifted
    schema_version is refused, and history_snapshot embeds it."""
    _, params, router, replicas, clock, *_ = _disagg_fleet(
        n=2, roles={0: ReplicaRole.PREFILL, 1: ReplicaRole.DECODE})
    bus = router.attach_signal_bus(interval_s=0.1)
    router.submit(np.arange(3, 9, dtype=np.int32))
    for _ in range(3):
        router.step(params)
        clock.advance(0.2)
        bus.tick()
    snap = bus.snapshot_contract()
    assert snap.schema_version == SIGNAL_SNAPSHOT_VERSION
    assert "r0" in snap.per_replica and "r1" in snap.per_replica
    wire = json.loads(json.dumps(snap.as_dict()))
    assert SignalSnapshot.from_dict(wire) == snap
    bad = dict(wire, schema_version=SIGNAL_SNAPSHOT_VERSION + 1)
    with pytest.raises(ValueError, match="schema_version"):
        SignalSnapshot.from_dict(bad)
    doc = bus.history_snapshot()
    assert doc["contract"]["schema_version"] == SIGNAL_SNAPSHOT_VERSION
    assert doc["contract"]["queue_depth"] == snap.queue_depth
    _drive(router, clock, params)


# ---------------------------------------------------------------------------
# tentpole: roles + the KV page handoff
# ---------------------------------------------------------------------------

def test_prefill_decode_handoff_greedy_byte_identical():
    """A prompt lands on the PREFILL replica; at first decoded token its
    settled pages hand off (wire round-trip, conservation audited) and
    the stream finishes on the DECODE replica byte-identical to the
    single-engine greedy reference."""
    cfg, params, router, replicas, clock, engines, *_ = _disagg_fleet(
        n=2, roles={0: ReplicaRole.PREFILL, 1: ReplicaRole.DECODE},
        max_new=6)
    p = np.arange(3, 13, dtype=np.int32)          # 10 tokens >= 2 pages
    pages0 = _counter_total("paddle_handoff_pages_total")
    h = router.submit(p)
    assert h.replica_id == 0                      # fresh admission: prefill
    _drive(router, clock, params)
    assert h.state == RequestState.DONE
    assert h.replica_id == 1                      # finished on decode
    assert router.handoffs_ok == 1 and router.handoffs_failed == 0
    assert router.handoff_pages_total >= 2        # settled full pages moved
    assert _counter_total("paddle_handoff_pages_total") - pages0 \
        == router.handoff_pages_total
    assert h.stream.result() == _greedy_ref(params, cfg, p, 6)
    for eng in engines:
        eng.mgr.check_conservation()
        assert eng.mgr.num_live_pages == 0        # zero leaked pages


def test_handoff_sampled_and_grammar_byte_identical():
    """Handoff under a SAMPLED stream (seed pinned at router submit) and
    a grammar-CONSTRAINED one (DFA resumed via grammar_prefix): both
    byte-identical to an all-hybrid fleet given the same submissions."""
    g = _abc_grammar(L.llama_tiny(num_hidden_layers=2).vocab_size)

    def fleet(roles):
        return _disagg_fleet(
            n=2, roles=roles, max_new=8, eos=0,
            grammar_states=g.n_states)

    def run(roles):
        cfg, params, router, replicas, clock, engines, *_ = fleet(roles)
        p = np.arange(3, 13, dtype=np.int32)
        hs = [router.submit(p, sampler=SamplerConfig(temperature=0.8)),
              router.submit(p + 1, grammar=g)]
        _drive(router, clock, params)
        assert all(h.state == RequestState.DONE for h in hs)
        for eng in engines:
            eng.mgr.check_conservation()
        return router, [list(h.stream.tokens) for h in hs], hs

    disagg, moved, hs = run({0: ReplicaRole.PREFILL,
                             1: ReplicaRole.DECODE})
    assert disagg.handoffs_ok >= 2                # both streams moved
    assert all(h.replica_id == 1 for h in hs)
    hybrid, stayed, _ = run(None)                 # all-HYBRID reference
    assert hybrid.handoffs_ok == 0
    assert moved == stayed
    st = g.start                                  # grammar-legal end to end
    for tok in moved[1]:
        assert g.legal(st, tok)
        st = g.advance(st, tok)


def test_decode_replica_is_last_resort_for_fresh_admissions():
    """DECODE replicas take no fresh prompts while any prefill-capable
    replica is routable — but when none is, availability beats role
    purity and traffic spills to the decode side."""
    cfg, params, router, replicas, clock, *_ = _disagg_fleet(
        n=2, roles={0: ReplicaRole.PREFILL, 1: ReplicaRole.DECODE},
        health_kw={"eject_after": 1, "probe_cooldown_s": 1e9})
    hs = [router.submit(np.arange(i, i + 6, dtype=np.int32))
          for i in range(1, 4)]
    assert all(h.replica_id == 0 for h in hs)     # never the decode side
    _drive(router, clock, params)
    replicas[0].kill()                            # the only prefill dies
    h = router.submit(np.arange(11, 17, dtype=np.int32))
    _drive(router, clock, params)
    assert h.state == RequestState.DONE and h.replica_id == 1


def test_handoff_failure_leaves_request_completing(monkeypatch):
    """A handoff torn mid-import is not an outage: the destination rolls
    back, conservation still holds, and the stream completes (at the
    source or via the standard failover continuation)."""
    cfg, params, router, replicas, clock, engines, *_ = _disagg_fleet(
        n=2, roles={0: ReplicaRole.PREFILL, 1: ReplicaRole.DECODE},
        max_new=6)

    def dying_import(tokens, ks, vs):
        raise RuntimeError("import torn mid-transfer")

    monkeypatch.setattr(engines[1].cache, "import_prefix", dying_import)
    f0 = _counter_total("paddle_handoff_requests_total")
    p = np.arange(3, 13, dtype=np.int32)
    h = router.submit(p)
    _drive(router, clock, params)
    assert h.state == RequestState.DONE
    assert router.handoffs_failed == 1
    assert _counter_total("paddle_handoff_requests_total") - f0 >= 1
    assert h.stream.result() == _greedy_ref(params, cfg, p, 6)
    for eng in engines:
        eng.mgr.check_conservation()
        assert eng.mgr.num_live_pages == 0


def test_role_flip_emits_event_and_gauge(tmp_path):
    configure_event_log(str(tmp_path / "events.jsonl"))
    try:
        _, params, router, replicas, clock, *_ = _disagg_fleet(n=2)
        assert router.role(0) == ReplicaRole.HYBRID
        router.set_role(0, ReplicaRole.PREFILL, reason="operator")
        router.set_role(0, ReplicaRole.PREFILL)   # no-op: no second event
        assert router.statusz()["roles"]["0"] == "prefill"
        with pytest.raises(ValueError):
            router.set_role(1, "turbo")
    finally:
        configure_event_log(None)
    events = [json.loads(l) for l in
              (tmp_path / "events.jsonl").read_text().splitlines()]
    flips = [e for e in events if e["kind"] == "role_changed"]
    assert len(flips) == 1
    assert flips[0]["replica"] == 0 and flips[0]["role"] == "prefill"
    assert flips[0]["previous"] == "hybrid"


# ---------------------------------------------------------------------------
# satellite: parked-age histogram + parked_expired shed event
# ---------------------------------------------------------------------------

def test_parked_deadline_shed_observes_age_and_event(tmp_path):
    configure_event_log(str(tmp_path / "events.jsonl"))
    try:
        cfg, params, router, replicas, clock, *_ = _disagg_fleet(
            n=1, roles=None,
            health_kw={"eject_after": 1, "probe_cooldown_s": 1e9})
        replicas[0].kill()
        h = router.submit(np.arange(3, 9, dtype=np.int32),
                          deadline_ms=500)
        router.step(params)                   # r0 fails once -> EJECTED
        clock.advance(0.05)
        router.step(params)                   # failover finds nobody: park
        assert router.parked == 1
        c0 = get_registry().get(
            "paddle_router_parked_age_seconds").hist().count
        clock.advance(1.0)                    # deadline lapses while parked
        router.step(params)
        assert h.state == RequestState.SHED
        hist = get_registry().get(
            "paddle_router_parked_age_seconds").hist()
        assert hist.count == c0 + 1 and hist.max >= 0.9
    finally:
        configure_event_log(None)
    events = [json.loads(l) for l in
              (tmp_path / "events.jsonl").read_text().splitlines()]
    exp = [e for e in events if e["kind"] == "parked_expired"]
    assert len(exp) == 1
    assert exp[0]["age_s"] >= 0.9 and exp[0]["trace_id"] == h.trace_id


# ---------------------------------------------------------------------------
# the policy: pure decisions over synthetic snapshots
# ---------------------------------------------------------------------------

def _snap(queue_depth=0.0, trend=0.0, wait_share=0.0, pressure=0.0,
          burn=0.0, acceptance=1.0, pending=0.0, parked=0.0,
          per_replica=None):
    return SignalSnapshot(
        schema_version=SIGNAL_SNAPSHOT_VERSION, t=0.0,
        queue_depth=queue_depth, queue_depth_trend=trend,
        queue_wait_share=wait_share, page_pressure=pressure,
        slo_fast_burn=burn, spec_acceptance=acceptance,
        pending=pending, parked=parked, per_replica=per_replica or {})


def test_policy_hysteresis_and_cooldown():
    pol = AutoscalePolicy(AutoscaleConfig(evidence_rounds=2,
                                          cooldown_s=10.0,
                                          max_replicas=4))
    roles = {0: ReplicaRole.HYBRID, 1: ReplicaRole.HYBRID}
    hot = _snap(parked=1.0)
    assert pol.decide(hot, roles, t=0.0) is None      # 1 round: not yet
    d = pol.decide(hot, roles, t=1.0)
    assert d is not None and d.action == "scale_up"
    assert "parked" in d.reason
    # evidence resets after acting AND scale_up is on cooldown
    assert pol.decide(hot, roles, t=2.0) is None
    assert pol.decide(hot, roles, t=3.0) is None      # rounds met, cooling
    d2 = pol.decide(hot, roles, t=12.0)               # cooldown elapsed
    assert d2 is not None and d2.action == "scale_up"
    # a calm round resets the hot streak entirely
    pol2 = AutoscalePolicy(AutoscaleConfig(evidence_rounds=2))
    assert pol2.decide(hot, roles, 0.0) is None
    assert pol2.decide(_snap(queue_depth=1.0), roles, 1.0) is None
    assert pol2.decide(hot, roles, 2.0) is None       # streak restarted


def test_policy_overload_evidence_maps_the_contract():
    pol = AutoscalePolicy(AutoscaleConfig())
    n = 2
    assert pol.overload_evidence(_snap(), n) == []
    # depth needs BOTH level and a rising slope
    assert pol.overload_evidence(_snap(queue_depth=20.0), n) == []
    ev = pol.overload_evidence(_snap(queue_depth=20.0, trend=0.5), n)
    assert any("queue_depth" in e for e in ev)
    for kw, tag in ((dict(burn=2.0), "slo_fast_burn"),
                    (dict(wait_share=0.7), "queue_wait_share"),
                    (dict(pressure=0.9), "page_pressure"),
                    (dict(acceptance=0.5), "spec_acceptance"),
                    (dict(parked=2.0), "parked")):
        assert any(tag in e
                   for e in pol.overload_evidence(_snap(**kw), n)), tag


def test_policy_scale_down_picks_idle_hybrid_first():
    pol = AutoscalePolicy(AutoscaleConfig(evidence_rounds=2,
                                          min_replicas=1))
    roles = {0: ReplicaRole.PREFILL, 1: ReplicaRole.HYBRID,
             2: ReplicaRole.DECODE}
    cold = _snap(per_replica={"r0": {"queue_depth": 0.0},
                              "r1": {"queue_depth": 0.0},
                              "r2": {"queue_depth": 0.0}})
    assert pol.decide(cold, roles, 0.0) is None
    d = pol.decide(cold, roles, 1.0)
    assert d is not None and d.action == "scale_down"
    assert d.replica_id == 1                       # hybrid before roles
    # at the floor the fleet never shrinks
    pol2 = AutoscalePolicy(AutoscaleConfig(evidence_rounds=1,
                                           min_replicas=1))
    assert pol2.decide(cold, {0: ReplicaRole.HYBRID}, 0.0) is None


def test_policy_rebalances_roles_at_max_replicas():
    pol = AutoscalePolicy(AutoscaleConfig(evidence_rounds=1,
                                          max_replicas=3,
                                          rebalance_backlog=2.0))
    roles = {0: ReplicaRole.PREFILL, 1: ReplicaRole.PREFILL,
             2: ReplicaRole.DECODE}
    # prompt-heavy: prefill side drowning, decode idle -> promote r2
    hot = _snap(parked=1.0,
                per_replica={"r0": {"queue_depth": 4.0},
                             "r1": {"queue_depth": 4.0},
                             "r2": {"queue_depth": 0.0}})
    d = pol.decide(hot, roles, 0.0)
    assert d is not None and d.action == "role_change"
    assert d.replica_id == 2 and d.role == ReplicaRole.PREFILL
    # decode side drowning demotes a surplus prefill — never the last
    back = _snap(per_replica={"r0": {"queue_depth": 0.0},
                              "r1": {"queue_depth": 0.0},
                              "r2": {"queue_depth": 5.0}})
    d2 = pol._rebalance(back, roles)
    assert d2 is not None and d2.role == ReplicaRole.DECODE
    assert d2.replica_id == 0
    only = {0: ReplicaRole.PREFILL, 2: ReplicaRole.DECODE}
    assert pol._rebalance(back, only) is None      # last prefill stays


# ---------------------------------------------------------------------------
# the controller: drain-based actuation
# ---------------------------------------------------------------------------

class _ScriptPolicy:
    """Canned decisions, in order; None once the script runs dry."""

    def __init__(self, decisions):
        self.config = AutoscaleConfig()
        self._script = list(decisions)

    def decide(self, snap, roles, t):
        return self._script.pop(0) if self._script else None


def test_controller_scale_up_role_change_scale_down(tmp_path):
    configure_event_log(str(tmp_path / "events.jsonl"))
    try:
        (_, params, router, replicas, clock, engines,
         make_engine, make_handle) = _disagg_fleet(n=1)
        script = _ScriptPolicy([
            Decision("scale_up", "test", role=ReplicaRole.PREFILL),
            Decision("role_change", "test", replica_id=1,
                     role=ReplicaRole.DECODE),
            Decision("scale_down", "test", replica_id=1),
        ])
        ctl = AutoscaleController(router, make_engine, make_handle,
                                  policy=script, interval_s=0.1)
        rec = ctl.evaluate()
        assert rec.action == "scale_up" and rec.state == "done"
        assert rec.replica_id == 1
        assert len(router.replicas) == 2
        assert router.role(1) == ReplicaRole.PREFILL
        assert len(engines) == 2                  # built via the factory
        # per-replica signals follow the fleet
        assert any(n.startswith("r1.") for n in ctl.bus.values())

        clock.advance(0.2)
        rec2 = ctl.evaluate()                     # role flip: drain first
        assert rec2.action == "role_change" and rec2.state == "applying"
        assert router.replicas[1].draining
        clock.advance(0.2)
        # the same round completes the flip (retag + undrain) and, with
        # the queue clear again, decides the next scripted op
        rec3 = ctl.evaluate()
        assert rec2.state == "done"
        assert router.role(1) == ReplicaRole.DECODE
        assert [p["phase"] for p in rec2.phases] \
            == ["drain", "retag", "undrain"]
        assert rec3.action == "scale_down" and rec3.state == "applying"
        clock.advance(0.2)
        ctl.evaluate()
        assert rec3.state == "done"
        assert len(router.replicas) == 1 and 1 not in router.replicas
        doc = ctl.timeline_snapshot()
        assert doc["kind"] == "paddle_tpu.autoscale"
        assert doc["replicas"] == 1 and doc["pending_ops"] == []
        assert [r["action"] for r in doc["records"]] \
            == ["scale_up", "role_change", "scale_down"]
        assert doc["records"][0]["snapshot"]["schema_version"] \
            == SIGNAL_SNAPSHOT_VERSION
    finally:
        configure_event_log(None)
    events = [json.loads(l) for l in
              (tmp_path / "events.jsonl").read_text().splitlines()]
    kinds = [e["kind"] for e in events]
    assert kinds.count("scale_up") == 1
    assert kinds.count("role_changed") == 1
    assert kinds.count("scale_down") == 1
    up = next(e for e in events if e["kind"] == "scale_up")
    assert up["replica"] == 1 and up["replicas"] == 2


def test_controller_drain_waits_for_live_requests():
    """A scale-down victim with work in flight is not removed until the
    drain empties it — and the fleet keeps serving meanwhile."""
    (_, params, router, replicas, clock, engines,
     make_engine, make_handle) = _disagg_fleet(n=2, max_new=6)
    script = _ScriptPolicy([Decision("scale_down", "test", replica_id=0)])
    ctl = AutoscaleController(router, make_engine, make_handle,
                              policy=script, interval_s=0.05)
    h = router.submit(np.arange(3, 9, dtype=np.int32))
    assert h.replica_id == 0
    rec = ctl.evaluate()
    assert rec.state == "applying" and 0 in router.replicas
    steps = 0
    while h.state != RequestState.DONE or 0 in router.replicas:
        ctl.step(params)
        clock.advance(0.05)
        steps += 1
        assert steps < 200, ctl.timeline_snapshot()
    assert rec.state == "done" and len(router.replicas) == 1


# ---------------------------------------------------------------------------
# /scalez + autoscale.json
# ---------------------------------------------------------------------------

def test_scalez_endpoint_and_flight_bundle(tmp_path):
    (_, params, router, replicas, clock, engines,
     make_engine, make_handle) = _disagg_fleet(
        n=2, roles={0: ReplicaRole.PREFILL, 1: ReplicaRole.DECODE})
    ctl = AutoscaleController(router, make_engine, make_handle,
                              interval_s=0.1)
    srv = DiagServer(port=0)
    try:
        srv.attach_autoscale(ctl)
        port = srv.start()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/scalez", timeout=10) as resp:
            doc = json.loads(resp.read())
        assert doc["kind"] == "paddle_tpu.autoscale"
        assert doc["roles"] == {"0": "prefill", "1": "decode"}
        assert "autoscale" in srv.statusz()
    finally:
        srv.stop()
    bare = DiagServer(port=0)
    try:
        bare.start()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{bare.port}/scalez", timeout=10)
        assert ei.value.code == 404
    finally:
        bare.stop()
    try:
        flight_recorder.arm(capacity=64, dump_dir=str(tmp_path))
        path = flight_recorder.dump_debug_bundle(
            str(tmp_path / "bundle.tar.gz"), reason="test")
        with tarfile.open(path) as tar:
            assert "autoscale.json" in tar.getnames()
            doc = json.loads(tar.extractfile("autoscale.json").read())
        assert doc["kind"] == "paddle_tpu.autoscale"
        assert doc["config"]["max_replicas"] == ctl.config.max_replicas
    finally:
        flight_recorder.disarm()
        flight_recorder.clear()
        flight_recorder._autoscale = None
        flight_recorder._dump_dir = None


# ---------------------------------------------------------------------------
# chaos acceptance: diurnal burst + mid-burst replica death
# ---------------------------------------------------------------------------

def _diurnal_prompts(cfg, seed=31):
    """Deterministic diurnal schedule: a trickle of short prompts, then
    a 10x prompt-heavy burst. Returns {step: [prompt, ...]}."""
    rng = np.random.RandomState(seed)
    sched = {}
    for step in (0, 8):                           # baseline: 1 per 8 steps
        n = int(rng.randint(4, 7))
        sched[step] = [rng.randint(1, cfg.vocab_size, (n,))
                       .astype(np.int32)]
    for step, k in ((16, 6), (18, 6), (20, 4)):   # 10x: 16 heavy prompts
        sched[step] = [rng.randint(1, cfg.vocab_size,
                                   (int(rng.randint(10, 13)),))
                       .astype(np.int32) for _ in range(k)]
    return sched


def _run_schedule(driver_step, router, clock, sched, max_steps=600):
    handles, step = [], 0
    sched = dict(sched)
    while step < max_steps:
        for p in sched.pop(step, []):
            handles.append(router.submit(p, max_new_tokens=4))
        if not sched and not router.pending:
            break
        driver_step()
        clock.advance(0.05)
        step += 1
    assert step < max_steps, router.statusz()
    return handles


def test_autoscaled_chaos_diurnal_byte_identical(tmp_path):
    """ISSUE 19 acceptance: a 10x diurnal burst with a mid-burst replica
    death. The autoscaler scales up AND rebalances roles; every request
    completes byte-identical to a static overprovisioned fleet run; the
    fleet SLO never breaches; no page leaks anywhere (including the
    scaled-up and removed engines)."""
    cfg = L.llama_tiny(num_hidden_layers=2)
    sched = _diurnal_prompts(cfg)

    # -- static reference: 4 always-on hybrids, no faults ------------------
    (_, params, ref_router, _, ref_clock, ref_engines, *_
     ) = _disagg_fleet(n=4, max_new=4)
    ref_handles = _run_schedule(lambda: ref_router.step(params),
                                ref_router, ref_clock, sched)
    assert all(h.state == RequestState.DONE for h in ref_handles)
    ref_out = [list(h.stream.tokens) for h in ref_handles]

    # -- chaos run: 3 replicas, autoscaled, replica dies mid-burst ---------
    injector = FaultInjector(schedule=[Fault("replica_die", 20,
                                             replica=1)])
    (_, params, router, replicas, clock, engines,
     make_engine, make_handle) = _disagg_fleet(
        n=3, roles={0: ReplicaRole.PREFILL, 1: ReplicaRole.PREFILL,
                    2: ReplicaRole.DECODE},
        max_new=4, injector=injector,
        health_kw={"suspect_after": 1, "eject_after": 2,
                   "probe_cooldown_s": 1e9},
        router_kw={"failover_backoff_s": 0.05})
    monitor = router.make_slo_monitor(completion_target=0.95,
                                      min_events=1)
    ctl = AutoscaleController(
        router, make_engine, make_handle,
        config=AutoscaleConfig(min_replicas=3, max_replicas=4,
                               up_queue_depth=1.0, up_trend=-1e9,
                               evidence_rounds=2, cooldown_s=0.4,
                               rebalance_backlog=0.5),
        interval_s=0.1)
    handles = _run_schedule(lambda: ctl.step(params), router, clock,
                            sched)
    assert all(h.state == RequestState.DONE for h in handles)

    done = [r for r in ctl.records if r.state == "done"]
    actions = [r.action for r in done]
    assert "scale_up" in actions                  # the fleet grew
    assert "role_change" in actions               # and rebalanced roles
    # every record replays its inputs: the decided-on snapshot rides along
    assert all(r.snapshot["schema_version"] == SIGNAL_SNAPSHOT_VERSION
               for r in ctl.records)

    # byte-identical to the static fleet, request for request
    assert [list(h.stream.tokens) for h in handles] == ref_out
    assert not monitor.breached() and monitor.health() == "ok"

    # zero leaked pages anywhere — dead replica 1's engine included
    # (kill() stops the scheduler, not the page books)
    for eng in engines + ref_engines:
        eng.mgr.check_conservation()
        assert eng.mgr.num_live_pages == 0
