"""paddle_tpu.serving fleet router: prefix-aware multi-replica routing,
failure detection (circuit breaker), drain/failover, and the chaos
acceptance run (ISSUE 6).

Every fleet shares one fake clock; engines are seeded and decoding is
greedy, so router outputs are prefix-deterministic — the property the
mid-stream failover and the byte-identical chaos assertions lean on."""

import json
from pathlib import Path

import numpy as np
import pytest

from paddle_tpu.models import llama as L
from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.observability import get_registry
from paddle_tpu.observability.events import configure_event_log
from paddle_tpu.resilience import Fault, FaultInjector
from paddle_tpu.serving import (FleetRouter, HealthConfig, HealthTracker,
                                ReplicaHandle, ReplicaState, RequestState,
                                RouterConfig, SchedulerConfig, ServingError)

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


def _fleet(n=2, max_new=4, num_slots=2, chunk=2, seed=3, page_size=4,
           eos=None, health_kw=None, router_kw=None, sched_kw=None,
           injector=None, speculative=False):
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=seed)
    clock = FakeClock()
    sched_kw = dict(sched_kw or {})
    sched_kw.setdefault("max_step_retries", 1)
    sched_kw.setdefault("retry_backoff_s", 0.01)
    replicas = []
    for i in range(n):
        eng = ContinuousBatchingEngine(
            cfg, GenerationConfig(max_new_tokens=max_new, seed=seed,
                                  eos_token_id=eos),
            num_slots=num_slots, page_size=page_size, max_seq_len=32,
            chunk=chunk, speculative=speculative)
        replicas.append(ReplicaHandle(
            i, eng, config=SchedulerConfig(**sched_kw),
            health_config=HealthConfig(**(health_kw or {})),
            clock=clock, sleep=clock.sleep))
    router = FleetRouter(replicas, config=RouterConfig(**(router_kw or {})),
                         clock=clock, sleep=clock.sleep,
                         fault_injector=injector)
    return cfg, params, router, replicas, clock


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


# ---------------------------------------------------------------------------
# routing policy
# ---------------------------------------------------------------------------

def test_prefix_affinity_beats_load_only_within_band():
    """Same-prefix requests pile onto the replica that holds the pages
    while its load stays within load_band of the least-loaded candidate;
    past the band, queue depth wins and the request spills over."""
    cfg, params, router, replicas, clock = _fleet(
        n=2, router_kw={"load_band": 1})
    rng = np.random.RandomState(21)
    base = rng.randint(1, cfg.vocab_size, (8,)).astype(np.int32)

    def prompt(i):
        return np.concatenate([base, [i + 1, i + 2]]).astype(np.int32)

    aff0 = _counter_total("paddle_router_prefix_affinity_hits_total")
    h0 = router.submit(prompt(0))       # cold: least-loaded tie -> r0
    h1 = router.submit(prompt(1))       # 8-token overlap, load diff 1 <= 1
    h2 = router.submit(prompt(2))       # load diff 2 > band: spills to r1
    assert [h.replica_id for h in (h0, h1, h2)] == [0, 0, 1]
    assert _counter_total(
        "paddle_router_prefix_affinity_hits_total") - aff0 == 1
    _drive(router, clock, params)
    assert all(h.state == RequestState.DONE for h in (h0, h1, h2))
    assert h2.stream.result() == _greedy_ref(params, cfg, prompt(2), 4)


def test_ejected_replica_never_receives_traffic():
    cfg, params, router, replicas, clock = _fleet(
        n=2, health_kw={"eject_after": 1, "probe_cooldown_s": 1e9})
    replicas[0].kill()
    h_dead = router.submit(np.arange(1, 7, dtype=np.int32))
    assert h_dead.replica_id == 0       # routed before the death shows
    router.step(params)                 # r0 fails once -> EJECTED
    clock.advance(0.05)
    assert replicas[0].health.state == ReplicaState.EJECTED
    hs = [router.submit(np.arange(i, i + 6, dtype=np.int32))
          for i in range(1, 6)]
    assert all(h.replica_id == 1 for h in hs)   # no traffic to ejected
    _drive(router, clock, params)
    # the in-flight request failed over and still completed
    assert h_dead.state == RequestState.DONE and h_dead.replica_id == 1
    assert all(h.state == RequestState.DONE for h in hs)
    assert replicas[0].health.state == ReplicaState.EJECTED


def test_half_open_probe_admits_exactly_one():
    cfg, params, router, replicas, clock = _fleet(
        n=2, health_kw={"eject_after": 1, "probe_cooldown_s": 0.2})
    replicas[0].stall(0.15)             # shorter than the cooldown
    router.submit(np.arange(1, 7, dtype=np.int32))
    router.step(params)                 # r0 raises once -> EJECTED
    clock.advance(0.05)
    assert replicas[0].health.state == ReplicaState.EJECTED
    clock.advance(0.3)                  # cooldown AND stall both lapse
    router.step(params)
    assert replicas[0].health.state == ReplicaState.HALF_OPEN
    probes = [router.submit(np.arange(i, i + 6, dtype=np.int32))
              for i in range(1, 4)]
    # exactly one request probes the half-open replica
    assert [h.replica_id for h in probes].count(0) == 1
    assert probes[0].replica_id == 0
    _drive(router, clock, params)
    # probe completed -> circuit closed, replica re-admitted
    assert replicas[0].health.state == ReplicaState.HEALTHY
    assert all(h.state == RequestState.DONE for h in probes)
    h_after = router.submit(np.arange(9, 15, dtype=np.int32))
    assert h_after.replica_id in (0, 1)     # back in rotation
    _drive(router, clock, params)


def test_failed_probe_reejects_with_doubled_cooldown():
    cfg, params, router, replicas, clock = _fleet(
        n=2, health_kw={"eject_after": 1, "probe_cooldown_s": 0.2})
    replicas[0].kill()
    router.submit(np.arange(1, 7, dtype=np.int32))
    router.step(params)
    clock.advance(0.3)
    router.step(params)
    assert replicas[0].health.state == ReplicaState.HALF_OPEN
    h = router.submit(np.arange(2, 8, dtype=np.int32))  # becomes the probe
    assert h.replica_id == 0
    router.step(params)                 # probe step fails
    clock.advance(0.05)
    assert replicas[0].health.state == ReplicaState.EJECTED
    assert replicas[0].health.cooldown_s == pytest.approx(0.4)
    _drive(router, clock, params)
    assert h.state == RequestState.DONE and h.replica_id == 1


def test_mid_stream_failover_byte_identical(tmp_path):
    """A replica dying mid-decode: its live request resumes on a sibling
    through the retry/backoff path and the consumer stream ends with the
    exact greedy tokens of an uninterrupted run."""
    configure_event_log(str(tmp_path / "events.jsonl"))
    try:
        cfg, params, router, replicas, clock = _fleet(
            n=2, max_new=6, health_kw={"eject_after": 2,
                                       "probe_cooldown_s": 1e9})
        p = np.arange(3, 8, dtype=np.int32)
        h = router.submit(p)
        assert h.replica_id == 0
        # step until the first tokens stream (the unified ragged step
        # prefills within the step, so tokens land a round later)
        for _ in range(4):
            router.step(params)
            clock.advance(0.05)
            if h.stream.tokens:
                break
        streamed = len(h.stream.tokens)
        assert 0 < streamed < 6
        replicas[0].kill()
        _drive(router, clock, params)
        assert h.state == RequestState.DONE
        assert h.replica_id == 1 and h.failovers == 1
        assert h.stream.result() == _greedy_ref(params, cfg, p, 6)
    finally:
        configure_event_log(None)
    events = [json.loads(l) for l in
              (tmp_path / "events.jsonl").read_text().splitlines()]
    fo = [e for e in events if e["kind"] == "failover"]
    assert fo and fo[0]["from_replica"] == 0 and fo[0]["to_replica"] == 1
    assert fo[0]["streamed"] == streamed        # genuinely mid-stream
    ej = [e for e in events if e["kind"] == "replica_ejected"]
    assert ej and ej[0]["replica"] == 0


def test_fully_delivered_request_salvaged_not_failed():
    """A replica dying after streaming the LAST budgeted token but
    before the finish callback: the request closes complete even when
    no failover budget remains — the consumer already holds everything."""
    cfg, params, router, replicas, clock = _fleet(
        n=2, router_kw={"max_failovers": 0})
    h = router.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=2)
    h.stream.push(11)
    h.stream.push(22)           # full budget delivered, close lost
    router._failover(h, 0, "died before finish callback")
    assert h.state == RequestState.DONE
    assert h.stream.result() == [11, 22]
    assert router.failed_total == 0
    # same salvage when the stream already ended on EOS short of the
    # budget: resubmitting would decode PAST the EOS on the sibling
    cfg2, params2, router2, _, _ = _fleet(
        n=2, eos=99, router_kw={"max_failovers": 3})
    h2 = router2.submit(np.arange(1, 7, dtype=np.int32),
                        max_new_tokens=6)
    h2.stream.push(42)
    h2.stream.push(99)          # EOS streamed, close lost
    router2._failover(h2, 0, "died before finish callback")
    assert h2.state == RequestState.DONE
    assert h2.stream.result() == [42, 99]


def test_graceful_drain_hands_queued_to_siblings():
    cfg, params, router, replicas, clock = _fleet(
        n=2, num_slots=1, router_kw={"load_band": 8})
    rng = np.random.RandomState(23)
    base = rng.randint(1, cfg.vocab_size, (8,)).astype(np.int32)
    hs = [router.submit(np.concatenate([base, [i + 1]]).astype(np.int32))
          for i in range(3)]
    assert all(h.replica_id == 0 for h in hs)   # affinity coalesced
    router.step(params)                 # one running, two queued on r0
    clock.advance(0.05)
    running = [h for h in hs if h.handle.state == RequestState.RUNNING]
    queued = [h for h in hs if h.handle.state == RequestState.QUEUED]
    assert len(running) == 1 and len(queued) == 2
    router.drain(0)
    # queued requests handed to the sibling immediately; the in-flight
    # stream finishes where it is
    assert all(h.replica_id == 1 for h in queued)
    assert running[0].replica_id == 0
    h_new = router.submit(np.concatenate([base, [9]]).astype(np.int32))
    assert h_new.replica_id == 1        # no new admissions while draining
    _drive(router, clock, params)
    assert all(h.state == RequestState.DONE for h in hs + [h_new])
    assert running[0].replica_id == 0   # finished in place, no failover
    assert running[0].failovers == 0
    st = router.statusz()
    assert st["replicas"]["0"]["draining"] is True
    router.undrain(0)
    assert router.fleet_health() == "ok"


def test_drain_handoff_exempt_from_sibling_queue_cap():
    """A drain handoff landing on a sibling already at its queue cap is
    remediation: the sibling sheds a FRESH request around it, never the
    handed-off one — the 'queued requests hand off to siblings' drain
    contract survives load."""
    cfg, params, router, replicas, clock = _fleet(
        n=2, num_slots=1, sched_kw={"max_queue_depth": 1})
    rng = np.random.RandomState(27)
    ps = [rng.randint(1, cfg.vocab_size, (6,)).astype(np.int32)
          for _ in range(4)]
    h0 = router.submit(ps[0])           # -> r0 (admitted next step)
    h1 = router.submit(ps[1])           # -> r1
    router.step(params)
    clock.advance(0.05)
    h2 = router.submit(ps[2])           # queued on r0
    h3 = router.submit(ps[3])           # queued on r1 (AT its cap)
    assert h2.replica_id == 0 and h3.replica_id == 1
    router.drain(0)                     # h2 hands off to the full r1
    assert h2.replica_id == 1
    _drive(router, clock, params)
    assert h2.state == RequestState.DONE        # handoff survived
    assert h3.state == RequestState.SHED        # the fresh victim shed
    assert all(h.state == RequestState.DONE for h in (h0, h1))


def test_drain_of_half_open_replica_releases_probe_slot():
    """Draining a replica whose probe is still queued must hand the
    probe off AND clear the probe bookkeeping, so after undrain the
    replica can be probed (and re-admitted) again."""
    cfg, params, router, replicas, clock = _fleet(
        n=2, health_kw={"eject_after": 1, "probe_cooldown_s": 0.2})
    replicas[0].stall(0.15)
    router.submit(np.arange(1, 7, dtype=np.int32))
    router.step(params)                 # r0 -> EJECTED
    clock.advance(0.3)
    router.step(params)                 # cooldown over -> HALF_OPEN
    h_probe = router.submit(np.arange(2, 8, dtype=np.int32))
    assert h_probe.replica_id == 0      # queued probe
    router.drain(0)                     # probe hands off to the sibling
    assert h_probe.replica_id == 1
    _drive(router, clock, params)
    assert h_probe.state == RequestState.DONE
    router.undrain(0)
    h_new = router.submit(np.arange(3, 9, dtype=np.int32))
    assert h_new.replica_id == 0        # a fresh probe is admitted
    _drive(router, clock, params)
    assert replicas[0].health.state == ReplicaState.HEALTHY


def test_router_index_capped_with_lru_eviction():
    cfg, params, router, replicas, clock = _fleet(
        n=1, router_kw={"index_max_nodes": 2})
    rng = np.random.RandomState(29)
    for _ in range(4):
        router.submit(rng.randint(1, cfg.vocab_size, (8,))
                      .astype(np.int32))
    _drive(router, clock, params)
    assert router.statusz()["index_nodes"]["0"] <= 2


def test_run_finishing_on_final_step_does_not_raise():
    cfg, params, router, replicas, clock = _fleet(n=1)
    router.submit(np.arange(1, 5, dtype=np.int32))
    steps_needed = 0
    probe = _fleet(n=1)
    probe[2].submit(np.arange(1, 5, dtype=np.int32))
    while probe[2].pending:
        probe[2].step(params)
        steps_needed += 1
    router.run(params, max_steps=steps_needed)      # exact budget: ok


def test_scheduler_degrade_treated_as_replica_death():
    """A replica whose scheduler burns its retry budget (engine step
    failing INSIDE the scheduler) is force-ejected and its requests
    fail over — the drained replica-level errors never surface to the
    router's consumers."""
    cfg, params, router, replicas, clock = _fleet(
        n=2, sched_kw={"max_step_retries": 0},
        health_kw={"probe_cooldown_s": 1e9})
    h = router.submit(np.arange(1, 7, dtype=np.int32))
    assert h.replica_id == 0
    router.step(params)
    clock.advance(0.05)

    def always_fail(p):
        raise RuntimeError("persistent device fault")

    replicas[0].engine.step = always_fail
    _drive(router, clock, params)
    assert replicas[0].degraded
    assert replicas[0].health.state == ReplicaState.EJECTED
    assert h.state == RequestState.DONE and h.replica_id == 1
    assert h.stream.result() == _greedy_ref(
        params, cfg, np.arange(1, 7, dtype=np.int32), 4)


def test_all_replicas_down_parks_then_recovers():
    cfg, params, router, replicas, clock = _fleet(
        n=2, health_kw={"eject_after": 1, "probe_cooldown_s": 0.2})
    replicas[0].stall(0.15)
    replicas[1].stall(0.15)
    fo0 = _counter_total("paddle_router_failovers_total")
    h0 = router.submit(np.arange(1, 7, dtype=np.int32))
    router.step(params)                 # both raise -> both EJECTED
    clock.advance(0.05)
    assert router.fleet_health() == "breached"
    h1 = router.submit(np.arange(2, 8, dtype=np.int32))
    assert h1.replica_id is None        # parked: nothing routable
    assert router.statusz()["parked"] >= 1
    clock.advance(0.3)                  # cooldowns + stalls lapse
    router.step(params)
    # half-open replicas CAN take their probes: not "breached" (a 503
    # here would let a load balancer starve the probes forever)
    assert router.fleet_health() == "degraded"
    _drive(router, clock, params)
    assert h0.state == RequestState.DONE
    assert h1.state == RequestState.DONE
    assert router.fleet_health() == "ok"
    # failovers_total counts actual sibling resubmissions: h0's parked
    # failover is counted once it finally dispatched, h1 never failed
    # over — the all-down window must not inflate the metric
    assert (_counter_total("paddle_router_failovers_total") - fo0
            == h0.failovers)


# ---------------------------------------------------------------------------
# health tracker unit behavior
# ---------------------------------------------------------------------------

def test_parked_request_deadline_beats_late_recovery():
    """A deadline that lapses while a request is parked (fleet down)
    sheds it as deadline even if a replica heals the same step — it is
    never re-routed with a zero-clamped deadline and served."""
    cfg, params, router, replicas, clock = _fleet(
        n=2, health_kw={"eject_after": 1, "probe_cooldown_s": 0.2})
    replicas[0].stall(0.05)
    replicas[1].stall(0.05)
    h0 = router.submit(np.arange(1, 7, dtype=np.int32))
    router.step(params)                 # both eject; h0 parks
    clock.advance(0.05)
    h1 = router.submit(np.arange(2, 8, dtype=np.int32), deadline_ms=100)
    assert h1.replica_id is None
    clock.advance(0.5)                  # deadline AND cooldowns lapse
    router.step(params)
    assert h1.state == RequestState.SHED
    assert h1.stream.finish_reason == "shed:deadline"
    _drive(router, clock, params)
    assert h0.state == RequestState.DONE    # no deadline: probe + serve


def test_wedged_replica_trips_watchdog_and_fails_over():
    """A replica whose steps RETURN but serve nothing (engine wedged,
    no tokens, no completions) must not look healthy forever: the
    progress-gated watchdog ejects it and its requests fail over."""
    cfg, params, router, replicas, clock = _fleet(
        n=2, health_kw={"suspect_after": 1, "eject_after": 2,
                        "watchdog_s": 0.2, "probe_cooldown_s": 1e9})
    h = router.submit(np.arange(1, 7, dtype=np.int32))
    assert h.replica_id == 0
    replicas[0].engine.step = lambda params: 0      # wedged, not raising
    _drive(router, clock, params, dt=0.15)
    assert replicas[0].health.state == ReplicaState.EJECTED
    assert "watchdog" in replicas[0].health.last_failure
    assert h.state == RequestState.DONE and h.replica_id == 1
    assert h.stream.result() == _greedy_ref(
        params, cfg, np.arange(1, 7, dtype=np.int32), 4)


def test_health_tracker_state_machine():
    clock = FakeClock()
    t = HealthTracker(HealthConfig(suspect_after=1, eject_after=3,
                                   probe_cooldown_s=1.0,
                                   cooldown_multiplier=2.0), clock=clock)
    assert t.state == ReplicaState.HEALTHY and t.accepting
    t.record_failure("boom")
    assert t.state == ReplicaState.SUSPECT and t.accepting
    t.record_success()
    assert t.state == ReplicaState.HEALTHY
    for _ in range(3):
        t.record_failure("boom")
    assert t.state == ReplicaState.EJECTED and not t.accepting
    clock.advance(0.5)
    assert t.tick() == ReplicaState.EJECTED     # cooldown not over
    clock.advance(0.6)
    assert t.tick() == ReplicaState.HALF_OPEN
    t.record_success()                  # idle step success: NOT enough
    assert t.state == ReplicaState.HALF_OPEN
    t.record_failure("probe died")      # probe failure: re-eject, 2x
    assert t.state == ReplicaState.EJECTED
    assert t.cooldown_s == pytest.approx(2.0)
    clock.advance(2.1)
    assert t.tick() == ReplicaState.HALF_OPEN
    t.record_probe_success()            # probe completion closes it
    assert t.state == ReplicaState.HEALTHY
    assert t.cooldown_s == pytest.approx(1.0)   # backoff reset


def test_health_tracker_watchdog():
    clock = FakeClock()
    t = HealthTracker(HealthConfig(suspect_after=1, eject_after=2,
                                   watchdog_s=1.0), clock=clock)
    t.record_success()
    clock.advance(0.5)
    assert not t.check_watchdog(busy=True)      # within the window
    clock.advance(1.0)
    assert not t.check_watchdog(busy=False)     # idle is not stuck
    assert t.check_watchdog(busy=True)          # silent + busy = failure
    assert t.state == ReplicaState.SUSPECT
    # ONE failure per silent window: an immediate re-check must not
    # double-charge (a raising replica would otherwise eject at half
    # the configured threshold)
    assert not t.check_watchdog(busy=True)
    clock.advance(1.1)                          # another full window
    assert t.check_watchdog(busy=True)
    assert t.state == ReplicaState.EJECTED
    # the watchdog window restarts at HALF_OPEN: last_ok_t froze while
    # ejected, and a stale stamp must not kill the probe before it runs
    clock.advance(t.cooldown_s + 0.1)
    assert t.tick() == ReplicaState.HALF_OPEN
    assert not t.check_watchdog(busy=True)      # fresh window
    assert t.state == ReplicaState.HALF_OPEN
    clock.advance(1.5)                          # probe silent too long
    assert t.check_watchdog(busy=True)
    assert t.state == ReplicaState.EJECTED


# ---------------------------------------------------------------------------
# fault injector: replica-scoped one-shot events
# ---------------------------------------------------------------------------

def test_fault_injector_replica_scoped_events():
    inj = FaultInjector(schedule=[
        Fault("replica_die", 3, replica=1),
        Fault("replica_stall", 2),              # unscoped wildcard
    ])
    assert not inj.fire("replica_die", 3, replica=0)    # wrong replica
    assert not inj.fire("replica_die", 2, replica=1)    # wrong step
    assert inj.fire("replica_die", 3, replica=1)
    assert not inj.fire("replica_die", 3, replica=1)    # one-shot
    # a wildcard fault is consumed by the first replica that asks
    assert inj.fire("replica_stall", 2, replica=0)
    assert not inj.fire("replica_stall", 2, replica=1)
    assert inj.fired == [("replica_die", 3, 1), ("replica_stall", 2, 0)]
    # replica-scoped faults never fire for unscoped (trainer) queries
    inj2 = FaultInjector(schedule=[Fault("step_error", 5, replica=2)])
    assert not inj2.fire("step_error", 5)
    assert inj2.fire("step_error", 5, replica=2)
    # seeded replica schedules are reproducible, 1-based (router steps
    # start at 1, so a step-0 fault could never fire), and duplicate-free
    # (the router consumes at most one triple per step, so a duplicate
    # would silently never fire)
    a = FaultInjector.seeded_replicas(7, 20, 4)
    b = FaultInjector.seeded_replicas(7, 20, 4)
    assert a.schedule == b.schedule and a.schedule
    for seed in range(16):
        sched = FaultInjector.seeded_replicas(seed, 3, 2, n_faults=6)
        assert all(1 <= f.step <= 3 for f in sched.schedule)
        assert len(set(sched.schedule)) == len(sched.schedule) == 6
    tiny = FaultInjector.seeded_replicas(0, 1, 1,
                                         events=("replica_die",),
                                         n_faults=5)
    assert len(tiny.schedule) == 1          # clamped to the fault space


# ---------------------------------------------------------------------------
# chaos acceptance
# ---------------------------------------------------------------------------

def _chaos_trace(inject, event_path=None, speculative=False):
    """One deterministic 4-replica fleet run: 12 requests submitted on a
    fixed step schedule, optionally with an injected replica death (mid-
    decode) and a stall. Returns (per-request outputs, router, monitor,
    handles)."""
    if event_path is not None:
        configure_event_log(str(event_path))
    try:
        injector = None
        if inject:
            injector = FaultInjector(schedule=[
                Fault("replica_die", 3, replica=1),
                Fault("replica_stall", 5, replica=2),
            ])
        cfg, params, router, replicas, clock = _fleet(
            n=4, max_new=8, num_slots=2, chunk=2,
            health_kw={"suspect_after": 1, "eject_after": 2,
                       "probe_cooldown_s": 0.4},
            router_kw={"failover_backoff_s": 0.05, "stall_s": 0.5},
            injector=injector, speculative=speculative)
        monitor = router.make_slo_monitor(completion_target=0.95,
                                          min_events=1)
        rng = np.random.RandomState(31)
        base = rng.randint(1, cfg.vocab_size, (4,)).astype(np.int32)
        prompts = []
        for i in range(12):
            if i % 3 == 0:      # a third share a 4-token system prefix
                tail = rng.randint(1, cfg.vocab_size, (3,))
                prompts.append(np.concatenate([base, tail])
                               .astype(np.int32))
            else:
                n = int(rng.randint(4, 9))
                prompts.append(rng.randint(1, cfg.vocab_size, (n,))
                               .astype(np.int32))
        submissions = {0: prompts[:8], 6: prompts[8:10], 16: prompts[10:]}
        handles = []
        step = 0
        while step < 300:
            for p in submissions.pop(step, []):
                handles.append(router.submit(p))
            if not submissions and not router.pending:
                break
            router.step(params)
            clock.advance(0.05)
            step += 1
        assert step < 300, router.statusz()
        outputs = [h.stream.result() for h in handles]
        return outputs, prompts, router, monitor, handles, params, cfg
    finally:
        if event_path is not None:
            configure_event_log(None)


def test_chaos_fleet_byte_identical_acceptance(tmp_path):
    """ISSUE 6 acceptance: 4-replica fleet, deterministic injected
    replica death mid-decode plus one stall — the router ejects, drains,
    fails over; every accepted request completes, greedy outputs are
    byte-identical to the fault-free run, no consumer hangs, and the
    fleet SLO never breaches (failover remediation excluded)."""
    clean, _, _, _, _, _, _ = _chaos_trace(inject=False)
    ev = tmp_path / "chaos_events.jsonl"
    chaos, prompts, router, monitor, handles, params, cfg = _chaos_trace(
        inject=True, event_path=ev)

    # every accepted request completed; zero consumer hangs
    assert all(h.state == RequestState.DONE for h in handles)
    assert all(h.stream.finished for h in handles)
    # greedy outputs byte-identical to the no-fault run
    assert chaos == clean
    # ... and to the full-reforward oracle (spot checks)
    for i in (0, 3):
        assert chaos[i] == _greedy_ref(params, cfg, prompts[i], 8)
    # no terminal failures/sheds -> fleet SLO untouched
    assert router.failed_total == 0 and router.shed_total == 0
    assert not monitor.breached()
    assert monitor.health() == "ok"

    events = [json.loads(l) for l in ev.read_text().splitlines()]
    kinds = [e["kind"] for e in events]
    ejected = [e for e in events if e["kind"] == "replica_ejected"]
    assert {e["replica"] for e in ejected} >= {1, 2}
    failovers = [e for e in events if e["kind"] == "failover"]
    assert failovers and not any(e.get("exhausted") for e in failovers)
    assert any(e["streamed"] > 0 for e in failovers)   # mid-decode death
    # the stalled replica recovered through the half-open probe
    recovered = [e for e in events if e["kind"] == "replica_recovered"]
    assert any(e["replica"] == 2 and e["via"] == "probe"
               for e in recovered)
    assert "slo_breach" not in kinds
    # the dead replica stays quarantined; the stalled one rejoined
    assert router.replicas[1].health.state in (ReplicaState.EJECTED,
                                               ReplicaState.HALF_OPEN)
    assert not router.replicas[1].health.accepting
    assert router.replicas[2].health.state == ReplicaState.HEALTHY


def test_chaos_fleet_green_with_speculation(tmp_path):
    """ISSUE 9 acceptance: the chaos suite stays green with speculative
    decoding enabled on every replica — same deterministic death+stall
    schedule, and the fleet's greedy outputs are byte-identical to BOTH
    the fault-free speculative run and the non-speculative chaos run
    (speculation is verify-then-commit, failover replays committed
    prefixes, so faults can never surface a drafted-but-unverified
    token)."""
    clean, _, _, _, _, _, _ = _chaos_trace(inject=False, speculative=True)
    plain, _, _, _, _, _, _ = _chaos_trace(inject=True)
    ev = tmp_path / "spec_chaos_events.jsonl"
    chaos, prompts, router, monitor, handles, params, cfg = _chaos_trace(
        inject=True, event_path=ev, speculative=True)

    assert all(h.state == RequestState.DONE for h in handles)
    assert all(h.stream.finished for h in handles)
    assert chaos == clean == plain
    for i in (0, 3):
        assert chaos[i] == _greedy_ref(params, cfg, prompts[i], 8)
    assert router.failed_total == 0 and router.shed_total == 0
    assert not monitor.breached()
    # speculation actually ran on the fleet (replica-labelled stats)
    drafted = sum(r.engine.spec.stats["drafted"]
                  for r in router.replicas.values())
    assert drafted > 0
    events = [json.loads(l) for l in ev.read_text().splitlines()]
    assert {e["kind"] for e in events} >= {"replica_ejected", "failover"}


def test_infeasible_request_rejected_without_poisoning_breakers():
    """A request no replica could EVER serve raises at submit (caller
    error) instead of being mistaken for replica failures and ejecting
    the whole fleet."""
    cfg, params, router, replicas, clock = _fleet(n=2)
    with pytest.raises(ValueError, match="max_seq_len"):
        router.submit(np.ones(40, np.int32))
    assert router.pending == 0
    assert all(r.health.state == ReplicaState.HEALTHY for r in replicas)
    assert router.accepted_total == 0       # never accepted


def test_diagserver_fleet_view():
    from paddle_tpu.observability.server import DiagServer
    cfg, params, router, replicas, clock = _fleet(n=2)
    srv = DiagServer()
    srv.attach_router(router)
    st = srv.statusz()
    assert st["health"] == "ok"
    assert set(st["router"]["replicas"]) == {"0", "1"}
    replicas[0].kill()
    replicas[1].kill()
    router.submit(np.arange(1, 7, dtype=np.int32))
    for _ in range(4):
        router.step(params)
        clock.advance(0.05)
    assert srv.health() == "breached"


def _fresh_handle(rid, clock, max_new=4, num_slots=2, chunk=2, seed=3,
                  page_size=4, speculative=False, sched_kw=None,
                  health_kw=None):
    """A replacement ReplicaHandle reusing ``rid`` (the
    ``replace_replica`` recovery path: fresh engine, same id — its
    ``paddle_serving_r<rid>`` namespace re-registers)."""
    cfg = L.llama_tiny(num_hidden_layers=2)
    sched_kw = dict(sched_kw or {})
    sched_kw.setdefault("max_step_retries", 1)
    sched_kw.setdefault("retry_backoff_s", 0.01)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=max_new, seed=seed),
        num_slots=num_slots, page_size=page_size, max_seq_len=32,
        chunk=chunk, speculative=speculative)
    return ReplicaHandle(rid, eng, config=SchedulerConfig(**sched_kw),
                         health_config=HealthConfig(**(health_kw or {})),
                         clock=clock, sleep=clock.sleep)


def test_replace_replica_reused_id_metrics_idempotent():
    """Satellite (ISSUE 14): a reused replica id re-registers the
    ``paddle_serving_r<id>`` metrics namespace — the registry sink must
    REPLACE (never raise on the re-declared families), the scrape must
    carry exactly one family section per name, and its values must come
    from the NEW sink. Two full replace cycles, speculation + SLO
    monitors attached, prove the whole per-replica telemetry surface is
    idempotent under id reuse."""
    cfg, params, router, replicas, clock = _fleet(
        n=2, speculative=True, health_kw={"eject_after": 1})
    for r in replicas:
        r.make_slo_monitor()
    h = [router.submit(np.arange(1, 6, dtype=np.int32))
         for _ in range(3)]
    _drive(router, clock, params)
    assert all(q.state == RequestState.DONE for q in h)
    old_submitted = get_registry().snapshot()[
        "paddle_serving_r0"]["counters"]["requests_submitted_total"]
    assert old_submitted > 0
    for _cycle in range(2):      # two replace cycles: reuse of a reuse
        router.replicas[0].kill()
        router.eject_replica(0, "test: chip torn")
        fresh = _fresh_handle(0, clock, speculative=True,
                              health_kw={"eject_after": 1})
        router.replace_replica(fresh)       # must not raise
        fresh.make_slo_monitor()            # SLO families re-register too
    text = get_registry().prometheus_text()
    lines = [ln for ln in text.splitlines()
             if ln.startswith("paddle_serving_r0_requests_submitted_total")]
    # exactly one sample line for the family, and it reads the NEW
    # (empty) sink — not the retired one that served the storm
    assert lines == ["paddle_serving_r0_requests_submitted_total 0"], lines
    # the replaced replica serves again and its counters land in /metrics
    h2 = router.submit(np.arange(1, 6, dtype=np.int32))
    _drive(router, clock, params)
    assert h2.state == RequestState.DONE
    text = get_registry().prometheus_text()
    assert sum(ln.startswith("paddle_serving_r0_requests_submitted_total")
               for ln in text.splitlines()) == 1


def test_replace_replica_invalidates_affinity_index():
    """Satellite (ISSUE 14): the router-side radix affinity index for a
    replaced (or mesh-resized) replica must drop — a replacement engine's
    pool is COLD, so surviving synthetic page entries would route
    affinity traffic to prefixes the new pool no longer holds."""
    cfg, params, router, replicas, clock = _fleet(
        n=2, health_kw={"eject_after": 1})
    shared = np.arange(1, 13, dtype=np.int32)      # 3 full 4-token blocks
    h = [router.submit(shared) for _ in range(2)]
    _drive(router, clock, params)
    assert all(q.state == RequestState.DONE for q in h)
    warm = [rid for rid in router.replicas
            if router._overlap_tokens(rid, shared) > 0]
    assert warm, "storm should have warmed at least one index slice"
    victim = warm[0]
    assert router.statusz()["index_nodes"][str(victim)] > 0
    router.replicas[victim].kill()
    router.eject_replica(victim, "test: resize")
    router.replace_replica(_fresh_handle(victim, clock,
                                         health_kw={"eject_after": 1}))
    assert router._overlap_tokens(victim, shared) == 0
    assert router.statusz()["index_nodes"][str(victim)] == 0
    # the public invalidation hook the elastic resize controller uses
    other = [rid for rid in router.replicas if rid != victim]
    for rid in other:
        router.invalidate_index(rid)
        assert router._overlap_tokens(rid, shared) == 0
