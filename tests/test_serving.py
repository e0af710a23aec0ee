"""paddle_tpu.serving: SLO-aware scheduler, streaming, backpressure,
robustness and metrics over the continuous-batching engine (ISSUE 1).

Seeded arrival traces on the tiny stacked llama; the engine seed plus a
deterministic trace makes every assertion reproducible."""

import re
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from paddle_tpu.models import llama as L
from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.profiler.record import host_recorder
from paddle_tpu.serving import (RequestState, SchedulerConfig, ServingError,
                                ServingMetrics, ServingScheduler)

from _oracle import greedy_reforward as _greedy_ref

REPO = Path(__file__).resolve().parent.parent


class FakeClock:
    """Deterministic scheduler clock; sleep() advances it."""

    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt

    def advance(self, dt):
        self.t += dt


def _setup(max_new=5, num_slots=2, chunk=2, seed=3, do_sample=False,
           max_queue_depth=64, clock=None, **sched_kw):
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=seed)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=max_new, do_sample=do_sample,
                              seed=seed),
        num_slots=num_slots, page_size=4, max_seq_len=32, chunk=chunk)
    clock = clock or FakeClock()
    sched = ServingScheduler(
        eng, SchedulerConfig(max_queue_depth=max_queue_depth, **sched_kw),
        clock=clock, sleep=clock.sleep)
    return cfg, params, eng, sched, clock


def _prompts(cfg, n, rng_seed=0, lens=(3, 8)):
    rng = np.random.RandomState(rng_seed)
    return [rng.randint(1, cfg.vocab_size,
                        (int(rng.randint(lens[0], lens[1] + 1)),)
                        ).astype(np.int32) for _ in range(n)]


# ---------------------------------------------------------------------------
# scheduling policy
# ---------------------------------------------------------------------------

def test_priority_ordering_fifo_within_class():
    """With one slot, admission strictly follows (priority, arrival):
    engine rids are handed out in admission order."""
    cfg, params, eng, sched, _ = _setup(num_slots=1)
    ps = _prompts(cfg, 5, rng_seed=1)
    # arrival order: priorities 2, 0, 1, 0, 2
    handles = [sched.submit(p, priority=pr)
               for p, pr in zip(ps, (2, 0, 1, 0, 2))]
    sched.run(params, max_steps=200)
    assert all(h.state == RequestState.DONE for h in handles)
    admission = sorted(range(5), key=lambda i: handles[i].engine_rid)
    # priority 0 first (FIFO: rid1 before rid3), then 1, then 2 (rid0, rid4)
    assert admission == [1, 3, 2, 0, 4]


def test_outputs_match_engine_serve_oracle():
    """The scheduler is a lifecycle layer: per-request tokens equal the
    greedy full-reforward oracle, same as engine.serve."""
    cfg, params, eng, sched, _ = _setup(max_new=4, num_slots=2)
    ps = _prompts(cfg, 4, rng_seed=2)
    hs = [sched.submit(p) for p in ps]
    sched.run(params, max_steps=200)
    for p, h in zip(ps, hs):
        assert h.stream.result() == _greedy_ref(params, cfg, p, 4)


def test_per_request_max_new_tokens():
    cfg, params, eng, sched, _ = _setup(max_new=5)
    ps = _prompts(cfg, 2, rng_seed=3)
    h_short = sched.submit(ps[0], max_new_tokens=2)
    h_long = sched.submit(ps[1])
    sched.run(params, max_steps=200)
    assert len(h_short.stream.tokens) == 2
    assert len(h_long.stream.tokens) == 5
    assert h_short.stream.result() == _greedy_ref(params, cfg, ps[0], 2)


def test_queue_overflow_sheds_lowest_priority_latest_deadline():
    cfg, params, eng, sched, clock = _setup(max_queue_depth=3)
    ps = _prompts(cfg, 5, rng_seed=4)
    h0 = sched.submit(ps[0], priority=0, deadline_ms=100)
    h1 = sched.submit(ps[1], priority=1, deadline_ms=50)
    h2 = sched.submit(ps[2], priority=1, deadline_ms=500)   # latest deadline
    h3 = sched.submit(ps[3], priority=1, deadline_ms=200)   # overflow: shed h2
    assert h2.state == RequestState.SHED
    assert h2.stream.finish_reason == "shed:queue_full"
    with pytest.raises(ServingError) as ei:
        h2.stream.result()
    assert ei.value.code == "shed_queue_full"
    # no-deadline request sheds before deadlined peers of the same class
    h4 = sched.submit(ps[4], priority=1)
    assert h4.state == RequestState.SHED
    assert sched.metrics.shed == {"queue_full": 2}
    sched.run(params, max_steps=200)
    assert all(h.state == RequestState.DONE for h in (h0, h1, h3))


def test_deadline_expiry_sheds_queued_request():
    """A request still queued past its deadline is shed, not decoded."""
    cfg, params, eng, sched, clock = _setup(num_slots=1)
    ps = _prompts(cfg, 2, rng_seed=5)
    h_ok = sched.submit(ps[0], priority=0)
    h_late = sched.submit(ps[1], priority=1, deadline_ms=50)
    clock.advance(0.2)          # deadline (50 ms) lapses while queued
    sched.run(params, max_steps=200)
    assert h_ok.state == RequestState.DONE
    assert h_late.state == RequestState.SHED
    assert h_late.stream.finish_reason == "shed:deadline"
    assert h_late.stream.tokens == []
    assert sched.metrics.shed == {"deadline": 1}


def test_mid_decode_cancellation_frees_slot_and_pages():
    cfg, params, eng, sched, _ = _setup(max_new=8, num_slots=2, chunk=2)
    free0 = eng.mgr.num_free_pages
    ps = _prompts(cfg, 2, rng_seed=6)
    h0 = sched.submit(ps[0])
    h1 = sched.submit(ps[1])
    # step until h0 is mid-decode (prefill rides the unified ragged step,
    # so the first tokens land a round after admission, not with it)
    for _ in range(10):
        sched.step(params)
        if h0.stream.tokens:
            break
    assert h0.state == RequestState.RUNNING and len(h0.stream.tokens) >= 1
    assert sched.cancel(h0.rid)
    # slot + pages reclaimed immediately, stream closed as cancelled
    assert eng._slot_rid.count(None) == 1
    assert h0.state == RequestState.CANCELLED
    assert h0.stream.finish_reason == "cancelled"
    assert not sched.cancel(h0.rid)         # idempotent: already finished
    sched.run(params, max_steps=200)        # survivor completes normally
    assert h1.stream.result() == _greedy_ref(params, cfg, ps[1], 8)
    assert eng.mgr.num_free_pages == free0
    assert sched.metrics.counters["requests_cancelled_total"] == 1


def test_on_token_callback_can_cancel_own_request():
    """A stop-sequence-style on_token callback may cancel its own request
    mid-chunk; the engine's unpack loop must survive the reentrant retire
    and keep delivering that chunk's tokens to the other slots."""
    cfg, params, eng, sched, _ = _setup(max_new=6, num_slots=2, chunk=2)
    free0 = eng.mgr.num_free_pages
    ps = _prompts(cfg, 2, rng_seed=16)
    box = {}
    h0 = sched.submit(ps[0], on_token=lambda t: sched.cancel(box["rid"]))
    box["rid"] = h0.rid
    h1 = sched.submit(ps[1])
    sched.run(params, max_steps=200)
    assert h0.state == RequestState.CANCELLED
    assert len(h0.stream.tokens) == 1       # stopped after the first token
    assert h1.state == RequestState.DONE
    assert h1.stream.result() == _greedy_ref(params, cfg, ps[1], 6)
    assert eng.mgr.num_free_pages == free0


def test_finished_requests_evicted_from_registry():
    """The scheduler registry must not grow without bound in a
    long-running server: resolved requests are evicted (the caller keeps
    the handle; cancel() on a finished rid stays a no-op)."""
    cfg, params, eng, sched, _ = _setup()
    hs = [sched.submit(p) for p in _prompts(cfg, 3, rng_seed=17)]
    sched.run(params, max_steps=200)
    assert all(h.state == RequestState.DONE for h in hs)
    assert sched._requests == {}
    assert not sched.cancel(hs[0].rid)


def test_cancel_queued_request_never_reaches_engine():
    cfg, params, eng, sched, _ = _setup(num_slots=1)
    ps = _prompts(cfg, 2, rng_seed=7)
    h0 = sched.submit(ps[0])
    h1 = sched.submit(ps[1])
    assert sched.cancel(h1.rid)
    sched.run(params, max_steps=200)
    assert h0.state == RequestState.DONE
    assert h1.state == RequestState.CANCELLED and h1.engine_rid is None


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------

def test_tokens_stream_at_chunk_cadence():
    """Tokens surface after every step (chunk granularity), not at the
    end; drain() and on_token agree with the final result."""
    cfg, params, eng, sched, _ = _setup(max_new=6, num_slots=1, chunk=2)
    seen_cb = []
    h = sched.submit(_prompts(cfg, 1, rng_seed=8)[0],
                     on_token=seen_cb.append)
    drained = []
    growth = []
    while sched.pending:
        sched.step(params)
        new = h.stream.drain()
        drained.extend(new)
        growth.append(len(new))
    assert drained == seen_cb == h.stream.result()
    assert len(drained) == 6
    # incremental: at least one step delivered a strict prefix
    assert any(0 < g < 6 for g in growth)


def test_blocking_iterator_from_consumer_thread():
    cfg, params, eng, sched, _ = _setup(max_new=4, num_slots=1, chunk=2)
    h = sched.submit(_prompts(cfg, 1, rng_seed=9)[0])
    got = []
    t = threading.Thread(target=lambda: got.extend(h.stream))
    t.start()
    sched.run(params, max_steps=200)
    t.join(timeout=10)
    assert not t.is_alive()
    assert got == h.stream.result() and len(got) == 4


def test_infeasible_request_rejected_at_submit():
    """A request that could never be admitted (prompt+budget beyond
    max_seq_len, or more KV pages than the whole pool) raises ValueError
    at submit instead of leaking into the queue or degrading the loop."""
    cfg, params, eng, sched, _ = _setup(max_new=5)      # max_seq_len=32
    with pytest.raises(ValueError, match="max_seq_len"):
        sched.submit(np.ones(40, np.int32))
    eng2 = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=5), num_slots=2, page_size=4,
        max_seq_len=32, num_pages=2, chunk=2)           # 1 usable page
    sched2 = ServingScheduler(eng2)
    with pytest.raises(ValueError, match="KV pages"):
        sched2.submit(np.ones(8, np.int32))             # needs 4 pages
    assert sched.pending == 0 and sched2.pending == 0   # nothing leaked


def test_page_pressure_no_priority_inversion():
    """Free slot but scarce pages: waiting requests stay in the SCHEDULER
    queue (the engine FIFO never buffers), so a later higher-priority
    submission is admitted first once pages free up."""
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=3)
    # pool = 2 usable pages = exactly one request (4 prompt + 4 new)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=4), num_slots=2, page_size=4,
        max_seq_len=32, num_pages=3, chunk=2)
    clock = FakeClock()
    sched = ServingScheduler(eng, SchedulerConfig(), clock=clock,
                             sleep=clock.sleep)
    rng = np.random.RandomState(15)

    def prompt():
        return rng.randint(1, cfg.vocab_size, (4,)).astype(np.int32)

    h_a = sched.submit(prompt(), priority=1)
    sched.step(params)                      # A admitted, pool exhausted
    h_b = sched.submit(prompt(), priority=1)
    h_c = sched.submit(prompt(), priority=0)  # later arrival, more urgent
    while sched.pending:
        sched.step(params)
        assert not eng._queue               # engine FIFO stays empty
    assert all(h.state == RequestState.DONE for h in (h_a, h_b, h_c))
    assert h_c.engine_rid < h_b.engine_rid  # no inversion behind the FIFO


# ---------------------------------------------------------------------------
# robustness
# ---------------------------------------------------------------------------

def test_injected_step_failure_retried_with_backoff():
    cfg, params, eng, sched, clock = _setup(
        max_new=4, retry_backoff_s=0.05, retry_backoff_multiplier=2.0,
        max_step_retries=3)
    real_step = eng.step
    fails = {"n": 2}
    calls = []

    def flaky_step(p):
        calls.append(clock())
        if fails["n"] > 0:
            fails["n"] -= 1
            raise RuntimeError("injected device fault")
        return real_step(p)

    eng.step = flaky_step
    h = sched.submit(_prompts(cfg, 1, rng_seed=10)[0])
    sched.run(params, max_steps=200)
    assert h.state == RequestState.DONE
    assert h.stream.result() == _greedy_ref(
        params, cfg, h.prompt, 4)
    m = sched.metrics.counters
    assert m["step_retries_total"] == 2
    assert m["step_failures_total"] == 2
    assert not sched.degraded
    # exponential backoff between the failed attempts: 0.05 then 0.1
    gaps = np.diff([c for c in calls[:3]])
    assert gaps[0] == pytest.approx(0.05) and gaps[1] == pytest.approx(0.1)


def test_repeated_failure_degrades_gracefully():
    """After the retry budget, in-flight AND queued requests drain with a
    structured error; the loop does not crash and resources are freed."""
    cfg, params, eng, sched, _ = _setup(
        num_slots=1, max_step_retries=2, retry_backoff_s=0.01)
    free0 = eng.mgr.num_free_pages

    def always_fail(p):
        raise RuntimeError("persistent device fault")

    eng.step = always_fail
    ps = _prompts(cfg, 3, rng_seed=11)
    hs = [sched.submit(p) for p in ps]
    sched.run(params, max_steps=200)        # returns instead of raising
    assert sched.degraded
    assert all(h.state == RequestState.FAILED for h in hs)
    for h in hs:
        with pytest.raises(ServingError) as ei:
            h.stream.result()
        assert ei.value.code == "engine_failure"
    assert sched.metrics.counters["step_retries_total"] == 2
    assert sched.metrics.counters["step_failures_total"] == 3
    assert eng.mgr.num_free_pages == free0   # pages reclaimed on degrade
    with pytest.raises(ServingError):        # refuses new work
        sched.submit(ps[0])


def test_step_timeout_counts_as_failure():
    cfg, params, eng, sched, _ = _setup(
        step_timeout_s=0.05, max_step_retries=1, retry_backoff_s=0.01)

    def hung_step(p):
        time.sleep(0.5)

    eng.step = hung_step
    h = sched.submit(_prompts(cfg, 1, rng_seed=12)[0])
    sched.run(params, max_steps=200)
    assert sched.degraded and h.state == RequestState.FAILED
    assert sched.metrics.counters["step_failures_total"] == 2


def test_timed_out_step_never_runs_concurrently():
    """A slow-but-completing step must not race a retry's second
    engine.step: the retry waits on the straggler, and its eventual
    completion counts as the step."""
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=3)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=4), num_slots=2, page_size=4,
        max_seq_len=32, chunk=2)
    eng.serve(params, [np.arange(1, 5, dtype=np.int32)])   # warm compiles
    sched = ServingScheduler(eng, SchedulerConfig(
        step_timeout_s=0.05, max_step_retries=5, retry_backoff_s=0.01))
    real_step = eng.step
    lock = threading.Lock()
    state = {"active": 0, "max_active": 0, "calls": 0}

    def slow_first_step(p):
        with lock:
            state["active"] += 1
            state["max_active"] = max(state["max_active"], state["active"])
            state["calls"] += 1
            first = state["calls"] == 1
        try:
            if first:
                time.sleep(0.2)             # slower than the timeout
            return real_step(p)
        finally:
            with lock:
                state["active"] -= 1

    eng.step = slow_first_step
    h = sched.submit(np.arange(1, 5, dtype=np.int32))
    sched.run(params, max_steps=200)
    assert state["max_active"] == 1         # never two concurrent steps
    assert h.state == RequestState.DONE and len(h.stream.result()) == 4
    assert not sched.degraded
    assert sched.metrics.counters["step_failures_total"] >= 1


def test_determinism_under_fixed_seed():
    """Same sampled-decoding trace twice -> identical outputs."""

    def run_once():
        cfg, params, eng, sched, _ = _setup(
            max_new=5, num_slots=2, do_sample=True, seed=7)
        hs = [sched.submit(p, priority=pr) for p, pr in
              zip(_prompts(cfg, 6, rng_seed=13), (1, 0, 2, 0, 1, 2))]
        sched.run(params, max_steps=300)
        return [h.stream.result() for h in hs]

    assert run_once() == run_once()


def test_deferred_submit_admits_only_after_backoff():
    """submit(defer_s=...) parks the request on the retry/backoff path:
    not admitted while the clock is short of ready time, admitted (at
    its original priority/FIFO position) once it passes."""
    cfg, params, eng, sched, clock = _setup(num_slots=2)
    p = _prompts(cfg, 1, rng_seed=20)[0]
    h = sched.submit(p, defer_s=1.0)
    assert sched.pending == 1
    sched.step(params)
    assert h.state == RequestState.QUEUED and h.engine_rid is None
    assert sched.statusz()["backoff"] == 1
    clock.advance(1.5)
    sched.run(params, max_steps=200)
    assert h.state == RequestState.DONE
    assert h.stream.result() == _greedy_ref(params, cfg, p, 5)


def test_run_waits_out_backoff_instead_of_spinning():
    """run() with only deferred requests pending sleeps the backoff
    instead of burning max_steps on no-op rounds (fake-clock sleeps
    advance the clock, so the deferral comes due and completes)."""
    cfg, params, eng, sched, clock = _setup()
    h = sched.submit(_prompts(cfg, 1, rng_seed=26)[0], defer_s=2.0)
    sched.run(params, max_steps=60)     # would exhaust if hot-spinning
    assert h.state == RequestState.DONE


def test_cancel_in_backoff_queue_is_idempotent():
    """Regression (ISSUE 6 satellite): a request cancelled while parked
    in the backoff queue must NOT be re-admitted by a later retry tick —
    the cancel permanently removes it, and a second cancel is a no-op."""
    cfg, params, eng, sched, clock = _setup(num_slots=2)
    p = _prompts(cfg, 1, rng_seed=21)[0]
    h = sched.submit(p, defer_s=0.5)
    assert sched.cancel(h.rid)
    assert h.state == RequestState.CANCELLED
    assert h.stream.finish_reason == "cancelled"
    assert not sched.cancel(h.rid)          # idempotent
    clock.advance(2.0)                      # retry tick comes due...
    for _ in range(3):
        sched.step(params)
    # ...and must not resurrect the cancelled request
    assert sched.pending == 0
    assert h.engine_rid is None and not eng._queue and not eng._live
    assert sched.metrics.counters["requests_cancelled_total"] == 1
    assert sched.metrics.counters.get("requests_completed_total", 0) == 0


def test_promoted_backoff_request_exempt_from_queue_cap_shed():
    """A failover-remediation request (submit(defer_s=...)) promoted
    into a full queue must never be the queue_full victim — fresh load
    sheds around it (review fix: the promoted request used to be the
    newest arrival and thus the FIRST victim)."""
    cfg, params, eng, sched, clock = _setup(num_slots=1, max_queue_depth=2)
    ps = _prompts(cfg, 5, rng_seed=24)
    h_run = sched.submit(ps[0])
    sched.step(params)                          # occupies the only slot
    h_a = sched.submit(ps[1])
    h_b = sched.submit(ps[2])                   # queue now AT the cap
    h_remed = sched.submit(ps[3], defer_s=0.1)  # remediation traffic
    clock.advance(0.5)
    sched.step(params)          # promotion pushes the queue over cap:
    # the remediation request is the newest arrival (highest seq) but a
    # FRESH request must be the queue_full victim, never it
    assert h_remed.state != RequestState.SHED
    assert h_b.state == RequestState.SHED
    assert h_b.stream.finish_reason == "shed:queue_full"
    sched.run(params, max_steps=300)
    assert h_remed.state == RequestState.DONE
    assert h_remed.stream.result() == _greedy_ref(params, cfg, ps[3], 5)
    assert all(h.state == RequestState.DONE for h in (h_run, h_a))


def test_deferred_request_deadline_expires_in_backoff():
    cfg, params, eng, sched, clock = _setup()
    h = sched.submit(_prompts(cfg, 1, rng_seed=22)[0], deadline_ms=100,
                     defer_s=10.0)
    clock.advance(0.5)                      # deadline lapses while parked
    sched.step(params)
    assert h.state == RequestState.SHED
    assert h.stream.finish_reason == "shed:deadline"


def test_lapsed_deferred_request_never_displaces_fresh_load():
    """A deferred request whose deadline AND defer both lapsed must shed
    as deadline without transiting the queue — its no_shed exemption
    must not push a viable fresh request over the cap on the way out."""
    cfg, params, eng, sched, clock = _setup(num_slots=1, max_queue_depth=2)
    ps = _prompts(cfg, 4, rng_seed=25)
    h_run = sched.submit(ps[0])
    sched.step(params)                      # occupies the slot
    h_a = sched.submit(ps[1])
    h_b = sched.submit(ps[2])               # queue at the cap
    h_dead = sched.submit(ps[3], deadline_ms=50, defer_s=0.1)
    clock.advance(0.5)                      # defer due AND deadline gone
    sched.step(params)
    assert h_dead.state == RequestState.SHED
    assert h_dead.stream.finish_reason == "shed:deadline"
    assert h_a.state != RequestState.SHED   # nobody wrongfully displaced
    assert h_b.state != RequestState.SHED
    sched.run(params, max_steps=300)
    assert all(h.state == RequestState.DONE for h in (h_run, h_a, h_b))


# ---------------------------------------------------------------------------
# stream robustness: producers that die without closing
# ---------------------------------------------------------------------------

def test_stream_producer_death_unblocks_consumer():
    """A blocking consumer with NO timeout gets a terminal
    producer_dead error when the bound producer dies, instead of
    blocking indefinitely."""
    from paddle_tpu.serving import TokenStream
    alive = [True]
    stream = TokenStream(0)
    stream.attach_producer(lambda: alive[0], poll_s=0.01)
    got = []

    def consume():
        got.append(stream.get())            # blocking, timeout-free

    t = threading.Thread(target=consume)
    t.start()
    time.sleep(0.05)
    assert t.is_alive()                     # genuinely blocked
    alive[0] = False                        # producer dies silently
    t.join(timeout=10)
    assert not t.is_alive()
    assert got == [None]
    assert stream.finished and stream.finish_reason == "failed"
    with pytest.raises(ServingError) as ei:
        stream.result()
    assert ei.value.code == "producer_dead"


def test_fatal_engine_death_closes_streams_terminally():
    """An engine dying with a non-Exception BaseException (fatal runtime
    death) skips the finish callback entirely; the scheduler must drain
    every stream with a terminal error before re-raising, so a blocked
    consumer thread unblocks without any timeout."""

    class FatalDeath(BaseException):
        pass

    cfg, params, eng, sched, _ = _setup(num_slots=2)

    def dying_step(p):
        raise FatalDeath("runtime died")

    eng.step = dying_step
    hs = [sched.submit(p) for p in _prompts(cfg, 2, rng_seed=23)]
    got = []
    t = threading.Thread(target=lambda: got.extend(hs[0].stream))
    t.start()
    with pytest.raises(FatalDeath):
        sched.step(params)
    t.join(timeout=10)
    assert not t.is_alive()                 # consumer unblocked
    assert sched.degraded
    for h in hs:
        assert h.state == RequestState.FAILED
        with pytest.raises(ServingError) as ei:
            h.stream.result()
        assert ei.value.code == "engine_failure"


# ---------------------------------------------------------------------------
# end-to-end acceptance + metrics
# ---------------------------------------------------------------------------

def test_e2e_serving_mixed_priorities_with_metrics():
    """ISSUE 1 acceptance: >=16 concurrent mixed-priority streaming
    requests; one cancelled mid-decode with pages reclaimed; one
    past-deadline request shed; one injected step failure retried with
    backoff; exported metrics text consistent with the trace."""
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=3)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=5), num_slots=4,
        page_size=4, max_seq_len=32, chunk=2)
    metrics = ServingMetrics()
    sched = ServingScheduler(
        eng, SchedulerConfig(max_queue_depth=32, max_step_retries=2,
                             retry_backoff_s=0.001), metrics=metrics)
    free0 = eng.mgr.num_free_pages

    real_step = eng.step
    fail_once = {"armed": True}

    def flaky_step(p):
        if fail_once["armed"]:
            fail_once["armed"] = False
            raise RuntimeError("injected transient fault")
        return real_step(p)

    eng.step = flaky_step

    host_recorder.enabled = True
    host_recorder.clear()
    try:
        rng = np.random.RandomState(14)
        handles = []
        for i in range(16):
            prompt = rng.randint(1, cfg.vocab_size,
                                 (int(rng.randint(3, 9)),)).astype(np.int32)
            handles.append(sched.submit(prompt, priority=i % 3))
        # a request whose deadline cannot be met from the back of the queue
        h_late = sched.submit(
            rng.randint(1, cfg.vocab_size, (4,)).astype(np.int32),
            priority=2, deadline_ms=1e-3)
        h_cancel = handles[5]

        # step until the first tokens land (unified step: long prompts
        # may spread their prefill over a couple of ragged rounds)
        for _ in range(20):
            sched.step(params)
            if any(len(h.stream.tokens) > 0 for h in handles):
                break
        assert any(len(h.stream.tokens) > 0 for h in handles)
        assert sched.cancel(h_cancel.rid)   # mid-decode or queued
        sched.run(params, max_steps=500)
    finally:
        host_recorder.enabled = False

    survivors = [h for h in handles if h is not h_cancel]
    assert all(h.state == RequestState.DONE for h in survivors)
    assert all(len(h.stream.result()) == 5 for h in survivors)
    assert h_late.state == RequestState.SHED
    assert h_cancel.state == RequestState.CANCELLED
    assert eng.mgr.num_free_pages == free0          # cancelled pages back
    assert all(r is None for r in eng._slot_rid)

    c = metrics.counters
    assert c["requests_submitted_total"] == 17
    assert c["requests_completed_total"] == 15
    assert c["requests_cancelled_total"] == 1
    assert metrics.shed == {"deadline": 1}
    assert c["step_retries_total"] >= 1
    assert c["tokens_generated_total"] == sum(
        len(h.stream.tokens) for h in handles)

    # TTFT/ITL histograms populated and consistent
    assert metrics.histograms["ttft_ms"].count >= 15
    assert metrics.histograms["itl_ms"].count > 0
    assert metrics.histograms["ttft_ms"].sum > 0
    assert metrics.histograms["queue_depth"].count > 0

    text = metrics.to_prometheus_text()
    m = re.search(r"paddle_serving_ttft_ms_count (\d+)", text)
    assert m and int(m.group(1)) >= 15
    assert re.search(r"paddle_serving_itl_ms_count [1-9]", text)
    assert 'paddle_serving_ttft_ms_quantile{quantile="0.99"}' in text
    assert 'paddle_serving_requests_shed_total{reason="deadline"} 1' in text
    assert re.search(r"paddle_serving_step_retries_total [1-9]", text)
    assert re.search(r"paddle_serving_queue_depth_count [1-9]", text)

    # trace events reached the profiler host recorder
    spans = host_recorder.drain()
    names = {s.name for s in spans}
    assert "paddle_serving.step" in names
    assert "paddle_serving.request" in names
    assert "paddle_serving.shed.deadline" in names
    assert "paddle_serving.step_retry" in names


# ---------------------------------------------------------------------------
# lint: the compat shim stays the single shard_map source
# ---------------------------------------------------------------------------

def test_no_direct_shard_map_imports():
    """Forbid new `from jax import shard_map` / `jax.shard_map(` uses;
    paddle_tpu/core/compat.py is the single version-tolerant source.
    Ported to tpu-lint (rule ``layer-shard-map``, AST-based so strings/
    comments can't false-positive) — this is a thin assert over the
    suite-shared analysis run."""
    from paddle_tpu import analysis
    bad = analysis.cached_report().new_for_rule("layer-shard-map")
    assert not bad, (
        "direct jax shard_map usage:\n" + "\n".join(f.text() for f in bad)
        + "\nimport it from paddle_tpu.core.compat instead")


# ---------------------------------------------------------------------------
# regressions (ISSUE 8, tpu-lint metric-contract / private-engine)
# ---------------------------------------------------------------------------

def test_all_settable_gauges_declared_at_construction():
    """Every gauge family set_gauge() may touch is on /metrics from the
    moment the sink exists — the scrape schema must not depend on which
    code paths (SLO breach, prefix cache) have run yet. tpu-lint's
    metric-contract rule flagged slo_breached and the live/cached page
    splits as minted-on-first-use; they are declared now."""
    m = ServingMetrics(namespace="paddle_serving_decl_test")
    for gauge in ("slo_breached", "live_page_utilization",
                  "cached_page_utilization"):
        assert gauge in m.gauges, gauge
    text = m.to_prometheus_text()
    for family in ("paddle_serving_decl_test_slo_breached_gauge",
                   "paddle_serving_decl_test_live_page_utilization_gauge",
                   "paddle_serving_decl_test_cached_page_utilization_gauge"):
        assert family in text, family


def test_scheduler_admission_uses_public_engine_queue_depth():
    """The scheduler's headroom math goes through the public
    ``engine.num_queued`` (tpu-lint private-engine: serving code must
    not reach into ``engine._queue``)."""
    cfg, params, eng, sched, _ = _setup(num_slots=2)
    assert eng.num_queued == 0
    for p in _prompts(cfg, 3, rng_seed=42):
        eng.submit(p)                    # 3rd waits in the engine FIFO
    assert eng.num_queued == len(eng._queue)
    assert eng.num_queued >= 1
