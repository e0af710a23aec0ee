"""When a traced run is traced. A batch is armed by its progress (a quarter
of its requests completed), so that a batch a faster program ends early is
still traced while it is loaded; the open loop and training are armed by the
clock. A fake server on a fake clock, and a tracer that only notes when the
profiler would have started and stopped."""

import types

import numpy as np
import pytest

from perfbench import harness, loadgen

SECONDS = 51.0
TRACE_SECONDS = 6.0


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


class FakeServer:
    """Whatever was submitted is in flight; each request gets 4 tokens a
    dispatch, and a dispatch takes ``dt`` seconds of the fake clock."""

    def __init__(self, clock, dt):
        self.clock, self.dt = clock, dt
        self.live = []
        self.live_at = []                # (time, requests in flight) a step

    def submit(self, prompt, max_new_tokens, on_token):
        handle = types.SimpleNamespace(left=max_new_tokens, on_token=on_token)
        self.live.append(handle)
        return handle

    def busy(self):
        return bool(self.live)

    def step(self):
        self.live_at.append((self.clock.now, len(self.live)))
        self.clock.now += self.dt
        for h in self.live:
            for _ in range(min(4, h.left)):
                h.on_token(0)
            h.left = max(0, h.left - 4)
        self.live = [h for h in self.live if h.left]

    @staticmethod
    def outcome(handle):
        return None if handle.left else "ok"

    def begin_window(self):
        pass


class NotingTracer(harness.Tracer):
    def __init__(self, clock, seconds=TRACE_SECONDS):
        super().__init__("unused", seconds)
        self.clock, self.started, self.stopped = clock, None, None

    def _start(self):
        self.started = self.clock()

    def _stop(self):
        self.stopped = self.clock()


def chat_batch(seconds=SECONDS):
    mix = harness.read_json("perfbench", "traffic", "chat-batch.json")
    params = harness.read_json("perfbench", "cells",
                               "m7b-tp4.chat-batch.json")["params"]
    return loadgen.Traffic(mix, params, 32768, 7, seconds)


def run_batch(dt):
    clock = Clock()
    server, tracer = FakeServer(clock, dt), NotingTracer(clock)
    records, window = harness.serve_batch(
        server, chat_batch(), harness.Spans(clock), tracer, clock)
    tracer.finish()
    return server, tracer, records, window


def test_a_batch_that_ends_early_is_traced_while_it_is_loaded():
    server, _, _, (t0, _) = run_batch(1.0)
    steps = sum(1 for t, _ in server.live_at if t >= t0)
    # the same batch on a server fast enough to end it at 0.36 x seconds
    # (m7b-tp4.chat-batch since PR 25: 18.5 s of 51)
    dt = 0.36 * SECONDS / steps
    server, tracer, records, (t0, t1) = run_batch(dt)
    assert t1 - t0 == pytest.approx(0.36 * SECONDS, rel=0.02)
    assert len(records) == 4 + 69 and all(r.complete for r in records)
    # armed by the clock at a third of the window (17 s) it held the drain
    # tail and was cut by the batch's end; armed by progress it ends well
    # inside the batch and was stopped by the loop, not by finish()
    assert tracer.stopped is not None and not tracer.overran
    assert 0 <= tracer.stopped - tracer.started - TRACE_SECONDS <= dt
    assert tracer.stopped < t1 - 2.0
    done_at_start = sum(1 for r in records
                        if r.index >= 0 and r.token_times[-1] <= tracer.started)
    assert 69 / 4 <= done_at_start <= 69 / 4 + 8
    in_flight = [n for t, n in server.live_at
                 if tracer.started <= t < tracer.stopped]
    assert len(in_flight) >= 20          # complete dispatches in the trace
    # 69 requests through 32 callers: the callers run dry once 37 have
    # completed, so a batch is fully loaded for its first two fifths only;
    # the trace holds that and the first of the decline
    assert np.median(in_flight) == 32 and min(in_flight) >= 16
    # by the clock (a third of 51 s) the trace began where 2 were in flight
    old = [n for t, n in server.live_at if t >= t0 + SECONDS / 3]
    assert 0 < len(old) < 20 and max(old) <= 2


def test_a_trace_still_running_at_the_batchs_end_says_it_overran():
    clock = Clock()
    server = FakeServer(clock, 0.05)     # the whole batch takes ~4 s
    tracer = NotingTracer(clock)
    harness.serve_batch(server, chat_batch(), harness.Spans(clock), tracer,
                        clock)
    assert tracer.state == "tracing" and not tracer.overran
    tracer.finish()
    assert tracer.overran and tracer.stopped is not None


def test_an_untraced_batch_arms_nothing():
    clock = Clock()
    tracer = harness.Tracer(None, TRACE_SECONDS)
    harness.serve_batch(FakeServer(clock, 0.25), chat_batch(),
                        harness.Spans(clock), tracer, clock)
    assert tracer.state == "off" and tracer.finish() is None
    assert not tracer.overran


def test_the_open_loop_is_armed_by_the_clock_as_before():
    clock = Clock()

    def sleep(s):
        clock.now += s

    mix = harness.read_json("perfbench", "traffic", "chat-poisson.json")
    traffic = loadgen.Traffic(mix | {"warmup_s": 2.0}, {"rate_rps": 1.3},
                              32768, 7, 20.0)
    tracer = NotingTracer(clock, seconds=5.0)
    records, (t0, t1) = harness.serve_open(
        FakeServer(clock, 0.25), traffic, harness.Spans(clock), tracer, clock,
        sleep)
    assert t1 - t0 == 20.0
    assert tracer.started == pytest.approx(t1 - 5.0, abs=0.3)
    assert tracer.state == "tracing"     # stopping falls after the window
    assert np.isfinite([r.first for r in records if r.complete]).all()
