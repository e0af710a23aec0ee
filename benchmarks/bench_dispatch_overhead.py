"""Dispatch-overhead guard for the observability layer.

The unified-telemetry PR added a hook inside ``core.dispatch.apply``
(per-op counters + sampled durations + profiler spans). Its contract:

* fully DISARMED (telemetry disabled, no capture window) the dispatcher
  does one extra boolean check vs the seed — unmeasurable;
* ARMED (the always-on default) the per-dispatch cost stays **< 3%**.

This guard measures both and exits non-zero when the armed overhead
breaches the budget, so CI catches a regression that would tax every
eager op in production. Emits ONE line of JSON.

Methodology: the op under test is a small eager ``add`` on pre-built
tensors — near the worst case for relative overhead (big ops amortise
the hook further). Each trial round measures the two modes back-to-back
in ABBA order (disarmed, armed, armed, disarmed) so clock/allocator
drift cancels within the pair, and the reported overhead is the MEDIAN
of the per-round ratios (median, not mean, rejects scheduler noise).

Run: JAX_PLATFORMS=cpu python benchmarks/bench_dispatch_overhead.py
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUDGET_PCT = 3.0
N_OPS = 3000
TRIALS = 15


def main():
    import paddle_tpu as paddle
    from paddle_tpu.observability import telemetry
    from paddle_tpu.observability.runtime import dispatch_armed

    x = paddle.to_tensor(np.ones((8, 8), np.float32))
    y = paddle.to_tensor(np.ones((8, 8), np.float32))

    def burst(n=N_OPS):
        t0 = time.perf_counter()
        for _ in range(n):
            x + y
        return (time.perf_counter() - t0) / n

    burst(500)  # warm caches / allocator

    def disarmed_burst():
        telemetry.disable()
        assert not dispatch_armed[0], "disarm must clear the fast-path flag"
        return burst()

    def armed_burst():
        telemetry.enable()
        assert dispatch_armed[0]
        return burst()

    ratios, base_samples, armed_samples = [], [], []
    for _ in range(TRIALS):
        d1 = disarmed_burst()
        a1 = armed_burst()
        a2 = armed_burst()
        d2 = disarmed_burst()
        base_samples += [d1, d2]
        armed_samples += [a1, a2]
        ratios.append((a1 + a2) / (d1 + d2))
    telemetry.enable()  # leave the always-on default in place

    base_us = min(base_samples) * 1e6
    armed_us = min(armed_samples) * 1e6
    overhead_pct = (sorted(ratios)[len(ratios) // 2] - 1.0) * 100
    ok = overhead_pct < BUDGET_PCT
    from _telemetry import run_header
    print(json.dumps({
        **run_header("dispatch_overhead"),
        "n_ops": N_OPS,
        "trials": TRIALS,
        "disarmed_us_per_op": round(base_us, 3),
        "armed_us_per_op": round(armed_us, 3),
        "overhead_pct": round(overhead_pct, 2),
        "budget_pct": BUDGET_PCT,
        "pass": ok,
    }))
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
