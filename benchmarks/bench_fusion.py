"""Fusion admission harness: measured ABBA A/B for the fused region.

The fusion pass's three hard gates run HERE, not in prose, on its one
region (the optimizer chain):

* **byte-identical** — fused and eager steps must commit exactly the
  same parameter bits (asserted, not sampled);
* **recompile-count-neutral** — the fused step compiles its program
  exactly once across the run;
* **measured win** — interleaved A/B/B/A repetitions, medians reported
  in one JSON line.

Run: ``python benchmarks/bench_fusion.py`` (CPU smoke with
``JAX_PLATFORMS=cpu``).
"""

import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from _telemetry import metrics_snapshot, run_header  # noqa: E402


def _median(xs):
    return statistics.median(xs)


def _optimizer_ab(n_params=24, steps=20, reps=3):
    """Eager vs fused optimizer chain (AdamW + global-norm clip over a
    realistic parameter mix): bitwise gate first, then ABBA steps/s."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Parameter
    from paddle_tpu.jit.fusion import install_optimizer_fusion
    from paddle_tpu.observability.runtime import recompiles
    from paddle_tpu.optimizer.clip import ClipGradByGlobalNorm
    from paddle_tpu.optimizer.optimizer import AdamW

    shapes = [(256, 128), (128,), (512, 64), (64,)]

    def fresh(tag):
        rng = np.random.RandomState(7)
        ps = []
        for i in range(n_params):
            s = shapes[i % len(shapes)]
            p = Parameter(jnp.asarray(rng.randn(*s).astype(np.float32)))
            p.name = f"{tag}_{i}"
            ps.append(p)
        opt = AdamW(0.01, parameters=ps, weight_decay=0.05,
                    grad_clip=ClipGradByGlobalNorm(1.0))
        gs = [jnp.asarray(np.random.RandomState(100 + i)
                          .randn(*p._value.shape).astype(np.float32))
              for i, p in enumerate(ps)]
        return ps, opt, gs

    def run_steps(ps, opt, gs, n):
        for _ in range(n):
            for p, g in zip(ps, gs):
                p._grad_value = g
            opt.step()
        jax.block_until_ready(ps[0]._value)

    # gate: bitwise identity over a short run
    pe, oe, ge = fresh("e")
    run_steps(pe, oe, ge, 4)
    pf, of_, gf = fresh("f")
    install_optimizer_fusion(of_)
    rc0 = recompiles.count("fusion.optimizer_chain")
    run_steps(pf, of_, gf, 4)
    byte_identical = all(
        np.array_equal(np.asarray(a._value), np.asarray(b._value))
        for a, b in zip(pe, pf))
    assert byte_identical, "fused optimizer chain not byte-identical"
    recompile_neutral = (recompiles.count("fusion.optimizer_chain")
                         - rc0) == 1

    def timed(ps, opt, gs):
        t0 = time.perf_counter()
        run_steps(ps, opt, gs, steps)
        return steps / (time.perf_counter() - t0)

    a_runs, b_runs = [], []
    for _ in range(reps):
        a_runs.append(timed(pe, oe, ge))     # A (eager)
        b_runs.append(timed(pf, of_, gf))    # B (fused)
        b_runs.append(timed(pf, of_, gf))    # B
        a_runs.append(timed(pe, oe, ge))     # A
    a_med, b_med = _median(a_runs), _median(b_runs)
    return {
        "steps_per_s_eager": round(a_med, 2),
        "steps_per_s": round(b_med, 2),
        "ratio": round(b_med / a_med, 4),
        "params": n_params,
        "byte_identical": True,
        "recompile_neutral": recompile_neutral,
        "reps": reps * 2,
    }


def main():
    import jax

    from paddle_tpu.ops._common import is_tpu_platform

    on_tpu = is_tpu_platform(jax.devices()[0].platform)
    opt = _optimizer_ab()
    out = {
        **run_header("fusion"),
        "metric": "fusion_optimizer_ab_v5e" if on_tpu else
                  "fusion_optimizer_ab_cpu_smoke",
        "unit": "x_speedup",
        "steps_per_s": opt["steps_per_s"],
        "value": opt["ratio"],
        "optimizer_chain": opt,
        "gates": {"byte_identical": opt["byte_identical"],
                  "recompile_neutral": opt["recompile_neutral"]},
    }
    out["metrics_snapshot"] = metrics_snapshot()
    print(json.dumps(out))
    if not (out["gates"]["byte_identical"]
            and out["gates"]["recompile_neutral"]):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
