"""Per-kernel TPU microbenchmarks: Pallas vs XLA-fallback (VERDICT round-1
item 2 — 'per-kernel TPU microbench table').

Run on the real chip: python benchmarks/bench_kernels.py
(CPU smoke: JAX_PLATFORMS=cpu ... — fallback only, Pallas rows skipped.)

Timing uses a device->host value fence (float(...)).
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fence(x):
    import jax.numpy as jnp
    return float(jnp.asarray(x).astype(jnp.float32).sum())


def timeit(fn, iters=20):
    fence(fn())  # warm/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    fence(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def main():
    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform

    from paddle_tpu import flags
    from paddle_tpu.ops import flash_attention as FA
    from paddle_tpu.ops import rms_norm as RN
    from paddle_tpu.ops import rope as RO
    from paddle_tpu.ops._common import is_tpu_platform

    on_tpu = is_tpu_platform(platform)
    print(f"# platform={platform} pallas={'on' if on_tpu else 'off (cpu)'}")
    rows = []

    def with_pallas(flag, fn):
        old = flags.get_flags("use_pallas_kernels")["use_pallas_kernels"]
        flags.set_flags({"use_pallas_kernels": flag})
        try:
            return fn()
        finally:
            flags.set_flags({"use_pallas_kernels": old})

    rng = np.random.RandomState(0)

    # flash attention fwd+bwd: (BH, S, D) = (32, 2048, 128) bf16
    q = jnp.asarray(rng.randn(32, 2048, 128), jnp.bfloat16)

    def attn_loss(q):
        return FA.flash_attention_bhsd(q, q, q, 1.0 / 128 ** 0.5, True) \
            .astype(jnp.float32).sum()

    gfn = jax.jit(jax.value_and_grad(attn_loss))
    for label, flag in (("pallas", True), ("xla", False)):
        if flag and not on_tpu:
            continue
        jax.clear_caches()
        ms = with_pallas(flag, lambda: timeit(lambda: gfn(q)[0], iters=10))
        rows.append((f"flash_attn fwd+bwd 32x2048x128 [{label}]", ms))

    # rms_norm fwd+bwd: (8192, 4096) bf16
    x = jnp.asarray(rng.randn(8192, 4096), jnp.bfloat16)
    w = jnp.asarray(rng.randn(4096), jnp.bfloat16)

    def rms_loss(x, w):
        return RN.rms_norm_array(x, w).astype(jnp.float32).sum()

    rfn = jax.jit(jax.value_and_grad(rms_loss, argnums=(0, 1)))
    for label, flag in (("pallas", True), ("xla", False)):
        if flag and not on_tpu:
            continue
        jax.clear_caches()
        ms = with_pallas(flag, lambda: timeit(lambda: rfn(x, w)[0], iters=20))
        rows.append((f"rms_norm fwd+bwd 8192x4096 [{label}]", ms))

    # fused rope: (8, 2048, 32, 128)
    try:
        qr = jnp.asarray(rng.randn(8, 2048, 32, 128), jnp.bfloat16)
        cos, sin = RO.build_rope_cache(2048, 128)

        rofn = jax.jit(lambda a: RO.apply_rope_array(a, a, cos, sin)[0])
        ms = timeit(lambda: rofn(qr), iters=20)
        rows.append(("fused_rope 8x2048x32x128 [xla-fused]", ms))
    except Exception as e:
        print(f"# rope skipped: {type(e).__name__}: {e}", file=sys.stderr)

    width = max(len(r[0]) for r in rows) + 2
    print(f"{'kernel':<{width}} ms/iter")
    for name, ms in rows:
        print(f"{name:<{width}} {ms:7.3f}")
    # one machine-readable trailer line with the shared registry view,
    # so the perf trajectory carries telemetry (benchmarks/_telemetry.py)
    import json
    from _telemetry import metrics_snapshot
    print(json.dumps({
        "bench": "kernels",
        "ms_per_iter": {name: round(ms, 4) for name, ms in rows},
        "metrics_snapshot": metrics_snapshot(),
    }))


if __name__ == "__main__":
    main()
