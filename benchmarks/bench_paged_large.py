"""Paged-attention decode at LARGE page pools — prove or retire the
scalar-prefetch kernel at scale (VERDICT round-2 item 8).

Pools are generated ON DEVICE with jax.random, so nothing large is
uploaded from the host.

Run: python benchmarks/bench_paged_large.py   (CPU smoke: JAX_PLATFORMS=cpu)
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_attention as PA
    from paddle_tpu.ops._common import is_tpu_platform
    from paddle_tpu import flags

    on_tpu = is_tpu_platform(jax.devices()[0].platform)
    H, D, PSZ = 8, 128, 16
    configs = [(64, 128, 13), (256, 1024, 40), (256, 2048, 80),
               (512, 4096, 100)] if on_tpu else [(4, 16, 3)]
    iters = 20 if on_tpu else 2
    results = []
    for B, PAGES, pages_per_seq in configs:
        key = jax.random.key(0)
        k1, k2, k3 = jax.random.split(key, 3)
        # pools materialise on device; nothing big is uploaded
        kp = jax.jit(lambda k: jax.random.normal(
            k, (PAGES, PSZ, H, D), jnp.bfloat16))(k1)
        vp = jax.jit(lambda k: jax.random.normal(
            k, (PAGES, PSZ, H, D), jnp.bfloat16))(k2)
        qd = jax.jit(lambda k: jax.random.normal(
            k, (B, H, D), jnp.bfloat16))(k3)
        rng = np.random.RandomState(0)
        bt = jnp.asarray(rng.randint(0, PAGES, (B, pages_per_seq)), jnp.int32)
        sl = jnp.full((B,), pages_per_seq * PSZ - PSZ // 2, jnp.int32)

        pfn = jax.jit(lambda q: PA.paged_attention(q, kp, vp, bt, sl))
        row = {"seqs": B, "pages": PAGES, "tokens_per_seq": int(sl[0])}
        for label, flag in (("pallas", True), ("xla", False)):
            if flag and not on_tpu:
                continue
            jax.clear_caches()
            old = flags.get_flags()["use_pallas_kernels"]
            flags.set_flags({"use_pallas_kernels": flag})
            try:
                out = pfn(qd)
                float(out.astype(jnp.float32).sum())   # compile + fence
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = pfn(qd)
                float(out.astype(jnp.float32).sum())
                row[f"{label}_ms"] = round(
                    (time.perf_counter() - t0) / iters * 1e3, 2)
            except Exception as e:
                row[f"{label}_ms"] = f"{type(e).__name__}"
            finally:
                flags.set_flags({"use_pallas_kernels": old})
        if isinstance(row.get("pallas_ms"), float) and \
                isinstance(row.get("xla_ms"), float):
            row["speedup"] = round(row["xla_ms"] / row["pallas_ms"], 2)
        results.append(row)
        print(json.dumps(row))


if __name__ == "__main__":
    main()
