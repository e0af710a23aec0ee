"""Single-chip bench of the ring-attention INNER block at long-context
sizes (VERDICT r4 item 2 done-criteria): the Pallas flash block the ring
now uses per step vs the einsum block it replaced.

At sep=4 over S=64k each device holds S_local=16k: the einsum block's
(B, H, 16k, 16k) fp32 scores are a 17 GB materialization — the memory
cliff the flash kernel exists to avoid. The bench times fwd+bwd of one
ring step's block at S_local in {8k, 16k} and reports einsum OOM/thrash
behavior honestly.

Run on the real chip:  python benchmarks/bench_ring_inner.py
CPU smoke:             JAX_PLATFORMS=cpu BENCH_WORKLOADS_SMOKE=1 python ...
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fence(x):
    import jax.numpy as jnp
    return float(jnp.asarray(x).astype(jnp.float32).sum())


def timeit(fn, iters=5):
    fence(fn())
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    fence(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops._common import is_tpu_platform
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops import ring_attention as ra

    smoke = os.environ.get("BENCH_WORKLOADS_SMOKE") == "1" or \
        not is_tpu_platform(jax.devices()[0].platform)

    B, H, D = 1, 16, 128
    sizes = [512] if smoke else [8192, 16384]
    sc = 1.0 / np.sqrt(D)
    rows = []
    for S in sizes:
        rng = np.random.RandomState(0)
        shape = (B * H, S, D)
        q = jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(
            jnp.bfloat16)
        k = jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(
            jnp.bfloat16)
        v = jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(
            jnp.bfloat16)

        def f_flash(a, b_, c):
            out, lse = ra._block_fwd(a, b_, c, sc, False, 1)
            g = jnp.ones_like(out)
            dq, dk, dv = ra._block_bwd(a, b_, c, out.astype(a.dtype),
                                       lse, g.astype(a.dtype), sc,
                                       False, 1)
            return (out.astype(jnp.float32).sum()
                    + dq.astype(jnp.float32).sum()
                    + dk.astype(jnp.float32).sum())

        flash_jit = jax.jit(f_flash)
        flash_ms = timeit(lambda: flash_jit(q, k, v))

        # einsum block (the pre-round-4 inner block), fwd+bwd via autodiff
        def f_einsum(a, b_, c):
            s = jnp.einsum("bqd,bkd->bqk", a.astype(jnp.float32),
                           b_.astype(jnp.float32)) * sc
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bqk,bkd->bqd", p, c.astype(jnp.float32))
            return o.sum()

        einsum_jit = jax.jit(jax.value_and_grad(f_einsum, argnums=(0, 1, 2)))

        try:
            einsum_ms = timeit(lambda: einsum_jit(q, k, v)[0])
            note = ""
        except Exception as e:
            einsum_ms = None
            note = f"einsum block failed: {type(e).__name__} (scores " \
                f"{B * H * S * S * 4 / 1e9:.1f} GB fp32)"
        rows.append({"s_local": S, "flash_ms": round(flash_ms, 1),
                     "einsum_ms": (round(einsum_ms, 1)
                                   if einsum_ms is not None else None),
                     "note": note})
    print(json.dumps({"metric": "ring_inner_block", "B": B, "H": H, "D": D,
                      "rows": rows}))


if __name__ == "__main__":
    main()
