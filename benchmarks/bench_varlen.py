"""Varlen flash attention on the chip: kernel parity vs the masked XLA
reference + fwd/bwd timing vs (a) the XLA fallback and (b) the
pad-per-sequence dense alternative (VERDICT round-2 item 4 'Done' gate).

Run: python benchmarks/bench_varlen.py   (real chip; CPU smoke with
JAX_PLATFORMS=cpu runs tiny shapes)
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

    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops._common import is_tpu_platform
    from paddle_tpu import flags

    on_tpu = is_tpu_platform(jax.devices()[0].platform)
    rs = np.random.RandomState(0)
    if on_tpu:
        # modest T: the XLA comparison materialises (H, T, T) fp32 scores
        lens = [384, 512, 128, 768, 256, 512]                  # T = 2560
        H, D, iters = 16, 128, 20
        dt = jnp.bfloat16
    else:
        lens = [48, 80]
        H, D, iters = 2, 128, 2
        dt = jnp.float32
    total = sum(lens)
    cu = jnp.asarray(np.cumsum([0] + lens).astype(np.int32))
    q = jnp.asarray(rs.randn(total, H, D), dt)
    k = jnp.asarray(rs.randn(total, H, D), dt)
    v = jnp.asarray(rs.randn(total, H, D), dt)

    # ---- parity: Pallas varlen kernel vs masked XLA reference -------------
    out_pallas = fa.flash_attention_varlen(q, k, v, cu, cu, causal=True)
    flags.set_flags({"use_pallas_kernels": False})
    out_ref = fa.flash_attention_varlen(q, k, v, cu, cu, causal=True)
    flags.set_flags({"use_pallas_kernels": True})
    err = float(jnp.max(jnp.abs(out_pallas.astype(jnp.float32)
                                - out_ref.astype(jnp.float32))))
    denom = float(jnp.max(jnp.abs(out_ref.astype(jnp.float32)))) + 1e-9
    parity = err / denom

    def timed(f, *args):
        g = jax.jit(jax.grad(
            lambda a, b, c: (f(a, b, c).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2)))
        r = g(*args)
        float(r[0].astype(jnp.float32).sum())      # compile + fence
        t0 = time.perf_counter()
        for _ in range(iters):
            r = g(*args)
        float(r[0].astype(jnp.float32).sum())
        return (time.perf_counter() - t0) / iters * 1e3

    t_varlen = timed(lambda a, b, c: fa.flash_attention_varlen(
        a, b, c, cu, cu, causal=True), q, k, v)
    flags.set_flags({"use_pallas_kernels": False})
    t_xla = timed(lambda a, b, c: fa.flash_attention_varlen(
        a, b, c, cu, cu, causal=True), q, k, v)
    flags.set_flags({"use_pallas_kernels": True})

    # pad-per-sequence dense alternative: (B, maxlen) batch, wasted tiles
    maxlen = max(lens)
    B = len(lens)
    qp = np.zeros((B * H, maxlen, D), np.float32)
    for i, L in enumerate(lens):
        a, b = int(cu[i]), int(cu[i + 1])
        qp[i * H:(i + 1) * H, :L] = np.moveaxis(np.asarray(
            q[a:b], np.float32), 1, 0)
    qp = jnp.asarray(qp, dt)
    t_padded = timed(lambda a, b, c: fa.flash_attention_bhsd(
        a, b, c, 1.0 / np.sqrt(D), True), qp, qp, qp)

    print(json.dumps({
        "metric": "varlen_flash_attention",
        "total_tokens": total, "heads": H, "head_dim": D,
        "parity_vs_ref": round(parity, 6),
        "varlen_pallas_ms": round(t_varlen, 2),
        "varlen_xla_ms": round(t_xla, 2),
        "pad_per_seq_pallas_ms": round(t_padded, 2),
        "speedup_vs_xla": round(t_xla / t_varlen, 2),
        "speedup_vs_padded": round(t_padded / t_varlen, 2),
    }))


if __name__ == "__main__":
    main()
