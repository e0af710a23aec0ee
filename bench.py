"""Benchmark entry (driver-run on real TPU hardware).

Measures the flagship workload: Llama causal-LM training throughput
(tokens/sec/chip) and MFU on the available accelerator, via the compiled
hybrid train step (bf16 compute, Pallas flash attention + rms_norm, remat).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} where
value is MFU and vs_baseline is MFU / 0.50 (the north-star ≥50% MFU target,
BASELINE.md).
"""

import json
import os
import sys
import time

import numpy as np

# Published per-chip peaks, keyed by the ``device_kind`` jax reports.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s). A device that is not in this table is an ERROR, not a
# v5e: add its row, with its source, before measuring on it.
DEVICE_PEAKS = {
    "TPU v5 lite": {"gen": "v5e", "bf16_flops": 197e12,
                    "hbm_bytes_per_s": 819e9},
}


def device_peaks(device_kind: str) -> dict:
    """The peaks row for ``device_kind``; raises for an unknown device."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); a utilization against an assumed "
            "peak is not a measurement") from None


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.compile_cache import enable_compile_cache
    from paddle_tpu.ops._common import is_tpu_platform

    # A backend error, a failed kernel compile or a configuration that does
    # not fit FAILS the benchmark: nothing here falls back to the CPU, to
    # the XLA reference kernels or to another configuration.
    device = jax.devices()[0]
    platform = device.platform
    on_tpu = is_tpu_platform(platform)
    if not on_tpu and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        sys.exit(f"bench.py: no TPU (JAX found platform {platform!r}). The "
                 "CPU smoke must be asked for: JAX_PLATFORMS=cpu python "
                 "bench.py (it prints tokens/sec of the CPU backend, never "
                 "an MFU)")
    peaks = device_peaks(device.device_kind) if on_tpu else None
    enable_compile_cache()

    from paddle_tpu.models import llama as L
    from paddle_tpu.parallel import mesh as pmesh

    if on_tpu:
        # Wider models favour the MXU (fewer, larger matmuls). Measured on
        # the v5e chip, B=8 S=2048, full remat:
        #   llama7b_layer (877M, h=4096 L=4): 52.0% MFU <- default (the 7B
        #       north-star LAYER geometry; B=16 drops to 48.5%)
        #   wide3072 (876M, h=3072 L=6):  50.7-51.0% MFU
        #   wide2048 (637M, h=2048 L=10): 45.8%
        #   deep     (374M, h=1024 L=24): 37.6%
        model = os.environ.get("BENCH_MODEL", "llama7b_layer")
        if model == "llama7b_layer":
            # Llama-2-7B LAYER GEOMETRY (h=4096, ff=11008, 32 heads) at a
            # depth that fits one chip with optimizer state — the honest
            # per-chip proxy for the 7B north star:
            # per-layer matmul shapes identical to the full 32-layer model;
            # vocab factored small (8192) so the decoder stack dominates the
            # FLOP mix as it does at L=32.
            cfg = L.LlamaConfig(
                vocab_size=8192, hidden_size=4096, intermediate_size=11008,
                num_hidden_layers=4, num_attention_heads=32,
                num_key_value_heads=32, max_position_embeddings=2048,
                dtype=jnp.bfloat16)
        elif model == "llama13b_layer":
            # Llama-2-13B layer geometry (h=5120, ff=13824, 40 heads) at a
            # one-chip depth — the 13B sibling of llama7b_layer. Ask for it
            # with BENCH_KSTEP=1: the k-step scan double-buffers the
            # params+opt-state carry and does not fit (rounds 2-5 measured
            # 17.57G vs 15.75G HBM)
            cfg = L.LlamaConfig(
                vocab_size=8192, hidden_size=5120, intermediate_size=13824,
                num_hidden_layers=3, num_attention_heads=40,
                num_key_value_heads=40, max_position_embeddings=2048,
                dtype=jnp.bfloat16)
        elif model == "wide3072":
            cfg = L.LlamaConfig(
                vocab_size=32000, hidden_size=3072, intermediate_size=8192,
                num_hidden_layers=6, num_attention_heads=24,
                num_key_value_heads=24, max_position_embeddings=2048,
                dtype=jnp.bfloat16)
        elif model == "wide2048":
            cfg = L.LlamaConfig(
                vocab_size=32000, hidden_size=2048, intermediate_size=5504,
                num_hidden_layers=10, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=2048,
                dtype=jnp.bfloat16)
        else:
            cfg = L.LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=24, num_attention_heads=8,
                num_key_value_heads=8, max_position_embeddings=2048,
                dtype=jnp.bfloat16)
        # BENCH_SEQ: long-context rows. At
        # S=8192/16384 the default B=8 exceeds HBM even with full remat;
        # scale B down to hold B*S ~ 16k tokens unless BENCH_BATCH is set.
        S = int(os.environ.get("BENCH_SEQ", "2048"))
        default_B = max(1, (8 * 2048) // S)
        B = int(os.environ.get("BENCH_BATCH", str(default_B)))
        if S > cfg.max_position_embeddings:
            cfg.max_position_embeddings = S
        steps, warmup = 10, 2
    else:
        cfg = L.llama_tiny(num_hidden_layers=4)
        B, S, steps, warmup = 4, 64, 4, 1

    mesh = pmesh.build_mesh({}, devices=jax.devices()[:1])
    pmesh.set_global_mesh(mesh)
    # remat trades extra FLOPs for activation memory. Measured on the v5e
    # chip (374M, B=8 S=2048): remat OFF out-of-memories; the "dots" policy
    # (save matmul outputs) reached only 34.3% MFU vs full remat's 37.6% —
    # the saved activations raise HBM pressure more than the skipped
    # recompute saves. "full" recomputes everything but the flash forward
    # kernel, whose out and lse are kept. BENCH_REMAT=full|dots|offload|off.
    remat_mode = os.environ.get("BENCH_REMAT", "full")
    # legacy knob values from earlier rounds: 1 = full remat, 0 = off
    remat_mode = {"1": "full", "0": "off"}.get(remat_mode, remat_mode)
    if remat_mode not in ("full", "dots", "offload", "off"):
        sys.exit(f"unknown BENCH_REMAT={remat_mode!r}; "
                 "pick from full|dots|offload|off")
    # BENCH_KSTEP: k training steps per dispatch (lax.scan over a leading
    # k axis, params/opt-state carry donated) — amortizes the per-dispatch
    # host cost. k=1 is the single-step program. Default 8 from the
    # round-5 sweep on the then installation (converged by k=8).
    try:
        kstep = int(os.environ.get("BENCH_KSTEP", "8"))
    except ValueError:
        sys.exit(f"BENCH_KSTEP={os.environ['BENCH_KSTEP']!r} is not an "
                 "integer; pick k in [1, 64]")
    if not 1 <= kstep <= 64:
        sys.exit(f"BENCH_KSTEP={kstep} out of range [1, 64] (the scan "
                 "compile cost and HBM batch stacking grow with k)")
    step, init_fn = L.build_hybrid_train_step(
        cfg, mesh, learning_rate=1e-4, remat=remat_mode != "off",
        remat_policy=remat_mode if remat_mode != "off" else "full",
        k_steps=kstep)
    params, opt_state = init_fn(seed=0)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (1, B, S)).astype(np.int32)
    labels = np.roll(ids, -1, axis=-1).astype(np.int32)
    if kstep > 1:
        ids = np.broadcast_to(ids, (kstep,) + ids.shape).copy()
        labels = np.broadcast_to(labels, (kstep,) + labels.shape).copy()

    # warmup/compile. A configuration that does not fit fails here.
    # float(loss) is the fence; on this installation block_until_ready
    # holds just as well (chip_smoke.py's fence phase measures both).
    for _ in range(warmup):
        loss, params, opt_state = step(params, opt_state, ids, labels)
    float(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        loss, params, opt_state = step(params, opt_state, ids, labels)
    float(loss)  # chain of param deps ⇒ waits for all `steps` steps
    dt = time.perf_counter() - t0

    tokens = B * S * steps * kstep
    tok_per_sec = tokens / dt

    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    h, l = cfg.hidden_size, cfg.num_hidden_layers
    # training FLOPs/token: 6 FLOPs/param/token for matmul params (embedding
    # table is a gather, excluded) + causal attention ≈ 6*L*S*h (12*L*S*h for
    # full attention, halved by causal masking)
    n_matmul = n_params - cfg.vocab_size * h  # exclude embed gather
    flops_per_token = 6.0 * n_matmul + 6.0 * l * S * h
    achieved = flops_per_token * tok_per_sec

    mfu = achieved / peaks["bf16_flops"] if on_tpu else 0.0

    # run-metadata header (benchmarks/_telemetry.run_header): the
    # schema_version + bench/runtime fields scripts/bench_sentinel.py
    # keys trajectory comparability on
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmarks"))
    from _telemetry import run_header
    result = {
        **run_header("flagship_train"),
        "metric": f"llama_{n_params/1e6:.0f}M_train_mfu_"
                  f"{peaks['gen'] if on_tpu else platform}",
        "value": round(mfu, 4) if on_tpu else round(tok_per_sec, 2),
        "unit": "MFU" if on_tpu else "tokens/sec (cpu smoke)",
        "vs_baseline": round(mfu / 0.5, 4) if on_tpu else 0.0,
        "tokens_per_sec": round(tok_per_sec, 1),
        "loss": float(loss),
        "device": {"platform": platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
