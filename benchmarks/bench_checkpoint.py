"""Durable-checkpoint latency benchmark.

Measures (1) sync durable save latency (stage + fsync + CRC32 + atomic
rename commit), (2) intact-checkpoint load latency, and (3) async-save
overlap overhead: extra wall time a training loop pays per step while a
durable save runs on the writer thread, vs the same loop with no save
in flight. Emits ONE line of JSON so CI can diff runs.

Run: python benchmarks/bench_checkpoint.py
(CPU smoke with JAX_PLATFORMS=cpu uses a smaller state dict.)
"""

import json
import os
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q))


def main():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed.checkpoint import TrainState
    from paddle_tpu.ops._common import is_tpu_platform
    from paddle_tpu.resilience import (async_save_checkpoint,
                                       load_latest_checkpoint,
                                       save_checkpoint)

    on_tpu = is_tpu_platform(jax.devices()[0].platform)
    hidden, repeats = (2048, 8) if on_tpu else (256, 5)
    train_steps_per_save = 20

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(hidden, hidden), nn.ReLU(),
                        nn.Linear(hidden, hidden))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters())
    state = TrainState(net, opt)
    x = paddle.to_tensor(np.ones((8, hidden), np.float32))

    def train_step():
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        state.step()
        return loss

    train_step()  # materialise optimizer moments + compile
    state_bytes = sum(
        int(np.prod(p.shape)) * 4 for p in net.parameters()) * 3  # w, m, v

    root = os.path.join("/tmp", f"bench_ckpt_{os.getpid()}")
    shutil.rmtree(root, ignore_errors=True)

    # (1) sync durable save: snapshot + stage + fsync + CRC + rename
    save_ms = []
    for i in range(repeats):
        t0 = time.perf_counter()
        save_checkpoint(state.state_dict(), root, step=i, keep=2)
        save_ms.append((time.perf_counter() - t0) * 1e3)

    # (2) load latest (checksums verified)
    t0 = time.perf_counter()
    target = state.state_dict()
    restored = load_latest_checkpoint(target, root)
    load_ms = (time.perf_counter() - t0) * 1e3
    assert restored == repeats - 1, restored

    # (3) overlap overhead: per-step cost with an async save in flight
    def timed_steps(n):
        t0 = time.perf_counter()
        for _ in range(n):
            train_step()
        return (time.perf_counter() - t0) * 1e3 / n

    base_step_ms = timed_steps(train_steps_per_save)
    fut = async_save_checkpoint(state.state_dict(), root,
                                step=state.global_step, keep=2)
    overlapped_step_ms = timed_steps(train_steps_per_save)
    fut.result(timeout=300)
    shutil.rmtree(root, ignore_errors=True)

    overhead = (overlapped_step_ms - base_step_ms) / max(base_step_ms, 1e-9)
    # unified-telemetry snapshot: dispatch + recompile counters from the
    # process-global registry (shared shape: benchmarks/_telemetry.py)
    from _telemetry import metrics_snapshot as _snapshot
    from _telemetry import run_header
    metrics_snapshot = _snapshot()
    print(json.dumps({
        **run_header("checkpoint"),
        "platform": "tpu" if on_tpu else "cpu",
        "state_mb": round(state_bytes / 2 ** 20, 2),
        "sync_save_ms": {"p50": round(_pct(save_ms, 50), 3),
                         "max": round(max(save_ms), 3)},
        "load_ms": round(load_ms, 3),
        "step_ms_baseline": round(base_step_ms, 4),
        "step_ms_during_async_save": round(overlapped_step_ms, 4),
        "async_overlap_overhead_pct": round(overhead * 100, 2),
        "metrics_snapshot": metrics_snapshot,
    }))


if __name__ == "__main__":
    main()
