"""Prefix-cache benchmark: cold vs warm serving of a shared system prompt.

Every request is ``<shared system prompt> + <unique user suffix>``. The
cold wave prefills everything; the warm wave should reuse the cached
system-prompt pages and prefill only suffixes. Emits ONE line of JSON —
prefill tokens computed, TTFT percentiles, hit rate, skip percentage —
so CI can diff the cache's effect run over run. Run:
python benchmarks/bench_prefix_cache.py (real chip; CPU smoke with
JAX_PLATFORMS=cpu runs a tiny model).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _next_pow2(n, minimum=32):
    b = minimum
    while b < n:
        b *= 2
    return b


def main():
    import jax

    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.models import llama as L
    from paddle_tpu.ops._common import is_tpu_platform
    from paddle_tpu.serving import SchedulerConfig, ServingScheduler

    on_tpu = is_tpu_platform(jax.devices()[0].platform)
    sys_len = 256                       # the shared system prompt
    if on_tpu:
        cfg = L.llama_tiny(num_hidden_layers=8, hidden_size=1024)
        n_req, max_new, num_slots, chunk = 32, 32, 8, 8
        sfx_lens = (16, 64)
    else:
        cfg = L.llama_tiny(num_hidden_layers=2)
        n_req, max_new, num_slots, chunk = 8, 8, 4, 2
        sfx_lens = (8, 24)
    params = L.init_stacked_params(cfg, seed=0)
    max_seq = _next_pow2(sys_len + sfx_lens[1] + max_new)

    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=max_new),
        num_slots=num_slots, page_size=16, max_seq_len=max_seq,
        chunk=chunk, prefix_cache=True)
    # HBM ledger armed for the run: the cache study gains the byte view
    # (how much of the pool the warm cache actually holds) plus the
    # planner verdict the int8-pages PR must double (ISSUE 12)
    from paddle_tpu.observability.memory import (MEM_CLASSES,
                                                memory_ledger)
    memory_ledger.reset()
    memory_ledger.arm()

    rng = np.random.RandomState(0)

    def workload(seed):
        r = np.random.RandomState(seed)
        sys_p = r.randint(1, cfg.vocab_size, (sys_len,)).astype(np.int32)
        return [np.concatenate([sys_p,
                                r.randint(1, cfg.vocab_size,
                                          (int(r.randint(*sfx_lens)),)
                                          ).astype(np.int32)])
                for _ in range(n_req)]

    def wave(prompts):
        sched = ServingScheduler(eng, SchedulerConfig(max_queue_depth=n_req))
        tokens0 = eng._prefill_tokens
        hits0, miss0 = eng.cache.stats["hits"], eng.cache.stats["misses"]
        t0 = time.perf_counter()
        for p in prompts:
            sched.submit(p)
        sched.run(params, max_steps=100_000)
        wall = time.perf_counter() - t0
        m = sched.metrics
        return {
            "prefill_tokens": eng._prefill_tokens - tokens0,
            "hits": eng.cache.stats["hits"] - hits0,
            "misses": eng.cache.stats["misses"] - miss0,
            "ttft_ms": {k: round(m.histograms["ttft_ms"].summary()[k], 3)
                        for k in ("p50", "p95")},
            "wall_s": round(wall, 3),
        }

    # warmup: full dry run of BOTH waves of the SAME workload so every
    # prefill compile key — the plain cold-wave programs AND the
    # warm-wave suffix programs — compiles outside the timing window;
    # evicting everything afterwards puts the cache (but not the compile
    # caches) back in the cold state, and the deterministic greedy loop
    # replays the identical admission pattern in the measured waves
    prompts = workload(seed=1)
    wave(prompts)
    wave(prompts)
    eng.cache.evict(eng.mgr.num_pages)
    assert eng.mgr.num_cached_pages == 0

    cold = wave(prompts)                # populates the cache
    warm = wave(prompts)                # same prompts: prefix resident

    skipped = 1.0 - warm["prefill_tokens"] / max(cold["prefill_tokens"], 1)
    from _telemetry import run_header
    out = {
        **run_header("prefix_cache"),
        "platform": "tpu" if on_tpu else "cpu",
        "requests": n_req,
        "sys_prompt_tokens": sys_len,
        "max_new_tokens": max_new,
        "num_slots": num_slots,
        "cold": cold,
        "warm": warm,
        "prefill_tokens_skipped_pct": round(100 * skipped, 2),
        "warm_hit_rate": round(
            warm["hits"] / max(warm["hits"] + warm["misses"], 1), 4),
        "ttft_speedup_p50": round(
            cold["ttft_ms"]["p50"] / max(warm["ttft_ms"]["p50"], 1e-9), 3),
        "kvcache": eng.cache.snapshot(),
    }
    # same registry view every bench carries (benchmarks/_telemetry.py)
    from _telemetry import metrics_snapshot
    out["metrics_snapshot"] = metrics_snapshot()
    # capacity section: the byte split behind the hit rate (cached pages
    # ARE spent HBM) + planner verdict — "same HBM, 2x the pages" (int8
    # pages, ROADMAP item 2) must move these numbers, measurably
    mem_snap = memory_ledger.snapshot()
    planner = mem_snap["pools"][0]["planner"]
    assert planner["exact"], planner
    out["memory"] = {
        "page_bytes": mem_snap["pools"][0]["page_bytes"],
        "bytes": mem_snap["pools"][0]["bytes"],
        "peak_bytes": {c: memory_ledger.peak_bytes(c)
                       for c in MEM_CLASSES},
        "planner_predicted_max_pages": planner["predicted_max_pages"],
        "planner_actual_max_pages": planner["actual_max_pages"],
        "planner_exact": planner["exact"],
    }
    memory_ledger.disarm()
    assert skipped >= 0.5, (
        f"warm wave skipped only {100 * skipped:.1f}% of prefill tokens")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
