"""Serving-layer latency benchmark: mixed-priority streaming requests
through ServingScheduler + ContinuousBatchingEngine.

Emits ONE line of JSON (TTFT/ITL percentiles, tokens/s, shed rate) so CI
can diff runs. Run: python benchmarks/bench_serving.py
(real chip; CPU smoke with JAX_PLATFORMS=cpu runs a tiny model).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    from paddle_tpu.models import llama as L
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.ops._common import is_tpu_platform
    from paddle_tpu.serving import SchedulerConfig, ServingScheduler

    on_tpu = is_tpu_platform(jax.devices()[0].platform)
    if on_tpu:
        cfg = L.llama_tiny(num_hidden_layers=8, hidden_size=1024)
        n_req, max_new, num_slots, chunk = 64, 64, 16, 8
        prompt_lens = (16, 128)
    else:
        cfg = L.llama_tiny(num_hidden_layers=2)
        n_req, max_new, num_slots, chunk = 24, 8, 4, 2
        prompt_lens = (3, 12)
    params = L.init_stacked_params(cfg, seed=0)
    # HBM memory ledger: armed for the whole run so the JSON line gains
    # judgeable capacity numbers (peak bytes by class, planner verdict)
    # for the int8-pages PR to beat (ISSUE 12 / ROADMAP item 2)
    from paddle_tpu.observability.memory import (MEM_CLASSES,
                                                memory_ledger)
    memory_ledger.reset()
    memory_ledger.arm()

    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=max_new),
        num_slots=num_slots, page_size=16,
        max_seq_len=_next_pow2(prompt_lens[1] + max_new), chunk=chunk,
        # cache on for the stats line, but skip the O(pool) per-step
        # conservation audit so latency numbers stay comparable with
        # earlier rounds (bench_prefix_cache.py is the cache study)
        prefix_cache=True, check_invariants=False)

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size,
                           (int(rng.randint(*prompt_lens)),)
                           ).astype(np.int32) for _ in range(n_req)]

    # warmup: untimed dry run of the SAME workload, so every prefill
    # (bucket, padded-batch) compile key and the decode chunk the
    # measured run will hit compile outside the timing window — a single
    # warm request would only cover one bucket at batch 1
    w = ServingScheduler(eng, SchedulerConfig(max_queue_depth=n_req))
    for i, p in enumerate(prompts):
        w.submit(p, priority=i % 3)
    w.run(params, max_steps=100_000)

    sched = ServingScheduler(eng, SchedulerConfig(max_queue_depth=n_req))
    t0 = time.perf_counter()
    handles = [sched.submit(p, priority=i % 3,
                            deadline_ms=None if i % 5 else 30_000)
               for i, p in enumerate(prompts)]
    sched.run(params, max_steps=100_000)
    wall = time.perf_counter() - t0

    m = sched.metrics
    ttft = m.histograms["ttft_ms"].summary()
    itl = m.histograms["itl_ms"].summary()
    tokens = int(m.counters["tokens_generated_total"])
    from _telemetry import run_header
    out = {
        **run_header("serving"),
        "platform": "tpu" if on_tpu else "cpu",
        "requests": n_req,
        "num_slots": num_slots,
        "chunk": chunk,
        "max_new_tokens": max_new,
        "completed": int(m.counters["requests_completed_total"]),
        "shed_rate": round(m.shed_total / n_req, 4),
        "tokens_total": tokens,
        "tokens_per_s": round(tokens / wall, 2),
        "wall_s": round(wall, 3),
        "ttft_ms": {k: round(ttft[k], 3) for k in ("p50", "p95", "p99")},
        "itl_ms": {k: round(itl[k], 3) for k in ("p50", "p95", "p99")},
        "queue_wait_ms_p99": round(
            m.histograms["queue_wait_ms"].percentile(0.99), 3),
        "step_ms_p50": round(m.histograms["step_ms"].percentile(0.5), 3),
    }
    # unified-telemetry snapshot: per-op dispatch counts, recompiles,
    # serving sink — the registry view a /metrics scrape would see
    # (shared shape: benchmarks/_telemetry.py)
    from _telemetry import metrics_snapshot
    ms = metrics_snapshot("paddle_serving")
    ms["serving_counters"] = (ms.pop("paddle_serving", None)
                              or {}).get("counters")
    ms["step_timer"] = sched.step_timer.summary()["step_ms"]
    out["metrics_snapshot"] = ms
    # prefix-cache effect on this (mostly-unique-prompt) workload: the
    # dedicated shared-prefix study lives in bench_prefix_cache.py
    out["kvcache"] = eng.cache.snapshot()
    assert all(h.done for h in handles)

    # length-diverse "storm": the recompile cliff study. A cold engine +
    # prompt-length spread + mid-decode admissions; recompile counts and
    # compile seconds come straight from the RecompileDetector.
    if on_tpu:
        storm_kw = dict(n_req=48, max_new=32, num_slots=8, chunk=8,
                        prompt_lens=(16, 1024), max_seq_len=2048)
    else:
        storm_kw = dict(n_req=16, max_new=8, num_slots=4, chunk=2,
                        prompt_lens=(4, 48), max_seq_len=64)
    out["storm"] = {
        "prompt_lens": list(storm_kw["prompt_lens"]),
        "requests": storm_kw["n_req"],
        "unified": _storm(cfg, params, **storm_kw),
    }

    # speculative decoding A/B: the same mid-decode-admission storm with
    # drafting on vs off. Longer budgets than the recompile storm —
    # prompt-lookup acceptance comes from the quasi-cyclic tails greedy
    # decoding settles into, which need a few dozen tokens to form. The
    # CPU smoke uses a heavier model than the latency sections above:
    # speculation trades MORE dispatches for FEWER token-forwards, so on
    # a model small enough that the Python step loop dominates the
    # forward, the A/B would measure host overhead, not the tradeoff
    # (the serving regime this targets is device-bound by construction).
    if on_tpu:
        spec_cfg, spec_params = cfg, params
        spec_kw = dict(n_req=32, max_new=64, num_slots=8, chunk=8,
                       prompt_lens=(16, 256), max_seq_len=512)
    else:
        spec_cfg = L.llama_tiny(hidden_size=256, intermediate_size=512,
                                num_hidden_layers=4)
        spec_params = L.init_stacked_params(spec_cfg, seed=0)
        spec_kw = dict(n_req=12, max_new=32, num_slots=4, chunk=2,
                       prompt_lens=(4, 24), max_seq_len=64)
    spec_on = _storm(spec_cfg, spec_params, speculative=True, warm=True,
                     **spec_kw)
    spec_off = _storm(spec_cfg, spec_params, warm=True, **spec_kw)
    # O(1) recompiles asserted ACROSS the speculative storm: one program
    # (+ at most the sanctioned flag-flip retrace)
    assert spec_on["recompiles"] <= 2, spec_on
    out["spec_ab"] = {
        "requests": spec_kw["n_req"],
        "max_new_tokens": spec_kw["max_new"],
        "spec_k": 4,
        "on": spec_on,
        "off": spec_off,
        "tokens_per_s_ratio": round(
            spec_on["tokens_per_s"] / spec_off["tokens_per_s"], 3),
    }
    # ISSUE 10 acceptance: every request in a fleet storm (speculation
    # on AND off, one mid-storm replica kill) reconstructs into a
    # complete span tree whose exclusive segments sum to the measured
    # e2e within 1%; the hot-chain profile is the fusion-pass input
    out["timeline"] = {
        "spec_off": _timeline_storm(speculative=False),
        "spec_on": _timeline_storm(speculative=True),
    }
    out["hot_chains"] = _hot_chains()
    # ISSUE 16: the in-program sampling epilogue. Greedy vs sampled vs
    # JSON-constrained storms (same engine geometry), a mixed-config
    # storm holding the O(1)-recompile line, and sampled speculation's
    # acceptance under the rejection-sampling verifier. The line's
    # headline (metric/unit/value) is this scenario's sampled tok/s —
    # the trajectory hook for later epilogue optimisations.
    out["sampling"] = _sampling_scenario(cfg, params, on_tpu)
    out["metric"] = ("serving_sampling_v5e" if on_tpu
                     else "serving_sampling_cpu_smoke")
    out["unit"] = "tokens_per_s"
    out["value"] = out["sampling"]["sampled"]["tokens_per_s"]
    out["acceptance_rate"] = \
        out["sampling"]["spec_sampled"]["acceptance_rate"]
    # capacity section: peak device bytes by class across the whole run
    # (latency engine + storms + spec A/B) and the main engine's planner
    # verdict — predicted max pages must match the real pool exactly,
    # so "int8 pages double capacity" becomes a one-line diff
    memory_ledger.observe(eng.mgr,
                          cache_stats=eng.cache.stats, audit=False)
    mem_snap = memory_ledger.snapshot()
    # the pool table is LRU-ordered and the storms registered their own
    # engines' pools — the observe above moved the MAIN engine's pool
    # to the end, so [-1] is the one whose geometry this line reports
    main_pool = mem_snap["pools"][-1]
    assert main_pool["usable_pages"] == eng.mgr.usable_pages
    planner = main_pool["planner"]
    assert planner["exact"], planner
    out["memory"] = {
        "page_bytes": main_pool["page_bytes"],
        "peak_bytes": {c: memory_ledger.peak_bytes(c)
                       for c in MEM_CLASSES},
        "planner_predicted_max_pages": planner["predicted_max_pages"],
        "planner_actual_max_pages": planner["actual_max_pages"],
        "planner_exact": planner["exact"],
        "pools_tracked": len(mem_snap["pools"]),
    }
    memory_ledger.disarm()
    print(json.dumps(out))


def _timeline_storm(speculative, n_req=8):
    """2-replica fleet storm with a mid-storm replica kill under the
    armed span collector: asserts full span-tree reconstruction and
    <1% critical-path reconciliation for EVERY request."""
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.models import llama as L
    from paddle_tpu.observability.timeline import span_collector
    from paddle_tpu.resilience import Fault, FaultInjector
    from paddle_tpu.serving import SchedulerConfig
    from paddle_tpu.serving.health import HealthConfig
    from paddle_tpu.serving.replica import ReplicaHandle
    from paddle_tpu.serving.router import FleetRouter, RouterConfig

    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=3)
    replicas = [
        ReplicaHandle(
            i,
            ContinuousBatchingEngine(
                cfg, GenerationConfig(max_new_tokens=8, seed=3),
                num_slots=2, page_size=4, max_seq_len=32, chunk=2,
                speculative=speculative),
            config=SchedulerConfig(max_step_retries=1,
                                   retry_backoff_s=0.001),
            health_config=HealthConfig())
        for i in range(2)]
    router = FleetRouter(
        replicas, config=RouterConfig(failover_backoff_s=0.001),
        fault_injector=FaultInjector(
            schedule=[Fault("replica_die", 4, replica=0)]))
    span_collector.clear()
    span_collector.arm()
    rng = np.random.RandomState(0)
    handles = []
    steps = 0
    while router.pending or len(handles) < n_req:
        if len(handles) < n_req and steps % 2 == 0:   # mid-storm trickle
            handles.append(router.submit(
                rng.randint(1, cfg.vocab_size, (5,)).astype(np.int32)))
        router.step(params)
        steps += 1
        if steps > 100_000:
            raise RuntimeError("timeline storm stalled")
    span_collector.disarm()
    complete, max_err, failovers = 0, 0.0, 0
    for h in handles:
        tl = span_collector.attribute(h.trace_id)
        assert tl is not None and tl["complete"], tl
        complete += 1
        err = abs(sum(tl["segments"].values()) - tl["e2e_ms"]) \
            / max(tl["e2e_ms"], 1e-9)
        max_err = max(max_err, err)
        if "failover" in tl["segments"]:
            failovers += 1
    assert max_err < 0.01, max_err
    assert failovers > 0, "the kill must produce a failover segment"
    span_collector.clear()
    return {"requests": n_req, "complete_trees": complete,
            "reconcile_max_err_pct": round(max_err * 100, 4),
            "failover_segments": failovers}


def _hot_chains():
    """Continuous-profiling artifact — the fusion pass's input, now fed
    by the REAL decode tail: the engine's armed plan/dispatch/unpack
    taps (inference/decoding.py) plus an eager epilogue chain, profiled
    together so the exported chains resolve to the symbols
    ``jit/fusion.py`` rewrites. The one-line JSON carries the top
    chains AND the pass's verdict on them (admitted regions / skips)."""
    import numpy as _np

    import paddle_tpu as paddle
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.jit.fusion import FusionPass
    from paddle_tpu.models import llama as L
    from paddle_tpu.observability.profiling import chain_profiler
    from paddle_tpu.observability.runtime import telemetry

    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=0)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=8), num_slots=2,
        page_size=4, max_seq_len=64, chunk=3)
    rng = _np.random.RandomState(5)
    prompts = [rng.randint(1, cfg.vocab_size, (int(n),)).astype(_np.int32)
               for n in (5, 9, 13, 7)]
    eng.serve(params, prompts[:1])            # compile outside the window
    telemetry.enable()
    chain_profiler.reset()
    chain_profiler.arm()
    eng.serve(params, prompts)
    x = paddle.to_tensor(_np.ones((8, 8), _np.float32))
    for _ in range(64):
        y = x * 2.0
        y = y + x
        y = paddle.clip(y, 0.0, 8.0)
        y = paddle.scale(y, scale=0.25)
    chain_profiler.disarm()
    doc = chain_profiler.profile(top_n=5, workload="decode_tail")
    plan = FusionPass().plan(doc)
    return {"top": doc["chains"], "symbols": doc["symbols"],
            "transitions": doc["transitions"],
            "fusion_plan": {
                "admitted": sorted({c.region.name
                                    for c in plan.candidates}),
                "skipped": [{"chain": "->".join(s["chain"]),
                             "reason": s["reason"]}
                            for s in plan.skipped]}}


def _storm(cfg, params, *, n_req, max_new, num_slots, chunk,
           prompt_lens, max_seq_len, speculative=False, warm=False):
    """One cold engine through a length-diverse storm with mid-decode
    admissions; reports recompiles, compile wall time, TTFT/ITL p50/p95
    and tok/s so the spec-on-vs-off delta is a one-line diff."""
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.observability.runtime import recompiles
    from paddle_tpu.serving import SchedulerConfig, ServingScheduler

    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=max_new),
        num_slots=num_slots, page_size=16, max_seq_len=max_seq_len,
        chunk=chunk, speculative=speculative,
        spec_k=4, check_invariants=False)
    rng = np.random.RandomState(1)
    lens = rng.randint(prompt_lens[0], prompt_lens[1] + 1, n_req)
    prompts = [rng.randint(1, cfg.vocab_size, (int(n),)).astype(np.int32)
               for n in lens]
    fns = ("cbe.unified_step", "cbe.spec_step")
    rc0 = {f: recompiles.count(f) for f in fns}
    cs0 = {f: recompiles.compile_seconds_total(f) for f in fns}

    if warm:
        # A/B mode: compile outside the timing window (the recompile
        # counters above still span the warmup, so the O(1) assertion
        # covers the whole run); the cold-compile study is the
        # recompile storm. The warmup rides a THROWAWAY
        # scheduler (main()'s idiom) so the measured scheduler's
        # token counters and TTFT/ITL histograms hold only the timed
        # requests — not the warmup's compile-inclusive TTFT.
        w = ServingScheduler(eng, SchedulerConfig(max_queue_depth=1))
        w.submit(prompts[0])
        w.run(params, max_steps=100_000)
    sched = ServingScheduler(eng, SchedulerConfig(max_queue_depth=n_req))

    t0 = time.perf_counter()
    # a third lands up front; the rest trickle in MID-DECODE, so every
    # admission joins live traffic
    upfront = max(1, n_req // 3)
    handles = [sched.submit(p) for p in prompts[:upfront]]
    i = upfront
    steps = 0
    while sched.pending or i < n_req:
        if i < n_req and steps % 2 == 0:
            handles.append(sched.submit(prompts[i]))
            i += 1
        sched.step(params)
        steps += 1
        if steps > 200_000:
            raise RuntimeError("storm stalled")
    wall = time.perf_counter() - t0
    assert all(h.done for h in handles)

    m = sched.metrics
    ttft = m.histograms["ttft_ms"]
    itl = m.histograms["itl_ms"]
    out = {
        "recompiles": int(sum(recompiles.count(f) - rc0[f] for f in fns)),
        "compile_s": round(sum(
            recompiles.compile_seconds_total(f) - cs0[f] for f in fns), 3),
        "tokens_per_s": round(
            m.counters["tokens_generated_total"] / wall, 2),
        "wall_s": round(wall, 3),
        "ttft_ms": {"p50": round(ttft.percentile(0.5), 3),
                    "p95": round(ttft.percentile(0.95), 3)},
        "itl_ms": {"p50": round(itl.percentile(0.5), 3),
                   "p95": round(itl.percentile(0.95), 3)},
    }
    if speculative:
        out["acceptance_rate"] = round(eng.spec.acceptance_ratio, 4)
        out["spec"] = eng.spec.snapshot()
    return out


def _sampling_scenario(cfg, params, on_tpu):
    """Distribution-faithful decoding study: per-mode storms through the
    scheduler on identical engine geometry. ``mixed`` interleaves all
    three modes in ONE engine and asserts the recompile budget — the
    per-request sampler/grammar state is program INPUT, so the mix
    compiles at most twice (cold + sanctioned flag retrace)."""
    from paddle_tpu.inference.constrain import compile_regex, json_regex
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.inference.sampling import SamplerConfig
    from paddle_tpu.observability.runtime import recompiles
    from paddle_tpu.serving import SchedulerConfig, ServingScheduler

    if on_tpu:
        n_req, max_new, num_slots, chunk = 32, 32, 8, 8
        prompt_lens, max_seq_len = (16, 256), 512
    else:
        n_req, max_new, num_slots, chunk = 12, 8, 4, 2
        prompt_lens, max_seq_len = (4, 24), 64

    vocab = ["<eos>"] + list('{}[]:, ') + ['"', '\\']
    vocab += list("abcdefghijklmnopqrstuvwxyz0123456789+-.eE")
    vocab += [f"<junk{i}>" for i in range(len(vocab), cfg.vocab_size)]
    gram = compile_regex(json_regex(max_depth=1), vocab, eos_token_id=0)

    rng = np.random.RandomState(5)
    lens = rng.randint(prompt_lens[0], prompt_lens[1] + 1, n_req)
    prompts = [rng.randint(1, cfg.vocab_size, (int(n),)).astype(np.int32)
               for n in lens]
    modes = {
        "greedy": lambda i: {},
        "sampled": lambda i: {"sampler": SamplerConfig(
            temperature=0.8, top_k=0, top_p=0.95, seed=1000 + i)},
        "constrained": lambda i: {
            "sampler": SamplerConfig(temperature=1.0, seed=2000 + i),
            "grammar": gram},
        "mixed": lambda i: modes[("greedy", "sampled",
                                  "constrained")[i % 3]](i),
        # near-deterministic sampling for the speculation study: the
        # rejection verifier's acceptance is bounded by how sharp the
        # target is, and this model is UNTRAINED — near-flat logits make
        # high-temperature streams aperiodic, so prompt-lookup drafts
        # never land. At temperature 0.02 the target concentrates, the
        # stream develops the quasi-cyclic tails the drafter feeds on,
        # and acceptance approaches the greedy bound while every token
        # still comes from the target distribution.
        "spec": lambda i: {"sampler": SamplerConfig(
            temperature=0.02, seed=3000 + i)},
    }

    def storm(mode, speculative=False, budget=max_new):
        eng = ContinuousBatchingEngine(
            cfg, GenerationConfig(max_new_tokens=budget),
            num_slots=num_slots, page_size=16, max_seq_len=max_seq_len,
            chunk=chunk, speculative=speculative, spec_k=4,
            grammar_states=gram.n_states, check_invariants=False)
        fns = ("cbe.unified_step", "cbe.spec_step")
        rc0 = {f: recompiles.count(f) for f in fns}
        w = ServingScheduler(eng, SchedulerConfig(max_queue_depth=1))
        # representative warmup: mixed rotates greedy first, but the
        # program that serves the storm is the full-epilogue one (the
        # engine compiles the argmax-only tail until the first
        # sampler/grammar submit) — warm with a sampled config so the
        # timed region measures serving, not the one-time lazy flip
        w.submit(prompts[0], **modes[mode](1 if mode == "mixed" else 0))
        w.run(params, max_steps=100_000)
        sched = ServingScheduler(eng,
                                 SchedulerConfig(max_queue_depth=n_req))
        t0 = time.perf_counter()
        upfront = max(1, n_req // 3)
        handles = [sched.submit(p, **modes[mode](i))
                   for i, p in enumerate(prompts[:upfront])]
        i, steps = upfront, 0
        while sched.pending or i < n_req:
            if i < n_req and steps % 2 == 0:
                handles.append(sched.submit(prompts[i],
                                            **modes[mode](i)))
                i += 1
            sched.step(params)
            steps += 1
            if steps > 200_000:
                raise RuntimeError("sampling storm stalled")
        wall = time.perf_counter() - t0
        assert all(h.done for h in handles)
        m = sched.metrics
        out = {
            "recompiles": int(sum(recompiles.count(f) - rc0[f]
                                  for f in fns)),
            "tokens_per_s": round(
                m.counters["tokens_generated_total"] / wall, 2),
            "wall_s": round(wall, 3),
            "ttft_ms_p50": round(
                m.histograms["ttft_ms"].percentile(0.5), 3),
        }
        if speculative:
            out["acceptance_rate"] = round(eng.spec.acceptance_ratio, 4)
        return out

    out = {"requests": n_req, "max_new_tokens": max_new,
           "grammar_states": gram.n_states,
           "greedy": storm("greedy"),
           "sampled": storm("sampled"),
           "constrained": storm("constrained"),
           "mixed": storm("mixed"),
           "spec_sampled": storm("spec", speculative=True,
                                 budget=min(32, max_seq_len
                                            - prompt_lens[1]))}
    # the acceptance bar: mixing greedy/sampled/constrained rows stays
    # inside the unified step's compile budget
    assert out["mixed"]["recompiles"] <= 2, out["mixed"]
    return out


def _next_pow2(n, minimum=32):
    b = minimum
    while b < n:
        b *= 2
    return b


if __name__ == "__main__":
    main()
