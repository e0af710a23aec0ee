"""Fleet-router benchmark: shared-prompt storm over 4 replicas with one
injected mid-storm replica death, plus the elastic mesh-resize recovery
scenario (ISSUE 14).

Measures what the router tier actually buys:

* **prefix affinity hit rate** — fraction of requests routed to the
  replica whose cache already holds their prefix (the router-side radix
  index doing its job);
* **failover recovery p50** — ms from a request's failover to its
  completion on the sibling (the mid-stream re-admission cost);
* **TTFT delta vs single replica** — the same storm through a 1-replica
  "fleet", so queueing relief is visible as a TTFT ratio;
* **resize recovery** — an mp=2-sharded 2-replica fleet loses one chip
  of one replica mid-storm: recovery p50 (failover → completion on the
  surviving fleet) and delivered tok/s before / during / after the
  die → re-shard → rejoin arc. The judged sentinel metric
  (``metric=router_resize_*``, unit ``tokens_per_s``) is the
  post-rejoin throughput — a regression here means the rebuilt replica
  is not pulling its weight;
* **page migration + host loss** (ISSUE 17) — a 2-host wire-framed
  fleet migrates host 0's flights WITH their KV pages mid-decode, then
  a seeded ``host_die`` kills the destination: migration bytes/pages/
  latency, host-loss failover recovery p50, and tok/s before / during /
  after the loss (rides as ``migration``, not the judged series).

Emits ONE line of JSON (plus the shared ``_telemetry.py`` registry
snapshot). Run: python benchmarks/bench_router.py
(real chip; CPU smoke with JAX_PLATFORMS=cpu runs a tiny model).
"""

import json
import os
import sys
import time

import numpy as np

# the resize scenario shards replicas over mp=2 meshes: the CPU smoke
# needs the virtual 8-device backend (must be set before jax init)
if os.environ.get("JAX_PLATFORMS", "") == "cpu" and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


def _build_fleet(n_replicas, cfg, max_new, num_slots, chunk, page_size,
                 max_seq_len, prefix_cache):
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.serving import (FleetRouter, HealthConfig,
                                    ReplicaHandle, RouterConfig,
                                    SchedulerConfig)
    replicas = []
    for i in range(n_replicas):
        eng = ContinuousBatchingEngine(
            cfg, GenerationConfig(max_new_tokens=max_new),
            num_slots=num_slots, page_size=page_size,
            max_seq_len=max_seq_len, chunk=chunk,
            prefix_cache=prefix_cache, check_invariants=False)
        replicas.append(ReplicaHandle(
            i, eng,
            config=SchedulerConfig(max_queue_depth=256,
                                   max_step_retries=1,
                                   retry_backoff_s=0.005),
            health_config=HealthConfig(eject_after=1,
                                       probe_cooldown_s=60.0)))
    return FleetRouter(replicas,
                       config=RouterConfig(failover_backoff_s=0.005))


def _storm(router, params, prompts, kill_replica=None, kill_after_steps=2,
           max_steps=200_000):
    handles = [router.submit(p) for p in prompts]
    steps = 0
    while router.pending:
        router.step(params)
        steps += 1
        if kill_replica is not None and steps == kill_after_steps:
            router.replicas[kill_replica].kill()
        if steps >= max_steps:
            raise RuntimeError("storm did not converge")
    return handles


def _resize_scenario(cfg, params, prompts, max_new, num_slots, chunk,
                     page_size, max_seq_len, kill_step=6):
    """Elastic mesh-resize recovery: a 2-replica mp=2 fleet loses one
    chip of replica 0 mid-storm. Returns recovery p50 and tok/s
    delivered before / during / after the die → re-shard → rejoin arc
    (token counts read off the consumer streams, so replacement-sink
    metric resets can't skew them)."""
    import jax
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.parallel.mesh import serving_mesh
    from paddle_tpu.resilience import Fault, FaultInjector
    from paddle_tpu.serving import (ElasticServingController, FleetRouter,
                                    HealthConfig, ReplicaHandle,
                                    RouterConfig, SchedulerConfig)

    def engine_factory(mesh):
        return ContinuousBatchingEngine(
            cfg, GenerationConfig(max_new_tokens=max_new),
            num_slots=num_slots, page_size=page_size,
            max_seq_len=max_seq_len, chunk=chunk, prefix_cache=True,
            check_invariants=False, mesh=mesh)

    def handle_factory(rid, eng):
        return ReplicaHandle(
            rid, eng,
            config=SchedulerConfig(max_queue_depth=256,
                                   max_step_retries=1,
                                   retry_backoff_s=0.005),
            health_config=HealthConfig(eject_after=1,
                                       probe_cooldown_s=60.0))

    # mp=2 replicas when the backend has the chips (the CPU smoke's 8
    # virtual devices, or a real pod slice); a 1-chip box still runs
    # the arc as rebuild-in-place (chip_die on a single-chip replica)
    devs = jax.devices()
    mp = 2 if len(devs) >= 4 else 1

    def fleet(injector=None):
        handles = [handle_factory(i, engine_factory(
            serving_mesh(mp, devs[mp * i:mp * (i + 1)]) if mp > 1
            else None)) for i in range(2)]
        router = FleetRouter(
            handles, config=RouterConfig(failover_backoff_s=0.005),
            fault_injector=injector)
        ctl = ElasticServingController(router, engine_factory,
                                       handle_factory,
                                       fault_injector=injector)
        return router, ctl

    def drive(router, ctl, handles):
        streamed = lambda: sum(len(h.stream.tokens) for h in handles)
        marks = {}          # phase -> (t, tokens_streamed)
        t0 = time.perf_counter()
        steps = 0
        while router.pending or ctl.resizing:
            ctl.step(params)
            steps += 1
            if ctl.resizes and "kill" not in marks:
                marks["kill"] = (time.perf_counter(), streamed())
            if "kill" in marks and "recovered" not in marks:
                # the recovery window closes when every flight the kill
                # interrupted has completed on the surviving fleet (the
                # re-shard itself is synchronous — the window that
                # matters is the failover drain)
                hit = [h for h in handles if h.failovers > 0]
                if hit and all(h.stream.finished for h in hit):
                    marks["recovered"] = (time.perf_counter(), streamed())
            if steps >= 200_000:
                raise RuntimeError("resize storm did not converge")
        return t0, marks, time.perf_counter(), streamed()

    # warmup: compile both replicas' programs + warm the caches/index
    router_w, ctl_w = fleet()
    hw = [router_w.submit(p) for p in prompts]
    drive(router_w, ctl_w, hw)

    inj = FaultInjector(schedule=[
        Fault("chip_die", kill_step, replica=0, chip=mp - 1)])
    router, ctl = fleet(injector=inj)
    handles = [router.submit(p) for p in prompts]
    t0, marks, t_end, tok_end = drive(router, ctl, handles)
    assert all(h.stream.finished for h in handles)
    assert ctl.resizes and ctl.resizes[0].done
    (t_kill, tok_kill) = marks["kill"]
    (t_rec, tok_rec) = marks.get("recovered", (t_end, tok_end))
    failed_over = [h for h in handles if h.failovers > 0]
    recovery_ms = [(h.finish_t - h.failover_t) * 1e3 for h in failed_over
                   if h.failover_t is not None and h.finish_t is not None]

    def rate(tokens, dt):
        return round(tokens / dt, 2) if dt > 1e-9 else 0.0

    # "after": a fresh storm through the RESIZED fleet (one replica now
    # on the smaller mesh) — the steady-state cost of running degraded
    after_handles = [router.submit(p) for p in prompts]
    t_a = time.perf_counter()
    steps = 0
    while router.pending:
        ctl.step(params)
        steps += 1
        assert steps < 200_000
    after_s = time.perf_counter() - t_a
    tok_after = sum(len(h.stream.tokens) for h in after_handles)

    return {
        "resize_recovery_ms_p50": round(_percentile(recovery_ms, 50), 3),
        "resize_failovers": len(failed_over),
        "recovery_window_ms": round((t_rec - t_kill) * 1e3, 3),
        "tokens_per_s_overall": rate(tok_end, t_end - t0),
        "tokens_per_s_before": rate(tok_kill, t_kill - t0),
        "tokens_per_s_during": rate(tok_rec - tok_kill, t_rec - t_kill),
        "tokens_per_s_after": rate(tok_after, after_s),
        "from_chips": ctl.resizes[0].from_chips,
        "to_chips": ctl.resizes[0].to_chips,
    }


def _migration_scenario(prompts, max_new, num_slots, chunk, page_size,
                        migrate_step=4, kill_step=10):
    """Multi-host page-migration + host-loss arc (ISSUE 17): a 2-host
    fleet (in-process ``LocalTransport`` hosts — every frame still
    travels the versioned wire format) drains host 0 mid-decode with
    its KV pages, then a seeded ``host_die`` kills host 1 — which now
    holds the migrated pages AND its own flights — so every interrupted
    request fails over back to host 0. Reports the migration's
    byte/page/latency cost and delivered tok/s before / during / after
    the loss (token counts read off the consumer streams)."""
    import dataclasses

    from paddle_tpu.resilience import Fault, FaultInjector
    from paddle_tpu.serving import (HealthConfig, HostEndpoint,
                                    HostFleetRouter, HostHandle,
                                    HostServer, LocalTransport,
                                    RouterConfig, SchedulerConfig)
    from paddle_tpu.serving.multihost import llama_tiny_host

    hosts = []
    for i in range(2):
        eng, params = llama_tiny_host(
            max_new_tokens=max_new, num_slots=num_slots, chunk=chunk,
            page_size=page_size, max_seq_len=48)
        server = HostServer(eng, params, host_id=i,
                            scheduler_config=SchedulerConfig(
                                max_queue_depth=256, max_step_retries=1,
                                retry_backoff_s=0.005))
        hosts.append(HostHandle(
            i, HostEndpoint(LocalTransport(server)),
            health_config=HealthConfig(suspect_after=1, eject_after=2,
                                       probe_cooldown_s=600.0)))
    router = HostFleetRouter(
        hosts, config=RouterConfig(failover_backoff_s=0.005))

    def drive(handles, migrate=False, inj=None):
        mig = None
        marks = {}
        streamed = lambda: sum(len(h.stream.tokens) for h in handles)
        t0 = time.perf_counter()
        steps = 0
        while router.pending:
            router.step(None)
            steps += 1
            if migrate and mig is None and steps >= migrate_step:
                # wait for a migratable flight: drain() hands QUEUED
                # mirrors off page-free, so the arc only measures page
                # transfer once host 0 holds a mid-decode stream
                if any(r.replica_id == 0 and r.handle is not None
                       and not r.done and r.handle.state == "running"
                       and len(r.stream.tokens) >= 1
                       for r in router._requests.values()):
                    mig = router.migrate_host(0)
                    router.undrain(0)
            if inj is not None and inj.fired and "kill" not in marks:
                marks["kill"] = (time.perf_counter(), streamed())
            if "kill" in marks and "recovered" not in marks:
                hit = [h for h in handles if h.failovers > 0]
                if hit and all(h.stream.finished for h in hit):
                    marks["recovered"] = (time.perf_counter(), streamed())
            if steps >= 200_000:
                raise RuntimeError("migration storm did not converge")
        return t0, marks, time.perf_counter(), streamed(), mig

    def rate(tokens, dt):
        return round(tokens / dt, 2) if dt > 1e-9 else 0.0

    # warmup: compile both hosts' programs, warm caches + router index
    drive([router.submit(p) for p in prompts])
    drive([router.submit(p) for p in prompts])

    # measured arc: migrate host 0's flights (pages included) at
    # migrate_step, then a seeded host_die takes out host 1 — the new
    # home of the migrated pages — at kill_step (rebased past warmup)
    inj = FaultInjector.seeded_hosts(seed=17, num_steps=1, num_hosts=2,
                                     events=("host_die",))
    inj.schedule = [dataclasses.replace(f, step=kill_step + router._steps,
                                        host=1) for f in inj.schedule]
    router.injector = inj
    # the measured arc runs with the telemetry federation ARMED: every
    # heartbeat also pulls a wire-framed telemetry frame, so the JSON
    # line carries the fleet's clock-reconcile error and heartbeat RTT
    router.federation.arm()
    handles = [router.submit(p) for p in prompts]
    t0, marks, t_end, tok_end, mig = drive(handles, migrate=True, inj=inj)
    fed_reconcile_ms = router.federation.reconcile_error_s() * 1e3
    fed_rtt_p50_ms = \
        router.federation.mirror(0).clock.rtt_quantile(0.5) / 1e6
    router.federation.disarm()
    assert all(h.stream.finished for h in handles)
    assert inj.fired and mig is not None and mig["failed"] == 0
    (t_kill, tok_kill) = marks["kill"]
    (t_rec, tok_rec) = marks.get("recovered", (t_end, tok_end))
    failed_over = [h for h in handles if h.failovers > 0]
    recovery_ms = [(h.finish_t - h.failover_t) * 1e3 for h in failed_over
                   if h.failover_t is not None and h.finish_t is not None]

    # "after": a fresh storm through the halved fleet — the steady-state
    # cost of serving on the survivor until the host is replaced
    after = [router.submit(p) for p in prompts]
    t_a = time.perf_counter()
    steps = 0
    while router.pending:
        router.step(None)
        steps += 1
        assert steps < 200_000
    after_s = time.perf_counter() - t_a
    tok_after = sum(len(h.stream.tokens) for h in after)
    router.close()

    return {
        "migration_requests": mig["requests"],
        "migration_pages": mig["pages"],
        "migration_bytes": mig["bytes"],
        "migration_ms": round(mig["seconds"] * 1e3, 3),
        "host_loss_failovers": len(failed_over),
        "host_loss_recovery_ms_p50": round(_percentile(recovery_ms, 50), 3),
        "federation_reconcile_error_ms": round(fed_reconcile_ms, 6),
        "federation_rtt_p50_ms": round(fed_rtt_p50_ms, 6),
        "tokens_per_s_overall": rate(tok_end, t_end - t0),
        "tokens_per_s_before": rate(tok_kill, t_kill - t0),
        "tokens_per_s_during": rate(tok_rec - tok_kill, t_rec - t_kill),
        "tokens_per_s_after": rate(tok_after, after_s),
    }


def _diurnal_scenario(cfg, params, max_new, num_slots, chunk, page_size,
                      max_seq_len):
    """Diurnal-traffic arc (ISSUE 19): the same phased storm — a
    baseline trickle, then a 10x prompt-heavy burst — through (a) a
    static all-HYBRID fleet and (b) a PREFILL/DECODE role fleet under
    the autoscaling controller, with identical prompts in identical
    order.

    Two clocks, deliberately: the FLEET clock is deterministic (0.05s
    per driver step) so autoscale evidence windows, cooldowns and TTFT
    are step-count facts, not wall-speed races; steady-state ITL is
    measured in PER-REPLICA wall step time (each replica modelled as
    its own accelerator — the serial CPU driver must not charge one
    replica's prefill work to another replica's decode cadence). A
    token's gap counts only when the SAME replica produced the
    previous token, so handoff/failover dispatch gaps are excluded
    symmetrically in both fleets. The headline gate: the role fleet's
    burst-phase decode ITL p95 must beat the hybrid fleet's —
    decode-only steps stay short while hybrid steps interleave
    chunked prefill with decode."""
    from paddle_tpu.serving import (AutoscaleConfig, AutoscaleController,
                                    DisaggRouter, HealthConfig,
                                    ReplicaHandle, ReplicaRole,
                                    RouterConfig, SchedulerConfig)
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)

    rng = np.random.RandomState(11)

    # scenario-local knobs: the ITL contrast only rises above JAX
    # dispatch jitter (~1-3ms/step on CPU regardless of batch) when a
    # prefill chunk carries real compute, so prefill-heavy means BIG
    # chunks and 6-8 page prompts; longer decodes buy more gap samples
    # for a stable p95
    d_chunk = chunk * 4
    d_max_new = max(max_new, 8)
    d_msl = max(max_seq_len, 8 * page_size + 2 * d_max_new)

    def prompt(lo_pages, hi_pages):
        n = int(rng.randint(lo_pages * page_size, hi_pages * page_size))
        return rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)

    # ONE schedule, shared verbatim by both fleets: a trickle of short
    # prompts, then 2 heavy prompts per step for 8 steps (~10x the
    # baseline's 1-per-6-steps arrival rate)
    schedule = {}
    for i in range(4):
        schedule.setdefault(i * 6, []).append(("baseline", prompt(1, 2)))
    for i in range(8):
        schedule.setdefault(24 + i, []).extend(
            ("burst", prompt(6, 8)) for _ in range(2))
    # warmup storm: same length classes, different content (prefix
    # cache must MISS in the measured pass), concurrent so the mixed
    # prefill+decode batch shapes compile before timing starts
    warm = [prompt(1, 2), prompt(6, 8), prompt(6, 8)]

    class _FleetClock:
        def __init__(self):
            self.t = 1000.0

        def __call__(self):
            return self.t

        def sleep(self, dt):
            self.t += dt

    def run(roles, autoscale):
        cum, start = {}, {}          # per-replica accumulated step time
        clock = _FleetClock()
        engines = []

        def engine_factory():
            eng = ContinuousBatchingEngine(
                cfg, GenerationConfig(max_new_tokens=d_max_new),
                num_slots=num_slots, page_size=page_size,
                max_seq_len=d_msl, chunk=d_chunk, prefix_cache=True,
                check_invariants=False)
            engines.append(eng)
            return eng

        def handle_factory(rid, eng):
            h = ReplicaHandle(
                rid, eng,
                config=SchedulerConfig(max_queue_depth=256,
                                       max_step_retries=1,
                                       retry_backoff_s=0.005),
                health_config=HealthConfig(eject_after=1,
                                           probe_cooldown_s=60.0),
                clock=clock, sleep=clock.sleep)
            cum[rid] = 0.0
            orig = h.step

            def stepped(p, _rid=rid, _orig=orig):
                start[_rid] = time.perf_counter()
                try:
                    return _orig(p)
                finally:
                    cum[_rid] += time.perf_counter() - start[_rid]
                    start[_rid] = None
            h.step = stepped
            return h

        def rt(rid):
            """This replica's own clock: its accumulated step time."""
            s = start.get(rid)
            return cum[rid] + (time.perf_counter() - s
                               if s is not None else 0.0)

        handles = [handle_factory(i, engine_factory()) for i in range(3)]
        router = DisaggRouter(
            handles, roles=roles,
            config=RouterConfig(failover_backoff_s=0.005),
            clock=clock, sleep=clock.sleep)
        monitor = router.make_slo_monitor(completion_target=0.99,
                                          min_events=1)
        ctl = None
        if autoscale:
            ctl = AutoscaleController(
                router, engine_factory, handle_factory,
                config=AutoscaleConfig(min_replicas=3, max_replicas=4,
                                       up_queue_depth=1.0, up_trend=-1e9,
                                       evidence_rounds=2, cooldown_s=0.3,
                                       rebalance_backlog=0.5),
                interval_s=0.05)
        drive = ctl.step if ctl is not None else router.step

        # warmup: compile every admission/decode shape, warm the caches
        for p in warm:
            router.submit(p)
        steps = 0
        while router.pending:
            drive(params)
            clock.sleep(0.05)
            steps += 1
            assert steps < 200_000

        recs = []

        def submit(phase, p):
            rec = {"phase": phase, "h": None, "toks": []}

            def on_tok(t, rec=rec):
                rid = rec["h"].replica_id
                rec["toks"].append((rid, rt(rid)))
            rec["h"] = router.submit(p, on_token=on_tok)
            recs.append(rec)

        t0 = time.perf_counter()
        sched, step = dict(schedule), 0
        while sched or router.pending:
            for phase, p in sched.pop(step, []):
                submit(phase, p)
            drive(params)
            clock.sleep(0.05)
            step += 1
            assert step < 200_000, "diurnal storm did not converge"
        wall = time.perf_counter() - t0
        assert all(r["h"].state == "done" for r in recs)

        phases = {}
        for phase in ("baseline", "burst"):
            sub = [r for r in recs if r["phase"] == phase]
            ttft = [r["h"].ttft_ms for r in sub
                    if r["h"].ttft_ms is not None]
            gaps = []
            for r in sub:
                toks = r["toks"]
                gaps += [(t1 - t0_) * 1e3
                         for (r0, t0_), (r1, t1) in zip(toks, toks[1:])
                         if r0 == r1]      # same-replica cadence only
            phases[phase] = {
                "requests": len(sub),
                "ttft_ms_p50": round(_percentile(ttft, 50), 3),
                "ttft_ms_p95": round(_percentile(ttft, 95), 3),
                "itl_ms_p50": round(_percentile(gaps, 50), 3),
                "itl_ms_p95": round(_percentile(gaps, 95), 3),
            }
        out = {"phases": phases, "wall_s": round(wall, 3),
               "slo": monitor.health(),
               "handoffs": router.handoffs_ok}
        if ctl is not None:
            out["scale_decisions"] = [
                {"t": r.t, "action": r.action, "replica": r.replica_id,
                 "role": r.role, "state": r.state, "reason": r.reason}
                for r in ctl.records]
            out["role_timeline"] = (
                [{"t": 0.0, "roles": {str(k): v
                                      for k, v in sorted(roles.items())}}]
                + [{"t": r.t, "replica": r.replica_id, "role": r.role}
                   for r in ctl.records
                   if r.action == "role_change" and r.state == "done"])
            out["replicas_final"] = len(router.replicas)
        for eng in engines:
            eng.mgr.check_conservation()
        return out

    hybrid = run(None, autoscale=False)
    disagg = run({0: ReplicaRole.PREFILL, 1: ReplicaRole.PREFILL,
                  2: ReplicaRole.DECODE}, autoscale=True)

    # ISSUE 19 acceptance gates, hard-asserted in the bench itself
    ups = [d for d in disagg["scale_decisions"]
           if d["action"] == "scale_up" and d["state"] == "done"]
    flips = [d for d in disagg["scale_decisions"]
             if d["action"] == "role_change" and d["state"] == "done"]
    assert ups, "autoscaler never scaled up under the 10x burst"
    assert flips, "autoscaler never rebalanced roles under the burst"
    assert disagg["slo"] == "ok", f"SLO breached: {disagg['slo']}"
    h_p95 = hybrid["phases"]["burst"]["itl_ms_p95"]
    d_p95 = disagg["phases"]["burst"]["itl_ms_p95"]
    assert d_p95 < h_p95, (
        f"disagg burst ITL p95 {d_p95}ms did not beat hybrid {h_p95}ms")

    return {
        "hybrid": hybrid,
        "disagg": disagg,
        "itl_burst_p95_ms_hybrid": h_p95,
        "itl_burst_p95_ms_disagg": d_p95,
        "itl_burst_p95_speedup": round(h_p95 / d_p95, 3) if d_p95 else 0.0,
    }


def main():
    import jax

    from paddle_tpu.models import llama as L
    from paddle_tpu.ops._common import is_tpu_platform

    on_tpu = is_tpu_platform(jax.devices()[0].platform)
    if on_tpu:
        cfg = L.llama_tiny(num_hidden_layers=8, hidden_size=1024)
        n_req, max_new, num_slots, chunk = 64, 32, 8, 8
        page_size, prefix_len, max_seq_len = 16, 64, 256
    else:
        cfg = L.llama_tiny(num_hidden_layers=2)
        n_req, max_new, num_slots, chunk = 24, 6, 2, 2
        page_size, prefix_len, max_seq_len = 4, 8, 32
    params = L.init_stacked_params(cfg, seed=0)

    # shared-prompt storm: 75% of requests share one system prefix
    rng = np.random.RandomState(0)
    shared = rng.randint(1, cfg.vocab_size, (prefix_len,)).astype(np.int32)
    prompts = []
    for i in range(n_req):
        tail = rng.randint(1, cfg.vocab_size,
                           (int(rng.randint(2, 5)),)).astype(np.int32)
        prompts.append(np.concatenate([shared, tail]) if i % 4 else tail)

    def fleet(n):
        return _build_fleet(n, cfg, max_new, num_slots, chunk, page_size,
                            max_seq_len, prefix_cache=True)

    from paddle_tpu.observability import get_registry

    # single-replica baseline: untimed warmup storms on the SAME router
    # (two passes: the first warms the prefix caches and router index,
    # the second follows the warm-index routing and compiles its
    # admission shapes — the measured storm then runs compile-free)
    router1 = fleet(1)
    _storm(router1, params, prompts)
    _storm(router1, params, prompts)
    t0 = time.perf_counter()
    h1 = _storm(router1, params, prompts)
    wall_1 = time.perf_counter() - t0
    ttft_1 = [h.ttft_ms for h in h1 if h.ttft_ms is not None]

    # 4-replica fleet, same warmup discipline; storm B measures routing
    # (affinity + TTFT), storm C on the SAME warm fleet kills replica 1
    # mid-flight and measures failover recovery
    router4 = fleet(4)
    _storm(router4, params, prompts)
    _storm(router4, params, prompts)
    t0 = time.perf_counter()
    h4 = _storm(router4, params, prompts)
    wall_4 = time.perf_counter() - t0
    ttft_4 = [h.ttft_ms for h in h4 if h.ttft_ms is not None]
    hk = _storm(router4, params, prompts, kill_replica=1)
    assert all(h.stream.finished for h in h4 + hk)
    failed_over = [h for h in hk if h.failovers > 0]
    recovery_ms = [(h.finish_t - h.failover_t) * 1e3 for h in failed_over
                   if h.failover_t is not None and h.finish_t is not None]

    # elastic mesh-resize recovery (ISSUE 14): mp=2 fleet, one chip dies
    resize = _resize_scenario(cfg, params, prompts, max_new, num_slots,
                              chunk, page_size, max_seq_len)

    # multi-host page migration + host loss (ISSUE 17): 2 wire-framed
    # hosts, drain-with-pages then a seeded host_die on the destination
    migration = _migration_scenario(prompts[:12], max_new, num_slots,
                                    chunk, page_size)

    # disaggregated prefill/decode + autoscaling under diurnal traffic
    # (ISSUE 19): gates hard-asserted inside the scenario
    diurnal = _diurnal_scenario(cfg, params, max_new, num_slots, chunk,
                                page_size, max_seq_len)

    from _telemetry import run_header
    out = {
        **run_header("router"),
        # sentinel contract: the judged series is the resize storm's
        # overall delivered throughput — kill, failover drain and
        # post-rejoin serving included (BENCH_r07 seeds it). A box
        # without the chips for mp=2 (bare run, no 8-device CPU shim)
        # degrades to the rebuild-in-place arc — a DIFFERENT topology
        # that must not be judged against the mp=2 series, so it gets
        # its own metric name (sentinel: no comparable history).
        "metric": f"router_resize_{'tpu' if on_tpu else 'cpu'}_smoke"
                  + ("" if resize["from_chips"] > 1 else "_mp1"),
        "unit": "tokens_per_s",
        "value": resize["tokens_per_s_overall"],
        "tokens_per_s": resize["tokens_per_s_overall"],
        "resize": resize,
        "migration": migration,
        "diurnal": diurnal,
        "platform": "tpu" if on_tpu else "cpu",
        "replicas": 4,
        "requests": n_req,
        "shared_prefix_tokens": prefix_len,
        "affinity_hit_rate": round(
            sum(h.routed_by_affinity for h in h4) / n_req, 4),
        "completed": sum(h.state == "done" for h in h4),
        "failovers": sum(h.failovers for h in hk),
        "failover_recovery_ms_p50": round(_percentile(recovery_ms, 50), 3),
        "ttft_ms_p50_fleet": round(_percentile(ttft_4, 50), 3),
        "ttft_ms_p50_single": round(_percentile(ttft_1, 50), 3),
        "ttft_p50_delta_vs_single": round(
            _percentile(ttft_4, 50) - _percentile(ttft_1, 50), 3),
        "wall_s_fleet": round(wall_4, 3),
        "wall_s_single": round(wall_1, 3),
    }
    # unified-telemetry snapshot (shared shape: benchmarks/_telemetry.py)
    from _telemetry import metrics_snapshot

    ms = metrics_snapshot()
    snap = get_registry().snapshot()
    ms["router_requests_total"] = snap.get("paddle_router_requests_total",
                                           {})
    ms["router_failovers_total"] = snap.get("paddle_router_failovers_total",
                                            0.0)
    out["metrics_snapshot"] = ms
    print(json.dumps(out))


if __name__ == "__main__":
    main()
