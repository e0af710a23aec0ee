#!/usr/bin/env python
"""Fusion-pass smoke for scripts/verify.sh (ISSUE 13 satellite).

One self-contained CPU check of the artifact→pass→install loop:

1. arm the chain profiler over a real (tiny) serving storm + an eager
   optimizer run, export the ``paddle_tpu.hot_chains`` artifact;
2. feed it to :class:`paddle_tpu.jit.fusion.FusionPass` and assert BOTH
   shipped regions fuse (decode_tail + optimizer_chain), install them,
   and spot-check byte-identity of a fused serve;
3. degrade-gracefully paths: a synthetically stale artifact (ops whose
   claimed symbols no longer resolve) produces structured
   ``symbol-missing`` skips, a schema-mismatched artifact produces a
   ``schema-mismatch`` skip — and neither ever raises.

Exit 0 and ONE JSON line on success; nonzero + a message otherwise.
"""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.tensor import Parameter
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.jit.fusion import FusionPass
    from paddle_tpu.models import llama as L
    from paddle_tpu.observability.profiling import chain_profiler
    from paddle_tpu.observability.runtime import telemetry
    from paddle_tpu.optimizer.clip import ClipGradByGlobalNorm
    from paddle_tpu.optimizer.optimizer import AdamW

    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=3)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 13, 7, 17, 3)]

    def engine(fused=False):
        return ContinuousBatchingEngine(
            cfg, GenerationConfig(max_new_tokens=8), num_slots=2,
            page_size=4, max_seq_len=64, chunk=3, fused_tail=fused)

    # 1. profile the CPU smoke ------------------------------------------------
    telemetry.enable()
    chain_profiler.reset()
    chain_profiler.arm()
    try:
        want = engine().serve(params, prompts)
        ps = [Parameter(jnp.asarray(rng.randn(16, 8).astype(np.float32)))
              for _ in range(4)]
        opt = AdamW(0.01, parameters=ps,
                    grad_clip=ClipGradByGlobalNorm(1.0))
        for _ in range(3):
            for p in ps:
                p._grad_value = jnp.asarray(
                    rng.randn(16, 8).astype(np.float32))
            opt.step()
    finally:
        chain_profiler.disarm()
    path = os.path.join(tempfile.mkdtemp(prefix="fusion_smoke_"),
                        "hot_chains.json")
    chain_profiler.export(path=path, top_n=8, workload="verify_smoke")

    # 2. the pass fuses both regions -----------------------------------------
    artifact = FusionPass.load(path)
    plan = FusionPass().plan(artifact)
    fused_regions = {c.region.name for c in plan.candidates}
    assert "decode_tail" in fused_regions, (fused_regions, plan.skipped)
    assert "optimizer_chain" in fused_regions, (fused_regions,
                                                plan.skipped)
    eng2 = engine()
    installed = plan.apply(engine=eng2, optimizer=opt)
    assert set(installed) == {"decode_tail", "optimizer_chain"}
    assert eng2.serve(params, prompts) == want, \
        "fused serve diverged from unfused"

    # 3. degraded inputs become structured skips, never exceptions -----------
    stale = json.loads(json.dumps(artifact))
    stale["chains"] = [{"ops": [op + "_renamed" for op in ch["ops"]],
                        "count": ch["count"], "est_us": ch["est_us"]}
                       for ch in stale["chains"]]
    stale["symbols"] = {op + "_renamed": "paddle_tpu.gone.symbol"
                        for ch in artifact["chains"]
                        for op in ch["ops"]}
    stale_plan = FusionPass().plan(stale)
    assert not stale_plan.candidates
    assert stale_plan.skipped and all(
        s["reason"] == "symbol-missing" for s in stale_plan.skipped), \
        stale_plan.skipped

    mismatched = dict(artifact)
    mismatched["schema_version"] = mismatched["version"] = 999
    bad_plan = FusionPass().plan(mismatched)
    assert not bad_plan.candidates
    assert bad_plan.skipped[0]["reason"] == "schema-mismatch"

    print(json.dumps({
        "fusion_smoke": "ok",
        "artifact": path,
        "chains": len(artifact["chains"]),
        "fused_regions": sorted(fused_regions),
        "stale_skips": len(stale_plan.skipped),
        "schema_skips": len(bad_plan.skipped),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
