#!/usr/bin/env python
"""Fusion-pass smoke for scripts/verify.sh (ISSUE 13 satellite).

One self-contained CPU check of the artifact→pass→install loop:

1. arm the chain profiler over an eager optimizer run, export the
   ``paddle_tpu.hot_chains`` artifact;
2. feed it to :class:`paddle_tpu.jit.fusion.FusionPass`, assert the
   shipped region (optimizer_chain) fuses, install it, and spot-check
   that the fused steps commit the eager steps' bits;
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
    from paddle_tpu.jit.fusion import FusionPass
    from paddle_tpu.observability.profiling import chain_profiler
    from paddle_tpu.observability.runtime import telemetry
    from paddle_tpu.optimizer.clip import ClipGradByGlobalNorm
    from paddle_tpu.optimizer.optimizer import AdamW

    rng = np.random.RandomState(1)
    grads = [[jnp.asarray(rng.randn(16, 8).astype(np.float32))
              for _ in range(4)] for _ in range(3)]

    def optimizer():
        init = np.random.RandomState(2)
        ps = [Parameter(jnp.asarray(init.randn(16, 8).astype(np.float32)))
              for _ in range(4)]
        return ps, AdamW(0.01, parameters=ps,
                         grad_clip=ClipGradByGlobalNorm(1.0))

    def train(ps, opt):
        for step in grads:
            for p, g in zip(ps, step):
                p._grad_value = g
            opt.step()
        return [np.asarray(p._value) for p in ps]

    # 1. profile the CPU smoke ------------------------------------------------
    telemetry.enable()
    chain_profiler.reset()
    chain_profiler.arm()
    try:
        want = train(*optimizer())
    finally:
        chain_profiler.disarm()
    path = os.path.join(tempfile.mkdtemp(prefix="fusion_smoke_"),
                        "hot_chains.json")
    chain_profiler.export(path=path, top_n=8, workload="verify_smoke")

    # 2. the pass fuses the region -------------------------------------------
    artifact = FusionPass.load(path)
    plan = FusionPass().plan(artifact)
    fused_regions = {c.region.name for c in plan.candidates}
    assert fused_regions == {"optimizer_chain"}, (fused_regions,
                                                   plan.skipped)
    ps, opt = optimizer()
    installed = plan.apply(optimizer=opt)
    assert set(installed) == {"optimizer_chain"}
    got = train(ps, opt)
    assert installed["optimizer_chain"].steps_fused == len(grads)
    assert all(np.array_equal(a, b) for a, b in zip(got, want)), \
        "fused optimizer steps diverged from eager"

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
