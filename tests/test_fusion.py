"""Profile-guided fusion pass (ISSUE 13, ROADMAP item 1).

The admission gates, tested end to end:

* **byte-identical** — the fused optimizer megaregion commits
  bit-identical params/accumulators vs. the eager ``Optimizer.step()``
  for every shipped optimizer family;
* **recompile-count-neutral** — the fused program compiles once per
  parameter set, never per step;
* **graceful degradation** — stale artifacts (symbols that no longer
  resolve in the ProjectIndex), schema mismatches and chains no region
  declares (the serving engine's old ``cbe.*`` taps among them) become
  structured ``fusion_skipped`` events (one deduped event per chain per
  process), never an exception.
"""

import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Parameter
from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.jit import fusion as F
from paddle_tpu.models import llama as L
from paddle_tpu.observability import get_registry
from paddle_tpu.observability.events import configure_event_log
from paddle_tpu.observability.profiling import chain_profiler
from paddle_tpu.observability.runtime import recompiles, telemetry
from paddle_tpu.optimizer import clip as C
from paddle_tpu.optimizer import optimizer as O


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _engine(max_new=6, num_slots=2, chunk=3):
    cfg = L.llama_tiny(num_hidden_layers=2)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=max_new),
        num_slots=num_slots, page_size=4, max_seq_len=64, chunk=chunk)
    return cfg, eng


def _prompts(cfg, lens, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, (int(n),)).astype(np.int32)
            for n in lens]


def _params(cfg):
    return L.init_stacked_params(cfg, seed=3)


def _artifact(chains, symbols=None, schema=1, kind="paddle_tpu.hot_chains"):
    return {"version": schema, "schema_version": schema, "kind": kind,
            "meta": {}, "workload": "test", "top_n": len(chains),
            "transitions": 0, "dropped_pairs": 0, "op_totals": {},
            "symbols": symbols or {},
            "chains": [{"ops": list(ops), "count": 5, "est_us": 100.0 - i}
                       for i, ops in enumerate(chains)]}


def _optimizer():
    return O.SGD(0.1, parameters=[Parameter(jnp.ones((4, 4), jnp.float32))])


#: a chain of the serving engine's phase names: no region declares one
_ENGINE_CHAIN = ("cbe.plan_step", "cbe.unified_step", "cbe.decode_tail")


# ---------------------------------------------------------------------------
# the pass: artifact -> plan -> apply
# ---------------------------------------------------------------------------

def test_plan_maps_ranked_chains_to_regions():
    doc = _artifact([_ENGINE_CHAIN,
                     ("grad_clip", "optimizer_update"),
                     ("multiply", "add", "clip")])
    plan = F.FusionPass().plan(doc)
    names = [c.region.name for c in plan.candidates]
    assert names == ["optimizer_chain"]
    assert plan.candidates[0].matched == ("grad_clip", "optimizer_update")
    # the engine's chain and the eager math chain map to no declared
    # region: structured skips
    assert {tuple(s["chain"]): s["reason"] for s in plan.skipped} == {
        _ENGINE_CHAIN: "no-region", ("multiply", "add", "clip"): "no-region"}


def test_stale_artifact_skips_symbol_missing_never_raises(tmp_path):
    # the artifact CLAIMS a symbol for an op that no longer resolves in
    # the current tree (capture predates a refactor)
    doc = _artifact([("cbe.unified_step_v0", "cbe.decode_tail_v0")],
                    symbols={"cbe.unified_step_v0": "paddle_tpu.old.sym",
                             "cbe.decode_tail_v0": None})
    plan = F.FusionPass().plan(doc)
    assert not plan.candidates
    assert plan.skipped[0]["reason"] == "symbol-missing"
    assert plan.skipped[0]["missing"] == ["cbe.unified_step_v0"]
    # region taps renamed out of the tree: also symbol-missing
    doc2 = _artifact([("grad_clip", "optimizer_update")])
    plan2 = F.FusionPass(resolver=lambda: {}).plan(doc2)
    assert not plan2.candidates
    assert plan2.skipped[0]["reason"] == "symbol-missing"


def test_schema_mismatch_skips_structured():
    for bad in (_artifact([], schema=99),
                _artifact([], kind="other.artifact"),
                ["not", "a", "dict"], None, {}):
        plan = F.FusionPass().plan(bad)
        assert not plan.candidates
        assert plan.skipped == [{"chain": ("<artifact>",),
                                 "reason": "schema-mismatch"}]


def test_fusion_skipped_event_deduped_per_chain(tmp_path):
    configure_event_log(str(tmp_path / "events.jsonl"))
    try:
        doc = _artifact([("mystery_op_a", "mystery_op_b")])
        F.FusionPass().plan(doc)
        F.FusionPass().plan(doc)      # second pass: no second event
        lines = [json.loads(l) for l in
                 (tmp_path / "events.jsonl").read_text().splitlines()]
        skips = [e for e in lines if e["kind"] == "fusion_skipped"
                 and e["chain"] == "mystery_op_a->mystery_op_b"]
        assert len(skips) == 1
        assert skips[0]["reason"] == "no-region"
    finally:
        configure_event_log(None)
    # ... while the counter counts every occurrence
    snap = get_registry().snapshot()
    fam = snap.get("paddle_fusion_skipped_total", {})
    assert any("no-region" in k for k in fam)


def test_apply_installs_on_duck_typed_targets():
    """The region installs on whatever it is handed as the optimizer: a
    real one, and an object that merely takes the attribute."""
    doc = _artifact([_ENGINE_CHAIN,
                     ("optimizer_update", "optimizer_update")])
    plan = F.FusionPass().plan(doc)
    assert [s["reason"] for s in plan.skipped] == ["no-region"]
    opt = _optimizer()
    installed = plan.apply(optimizer=opt)
    assert set(installed) == {"optimizer_chain"}
    assert isinstance(opt._fused_step, F.FusedOptimizerStep)
    # idempotent
    assert plan.apply(opt)["optimizer_chain"] is opt._fused_step
    duck = type("Duck", (), {})()
    assert plan.apply(duck)["optimizer_chain"] is duck._fused_step
    snap = get_registry().snapshot()
    assert snap.get("paddle_fusion_active", {})


def test_apply_on_rejecting_target_skips_never_raises():
    """The degradation contract covers installation: an optimizer that
    REJECTS the fused step (ValueError as it is set) — apply() turns that
    into a target-unsupported skip instead of propagating."""
    doc = _artifact([("grad_clip", "optimizer_update")])
    plan = F.FusionPass().plan(doc)

    class Rejecting:
        def __setattr__(self, name, value):
            raise ValueError("this optimizer takes no fused step")

    target = Rejecting()
    installed = plan.apply(optimizer=target)
    assert installed == {}
    assert not hasattr(target, "_fused_step")
    snap = get_registry().snapshot()
    fam = snap.get("paddle_fusion_skipped_total", {})
    assert any("target-unsupported" in k for k in fam)


def test_apply_idempotent_counts_install_once():
    """Re-applying over an already-installed region neither re-counts
    the admission nor re-emits fusion_applied (the admitted counter
    stays an install count)."""
    doc = _artifact([("grad_clip", "optimizer_update")])
    opt = _optimizer()

    def admitted():
        fam = get_registry().snapshot().get(
            "paddle_fusion_admitted_total", {})
        return sum(v for k, v in fam.items() if "optimizer_chain" in k)

    before = admitted()
    plan = F.FusionPass().plan(doc)
    plan.apply(optimizer=opt)
    once = admitted()
    assert once == before + 1
    plan.apply(optimizer=opt)
    F.FusionPass().plan(doc).apply(optimizer=opt)
    assert admitted() == once


def test_active_gauge_follows_install_target_liveness():
    """paddle_fusion_active reflects whether an installed target is
    still alive: dropping the fused optimizer and re-running the pass
    clears the gauge instead of reporting an active megaregion
    forever."""
    import gc
    doc = _artifact([("grad_clip", "optimizer_update")])
    plan = F.FusionPass().plan(doc)
    opt = _optimizer()
    plan.apply(optimizer=opt)

    def active():
        fam = get_registry().snapshot().get("paddle_fusion_active", {})
        return {k: v for k, v in fam.items() if "optimizer_chain" in k}

    assert all(v == 1 for v in active().values()) and active()
    del opt
    gc.collect()
    F.FusionPass().plan(doc)        # any pass run refreshes liveness
    assert all(v == 0 for v in active().values())


def test_fused_optimizer_rebuilds_on_hyperparameter_mutation():
    """Mutating a baked-in scalar (the grad-clip bound, weight decay)
    after install rebuilds the program — fused stays bit-identical to
    an eager twin seeing the same mutation mid-run."""
    def factory(ps):
        return O.AdamW(0.01, parameters=ps, weight_decay=0.05,
                       grad_clip=C.ClipGradByGlobalNorm(1.0))

    def run(fused):
        ps = _fresh_params()
        opt = factory(ps)
        if fused:
            F.install_optimizer_fusion(opt)
        for k, grads in enumerate(_grad_seq(4)):
            if k == 2:
                opt._grad_clip.clip_norm = 0.25
                opt._weight_decay = 0.2
            for p, g in zip(ps, grads):
                p._grad_value = jnp.asarray(g)
            opt.step()
        return ps

    pe = run(False)
    pf = run(True)
    for i, (a, b) in enumerate(zip(pe, pf)):
        assert np.array_equal(np.asarray(a._value),
                              np.asarray(b._value)), f"param {i}"


def test_end_to_end_profile_plan_apply():
    """The whole loop: arm the profiler over a real storm + a real eager
    optimizer run, export the artifact, plan it, install the region. The
    engine's compiled dispatches leave no chain of their own."""
    cfg, eng = _engine()
    params = _params(cfg)
    telemetry.enable()
    chain_profiler.reset()
    chain_profiler.arm()
    try:
        eng.serve(params, _prompts(cfg, (5, 9, 13, 7)))
        ps = [Parameter(jnp.ones((8, 4), jnp.float32) * (i + 1))
              for i in range(3)]
        opt = O.AdamW(0.01, parameters=ps,
                      grad_clip=C.ClipGradByGlobalNorm(1.0))
        for _ in range(3):
            for p in ps:
                p._grad_value = jnp.ones((8, 4), jnp.float32)
            opt.step()
    finally:
        chain_profiler.disarm()
    doc = chain_profiler.profile(top_n=8, workload="e2e")
    assert not [op for ch in doc["chains"] for op in ch["ops"]
                if op.startswith("cbe.")]
    plan = F.FusionPass().plan(doc)
    assert {c.region.name for c in plan.candidates} == {"optimizer_chain"}
    opt2 = _optimizer()
    installed = plan.apply(optimizer=opt2)
    assert installed["optimizer_chain"] is opt2._fused_step


# ---------------------------------------------------------------------------
# optimizer chain: bit-exact megaregion across every optimizer family
# ---------------------------------------------------------------------------

_SHAPES = ((32, 16), (16,), (64, 8), (24,), (4, 4, 3))


def _fresh_params(mults=True, dtype=np.float32):
    rng = np.random.RandomState(42)
    ps = []
    for i, s in enumerate(_SHAPES):
        p = Parameter(jnp.asarray(rng.randn(*s).astype(dtype)))
        p.name = f"p_{i}"
        if mults and i % 2:
            p.optimize_attr["learning_rate"] = 0.5
        ps.append(p)
    return ps


def _grad_seq(steps, dtype=np.float32):
    return [[np.random.RandomState(100 + k + i).randn(*s).astype(dtype)
             for i, s in enumerate(_SHAPES)]
            for k in range(steps)]


def _run(make_opt, fused, steps=4):
    ps = _fresh_params()
    opt = make_opt(ps)
    if fused:
        F.install_optimizer_fusion(opt)
    for grads in _grad_seq(steps):
        for p, g in zip(ps, grads):
            p._grad_value = jnp.asarray(g)
        opt.step()
    return ps, opt


def _assert_bitwise(make_opt, steps=4):
    pe, oe = _run(make_opt, fused=False, steps=steps)
    pf, of = _run(make_opt, fused=True, steps=steps)
    assert of._fused_step.steps_fused == steps
    for i, (a, b) in enumerate(zip(pe, pf)):
        assert np.array_equal(np.asarray(a._value), np.asarray(b._value)), \
            f"param {i} drifted"
        se = oe._accumulators.get(id(a), {})
        sf = of._accumulators.get(id(b), {})
        assert se.keys() == sf.keys()
        for k in se:
            assert np.array_equal(np.asarray(se[k]), np.asarray(sf[k])), \
                f"state {i}.{k} drifted"


_CLIP = lambda: C.ClipGradByGlobalNorm(1.0)


@pytest.mark.parametrize("name,factory", [
    ("sgd", lambda ps: O.SGD(0.01, parameters=ps, weight_decay=0.01)),
    ("momentum_nesterov",
     lambda ps: O.Momentum(0.01, 0.9, parameters=ps, use_nesterov=True,
                           weight_decay=0.01, grad_clip=_CLIP())),
    ("adam", lambda ps: O.Adam(0.003, parameters=ps, weight_decay=0.01)),
    ("adamw_clip_decayfn",
     lambda ps: O.AdamW(0.01, parameters=ps, weight_decay=0.05,
                        apply_decay_param_fun=lambda n: not n.endswith("2"),
                        grad_clip=_CLIP())),
    ("adamax", lambda ps: O.Adamax(0.01, parameters=ps, weight_decay=0.01)),
    ("lamb", lambda ps: O.Lamb(0.01, parameters=ps)),
    ("rmsprop_centered",
     lambda ps: O.RMSProp(0.01, centered=True, momentum=0.9,
                          parameters=ps, weight_decay=0.01)),
    ("adagrad", lambda ps: O.Adagrad(0.01, parameters=ps,
                                     weight_decay=0.01)),
    ("clip_by_value",
     lambda ps: O.SGD(0.01, parameters=ps,
                      grad_clip=C.ClipGradByValue(0.1))),
    ("clip_by_norm",
     lambda ps: O.Momentum(0.01, 0.9, parameters=ps,
                           grad_clip=C.ClipGradByNorm(0.5))),
])
def test_fused_optimizer_bitwise_identical(name, factory):
    _assert_bitwise(factory)


def test_fused_optimizer_with_lr_scheduler_bitwise():
    from paddle_tpu.optimizer.lr import StepDecay

    def factory(ps):
        return O.Adam(StepDecay(0.01, step_size=2, gamma=0.5),
                      parameters=ps)

    pe, oe = _run(factory, fused=False, steps=5)
    pf, of = _run(factory, fused=True, steps=5)
    for a, b in zip(pe, pf):
        assert np.array_equal(np.asarray(a._value), np.asarray(b._value))


def test_fused_optimizer_multi_precision_bitwise():
    def factory(ps):
        return O.AdamW(0.01, parameters=ps, weight_decay=0.05,
                       multi_precision=True)

    rng = np.random.RandomState(0)

    def run(fused):
        ps = []
        for i, s in enumerate(_SHAPES):
            arr = rng.randn(*s).astype(np.float32)
            p = Parameter(jnp.asarray(arr).astype(jnp.bfloat16))
            p.name = f"mp_{i}"
            ps.append(p)
        opt = factory(ps)
        if fused:
            F.install_optimizer_fusion(opt)
        for grads in _grad_seq(3):
            for p, g in zip(ps, grads):
                p._grad_value = jnp.asarray(g).astype(jnp.bfloat16)
            opt.step()
        return ps, opt

    rng = np.random.RandomState(0)
    pe, oe = run(False)
    rng = np.random.RandomState(0)
    pf, of = run(True)
    for i, (a, b) in enumerate(zip(pe, pf)):
        assert np.array_equal(
            np.asarray(a._value, np.float32),
            np.asarray(b._value, np.float32)), f"bf16 param {i}"
        se, sf = oe._accumulators[id(a)], of._accumulators[id(b)]
        assert np.array_equal(np.asarray(se["master"]),
                              np.asarray(sf["master"]))


def test_fused_optimizer_compiles_once_and_reuses():
    before = recompiles.count("fusion.optimizer_chain")
    pf, of = _run(lambda ps: O.Adam(0.003, parameters=ps), fused=True,
                  steps=6)
    assert recompiles.count("fusion.optimizer_chain") - before == 1


def test_fused_optimizer_grad_subset_rebuilds_correctly():
    """A step where only some params carry grads matches eager (the
    fused program rebuilds for the new signature, a counted miss)."""
    def factory(ps):
        return O.Adam(0.01, parameters=ps, weight_decay=0.01)

    def run(fused):
        ps = _fresh_params()
        opt = factory(ps)
        if fused:
            F.install_optimizer_fusion(opt)
        grads = _grad_seq(2)
        for p, g in zip(ps, grads[0]):
            p._grad_value = jnp.asarray(g)
        opt.step()
        # second step: params 0/2/4 only
        opt.clear_grad()
        for i in (0, 2, 4):
            ps[i]._grad_value = jnp.asarray(grads[1][i])
        opt.step()
        return ps

    pe = run(False)
    pf = run(True)
    for i, (a, b) in enumerate(zip(pe, pf)):
        assert np.array_equal(np.asarray(a._value), np.asarray(b._value)), i


def test_fused_optimizer_state_dict_round_trip():
    """Resume-from-checkpoint composes with fusion: accumulators load
    into a fresh fused optimizer and training continues bit-exact."""
    def factory(ps):
        return O.Adam(0.01, parameters=ps)

    pe, oe = _run(factory, fused=False, steps=2)
    state = oe.state_dict()

    # eager continuation
    for grads in _grad_seq(2):
        for p, g in zip(pe, grads):
            p._grad_value = jnp.asarray(g)
        oe.step()

    # fused continuation from the checkpoint
    pf, of_ = _run(factory, fused=False, steps=2)
    opt2 = factory(pf)
    opt2.set_state_dict(state)
    F.install_optimizer_fusion(opt2)
    for grads in _grad_seq(2):
        for p, g in zip(pf, grads):
            p._grad_value = jnp.asarray(g)
        opt2.step()
    for a, b in zip(pe, pf):
        assert np.array_equal(np.asarray(a._value), np.asarray(b._value))


# ---------------------------------------------------------------------------
# staging mechanics
# ---------------------------------------------------------------------------

def test_stage_eager_matches_eager_bits_on_fma_hazard_chain():
    """The contraction-fence mechanism itself: a mul+add / chained-div
    graph staged through stage_eager reproduces the eager per-op bits
    (plain jit of the same chain is where FMA contraction bites)."""
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(64, 32).astype(np.float32))
    b = jnp.asarray(rng.randn(64, 32).astype(np.float32))

    def chain(x, y):
        m = 0.9 * x + (1 - 0.9) * y
        v = 0.999 * jnp.abs(x) + (1 - 0.999) * (y * y)
        return (m / 0.271) / (jnp.sqrt(v / 0.0009) + 1e-8)

    eager = chain(a, b)
    staged, _ = F.stage_eager(chain, a, b)
    out = staged(jnp.float32(np.inf), a, b)[0]
    assert np.array_equal(np.asarray(eager), np.asarray(out))
