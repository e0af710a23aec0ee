"""Profile-guided fusion pass (ISSUE 13, ROADMAP item 1).

The three admission gates, tested end to end:

* **byte-identical** — fused-tail storms (prefix cache on/off, spec
  on/off, mid-decode admission) emit exactly the unfused engine's greedy
  tokens, and the fused optimizer megaregion commits bit-identical
  params/accumulators vs. the eager ``Optimizer.step()`` for every
  shipped optimizer family;
* **recompile-count-neutral** — fused programs compile exactly as often
  as their unfused twins across a length-diverse storm;
* **graceful degradation** — stale artifacts (symbols that no longer
  resolve in the ProjectIndex) and schema mismatches become structured
  ``fusion_skipped`` events (one deduped event per chain per process),
  never an exception.
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

def _engine(fused, prefix_cache=False, speculative=False, max_new=6,
            num_slots=2, chunk=3, **kw):
    cfg = L.llama_tiny(num_hidden_layers=2)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=max_new),
        num_slots=num_slots, page_size=4, max_seq_len=64, chunk=chunk,
        prefix_cache=prefix_cache, fused_tail=fused,
        speculative=speculative, **kw)
    return cfg, eng


def _prompts(cfg, lens, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, (int(n),)).astype(np.int32)
            for n in lens]


_STORM_LENS = (5, 12, 3, 9, 17, 2, 7, 30)


def _params(cfg):
    return L.init_stacked_params(cfg, seed=3)


def _artifact(chains, symbols=None, schema=1, kind="paddle_tpu.hot_chains"):
    return {"version": schema, "schema_version": schema, "kind": kind,
            "meta": {}, "workload": "test", "top_n": len(chains),
            "transitions": 0, "dropped_pairs": 0, "op_totals": {},
            "symbols": symbols or {},
            "chains": [{"ops": list(ops), "count": 5, "est_us": 100.0 - i}
                       for i, ops in enumerate(chains)]}


# ---------------------------------------------------------------------------
# the pass: artifact -> plan -> apply
# ---------------------------------------------------------------------------

def test_plan_maps_ranked_chains_to_regions():
    doc = _artifact([("cbe.plan_step", "cbe.unified_step",
                      "cbe.decode_tail"),
                     ("grad_clip", "optimizer_update"),
                     ("multiply", "add", "clip")])
    plan = F.FusionPass().plan(doc)
    names = [c.region.name for c in plan.candidates]
    assert names == ["decode_tail", "optimizer_chain"]
    assert plan.candidates[0].matched == ("cbe.unified_step",
                                          "cbe.decode_tail")
    # the eager math chain maps to no declared region: structured skip
    assert {tuple(s["chain"]): s["reason"] for s in plan.skipped} == {
        ("multiply", "add", "clip"): "no-region"}


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
    doc = _artifact([("cbe.unified_step", "cbe.decode_tail"),
                     ("optimizer_update", "optimizer_update")])
    plan = F.FusionPass().plan(doc)
    cfg, eng = _engine(False)
    p = Parameter(jnp.ones((4, 4), jnp.float32))
    opt = O.SGD(0.1, parameters=[p])
    installed = plan.apply(engine=eng, optimizer=opt)
    assert set(installed) == {"decode_tail", "optimizer_chain"}
    assert eng._fused_tail
    assert isinstance(opt._fused_step, F.FusedOptimizerStep)
    # idempotent + partial targets
    assert plan.apply(optimizer=opt)["optimizer_chain"] is opt._fused_step
    snap = get_registry().snapshot()
    assert snap.get("paddle_fusion_active", {})


def test_apply_on_rejecting_target_skips_never_raises():
    """The degradation contract covers installation: a target that
    REJECTS the fused tail (ValueError from ``enable_fused_tail``) —
    apply() turns that into a target-unsupported skip instead of
    propagating."""
    doc = _artifact([("cbe.unified_step", "cbe.decode_tail")])
    plan = F.FusionPass().plan(doc)

    class Rejecting:
        _fused_tail = False

        def enable_fused_tail(self):
            raise ValueError("this engine has no decode tail to fuse")

    target = Rejecting()
    installed = plan.apply(engine=target)
    assert installed == {}
    assert not target._fused_tail
    snap = get_registry().snapshot()
    fam = snap.get("paddle_fusion_skipped_total", {})
    assert any("target-unsupported" in k for k in fam)


def test_apply_idempotent_counts_install_once():
    """Re-applying over an already-installed region neither re-counts
    the admission nor re-emits fusion_applied (the admitted counter
    stays an install count)."""
    doc = _artifact([("cbe.unified_step", "cbe.decode_tail")])
    cfg, eng = _engine(False)

    def admitted():
        fam = get_registry().snapshot().get(
            "paddle_fusion_admitted_total", {})
        return sum(v for k, v in fam.items() if "decode_tail" in k)

    plan = F.FusionPass().plan(doc)
    plan.apply(engine=eng)
    once = admitted()
    plan.apply(engine=eng)
    F.FusionPass().plan(doc).apply(engine=eng)
    assert admitted() == once


def test_active_gauge_follows_install_target_liveness():
    """paddle_fusion_active reflects whether an installed target is
    still alive: dropping the fused engine and re-running the pass
    clears the gauge instead of reporting an active megaregion
    forever."""
    import gc
    doc = _artifact([("cbe.unified_step", "cbe.decode_tail")])
    plan = F.FusionPass().plan(doc)
    cfg, eng = _engine(False)
    plan.apply(engine=eng)

    def active():
        fam = get_registry().snapshot().get("paddle_fusion_active", {})
        return {k: v for k, v in fam.items() if "decode_tail" in k}

    assert all(v == 1 for v in active().values()) and active()
    del eng
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
    optimizer run, export the artifact, plan it, install both regions."""
    cfg, eng = _engine(False)
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
    plan = F.FusionPass().plan(doc)
    names = {c.region.name for c in plan.candidates}
    assert {"decode_tail", "optimizer_chain"} <= names
    cfg2, eng2 = _engine(False)
    installed = plan.apply(engine=eng2)
    assert "decode_tail" in installed and eng2._fused_tail


# ---------------------------------------------------------------------------
# decode tail: byte-identity + recompile neutrality
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefix_cache", [False, True])
def test_fused_tail_storm_byte_identical(prefix_cache):
    cfg, base = _engine(False, prefix_cache=prefix_cache)
    params = _params(cfg)
    prompts = _prompts(cfg, _STORM_LENS)
    if prefix_cache:
        prompts[3] = np.concatenate([prompts[1], prompts[2]])
        prompts[5] = prompts[1].copy()
    want = base.serve(params, prompts)
    cfg2, fused = _engine(True, prefix_cache=prefix_cache)
    assert fused.serve(params, prompts) == want


def test_fused_tail_recompile_neutral_across_storm():
    """The O(1)-recompile invariant survives fusion: across a
    length-diverse storm with mid-decode admissions both engines miss
    the unified-step cache exactly once."""
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = _params(cfg)
    counts = {}
    for fused in (False, True):
        before = recompiles.count("cbe.unified_step")
        eng = ContinuousBatchingEngine(
            cfg, GenerationConfig(max_new_tokens=5), num_slots=2,
            page_size=4, max_seq_len=64, chunk=3, fused_tail=fused)
        prompts = _prompts(cfg, _STORM_LENS)
        rids = [eng.submit(p) for p in prompts[:3]]
        done = {}
        step = 0
        while len(done) < len(prompts):
            eng.step(params)
            done.update(eng.collect())
            step += 1
            if step == 2:               # mid-decode trickle admission
                rids += [eng.submit(p) for p in prompts[3:]]
        counts[fused] = recompiles.count("cbe.unified_step") - before
    assert counts[True] == counts[False] == 1


def test_enable_fused_tail_mid_serve_stays_byte_identical():
    """Installing the region mid-flight rebuilds the program (a counted
    miss) and continues the exact token streams."""
    cfg, base = _engine(False, max_new=8, num_slots=2, chunk=2)
    params = _params(cfg)
    prompts = _prompts(cfg, (5, 9, 13, 7))
    want = base.serve(params, prompts)

    cfg2, eng = _engine(False, max_new=8, num_slots=2, chunk=2)
    rids = [eng.submit(p) for p in prompts]
    for _ in range(3):
        eng.step(params)
    eng.enable_fused_tail()
    done = dict(eng.collect())
    while len(done) < len(prompts):
        eng.step(params)
        done.update(eng.collect())
    assert [done[r] for r in rids] == want


def test_plan_fast_path_matches_generic_planner():
    """Steady-state all-decode rounds plan through the vectorized fast
    path — byte-equal packed arrays AND identical position mirrors."""
    import copy
    cfg, a = _engine(True, num_slots=4, chunk=5)
    cfg2, b = _engine(True, num_slots=4, chunk=5)
    for eng in (a, b):
        # synthetic steady state: slots 0 and 2 decoding, 1/3 idle
        eng._slot_rid[0], eng._slot_rid[2] = 11, 12
        eng._pos[0], eng._pos[2] = 7, 3
        eng._pend[0] = eng._pend[2] = None
    tt_fast, tr_fast, emit_f, ec_f, fed_f = a._plan_step_packed()
    plan, emit_g, ec_g, fed_g = b._plan_step()
    tt_gen, tr_gen = F.pack_plan(*plan)
    np.testing.assert_array_equal(tt_fast, tt_gen)
    np.testing.assert_array_equal(tr_fast, tr_gen)
    np.testing.assert_array_equal(emit_f, emit_g)
    assert ec_f == ec_g and fed_f == fed_g
    np.testing.assert_array_equal(a._pos, b._pos)
    # mixed round (one slot still prefilling): falls back to generic
    a._pend[0] = np.asarray([1, 2, 3], np.int32)
    b._pend[0] = np.asarray([1, 2, 3], np.int32)
    tt_fast, tr_fast, *_ = a._plan_step_packed()
    plan, *_ = b._plan_step()
    tt_gen, tr_gen = F.pack_plan(*plan)
    np.testing.assert_array_equal(tt_fast, tt_gen)
    np.testing.assert_array_equal(tr_fast, tr_gen)


def test_spec_composition_byte_identical():
    """fusion + speculation together stays byte-identical to both off
    (and to each alone) — the ISSUE's composition gate."""
    cfg, plain = _engine(False)
    params = _params(cfg)
    prompts = _prompts(cfg, _STORM_LENS)
    want = plain.serve(params, prompts)
    for fused, spec in ((True, False), (False, True), (True, True)):
        cfg2, eng = _engine(fused, speculative=spec)
        assert eng.serve(params, prompts) == want, (fused, spec)


def test_fused_spec_recompile_neutral():
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = _params(cfg)
    counts = {}
    for fused in (False, True):
        before = recompiles.count("cbe.spec_step")
        cfg2, eng = _engine(fused, speculative=True)
        eng.serve(params, _prompts(cfg, _STORM_LENS))
        counts[fused] = recompiles.count("cbe.spec_step") - before
    assert counts[True] == counts[False] == 1


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
    out = jax.jit(staged)(jnp.float32(np.inf), a, b)[0]
    assert np.array_equal(np.asarray(eager), np.asarray(out))


def test_pack_plan_round_trip():
    K, tb, R = 3, 6, 2
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 100, (K, tb)).astype(np.int32)
    uc = rng.rand(K, tb) > 0.5
    tr = rng.randint(-1, R, (K, tb)).astype(np.int32)
    pos = rng.randint(0, 50, (K, tb)).astype(np.int32)
    kvl = rng.randint(0, 50, (K, R)).astype(np.int32)
    li = rng.randint(0, tb, (K, R)).astype(np.int32)
    sm = rng.rand(K, R) > 0.5
    tt, trr = F.pack_plan(ids, uc, tr, pos, kvl, li, sm)
    assert tt.shape == (4, K, tb) and trr.shape == (3, K, R)
    np.testing.assert_array_equal(tt[0], ids)
    np.testing.assert_array_equal(tt[1].astype(bool), uc)
    np.testing.assert_array_equal(tt[2], tr)
    np.testing.assert_array_equal(tt[3], pos)
    np.testing.assert_array_equal(trr[0], kvl)
    np.testing.assert_array_equal(trr[1], li)
    np.testing.assert_array_equal(trr[2].astype(bool), sm)
