"""Distribution-faithful decoding (ISSUE 16): the in-program sampling
epilogue, lossless rejection-sampling speculation, and grammar-
constrained decoding.

The acceptance bar: greedy stays byte-identical to the argmax-only
epilogue; a seeded sampled request replays its exact stream across
engine rebuilds, speculation on/off, the fused tail, TP sharding, and
router failovers; speculation under sampling is DISTRIBUTION-identical
to non-speculative sampling (the rejection-sampling verifier's whole
point); constrained rows emit only grammar-legal tokens; and a mixed
greedy/sampled/constrained storm still honours the unified step's
O(1)-recompile contract — per-request knobs are program INPUTS, never
cache keys."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _oracle import assert_greedy
from paddle_tpu.inference import sampling as S
from paddle_tpu.inference.constrain import (GrammarArena, compile_regex,
                                            json_regex, mask_logits)
from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.inference.sampling import SamplerConfig
from paddle_tpu.models import llama as L
from paddle_tpu.observability import get_registry
from paddle_tpu.observability.runtime import recompiles
from paddle_tpu.parallel.mesh import serving_mesh

CFG = L.llama_tiny(num_hidden_layers=2)
PARAMS = L.init_stacked_params(CFG, seed=0)


def _prompts(n=4, lens=(4, 12), seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, CFG.vocab_size,
                        (int(rng.randint(*lens)),)).astype(np.int32)
            for _ in range(n)]


def _engine(max_new=8, num_slots=2, mp=1, **kw):
    mesh = serving_mesh(mp) if mp > 1 else None
    return ContinuousBatchingEngine(
        CFG, GenerationConfig(max_new_tokens=max_new),
        num_slots=num_slots, page_size=16, max_seq_len=64, chunk=2,
        mesh=mesh, **kw)


def _run_each(eng, prompts, subs):
    """Serve ``prompts``, each with its own submit arguments."""
    rids = [eng.submit(p, **s) for p, s in zip(prompts, subs)]
    out, steps = {}, 0
    while len(out) < len(prompts):
        eng.step(PARAMS)
        out.update(eng.collect())
        steps += 1
        assert steps < 3000
    return [out[r] for r in rids]


def _run(eng, prompts, **sub):
    return _run_each(eng, prompts, [sub] * len(prompts))


def _abc_vocab():
    return ["<eos>"] + list("abcde") + [f"tok{i}"
                                        for i in range(6, CFG.vocab_size)]


def _json_vocab():
    toks = ["<eos>"] + list('{}[]:, ') + ['"', '\\']
    toks += list("abcdefghijklmnopqrstuvwxyz0123456789+-.eE")
    while len(toks) < CFG.vocab_size:
        toks.append(f"<junk{len(toks)}>")
    return toks


@pytest.fixture(scope="module")
def abc_grammar():
    return compile_regex("(ab|cd)*e", _abc_vocab(), eos_token_id=0)


@pytest.fixture(scope="module")
def json_grammar_dfa():
    return compile_regex(json_regex(max_depth=1), _json_vocab(),
                         eos_token_id=0)


def _assert_legal_stream(gram, toks, prefix=()):
    st = gram.start
    for tok in list(prefix) + list(toks):
        assert gram.legal(st, tok), (toks, tok, st)
        st = gram.advance(st, tok)
    return st


# ---------------------------------------------------------------------------
# SamplerConfig + process_logits units
# ---------------------------------------------------------------------------

def test_sampler_config_resolved():
    c = SamplerConfig(temperature=0.7, top_k=5, top_p=0.9)
    assert c.seed is None
    r = c.resolved(1234)
    assert r.seed == 1234 and r.temperature == 0.7
    # an explicit seed wins over the default
    assert SamplerConfig(seed=9).resolved(1234).seed == 9


@pytest.mark.parametrize("temp,top_k,top_p", [
    (1.0, 0, 1.0), (0.7, 0, 1.0), (1.3, 5, 1.0), (1.0, 0, 0.8),
    (0.9, 7, 0.6), (1.0, 1, 1.0),
])
def test_process_logits_matches_scalar_filter_chain(temp, top_k, top_p):
    """Per-row ``process_logits`` is bit-exact with the plain filter chain
    under ONE scalar (temperature, top-k, top-p) for the batch (same
    kth-value tie semantics, same smallest-set top-p cutoff on the
    post-top-k logits)."""
    rng = np.random.RandomState(0)
    lg = rng.randn(6, 32).astype(np.float32)
    lg[2, :16] = lg[2, 16:]                       # planted ties
    R = lg.shape[0]

    # the chain with scalar parameters, written plainly
    ref = jnp.asarray(lg) / jnp.maximum(temp, 1e-6)
    if top_k > 0:
        kth = jnp.sort(ref, axis=-1)[..., -top_k][..., None]
        ref = jnp.where(ref < kth, -jnp.inf, ref)
    if top_p < 1.0:
        srt = jnp.sort(ref, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cut_i = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cut = jnp.take_along_axis(srt, cut_i, axis=-1)
        ref = jnp.where(ref < cut, -jnp.inf, ref)

    got = S.process_logits(
        jnp.asarray(lg),
        jnp.full((R,), temp, jnp.float32),
        jnp.full((R,), top_k, jnp.int32),
        jnp.full((R,), top_p, jnp.float32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_row_state_defaults_are_greedy():
    """The row state is host numpy mirrors written in place; ``set_row``
    says whether a value changed (what the engine's dirty mark reads)."""
    samp = S.init_row_state(3)
    assert all(isinstance(a, np.ndarray) for a in samp)
    assert not S.set_row(samp, 1, None)           # greedy holds greedy
    assert S.set_row(samp, 1, SamplerConfig(temperature=0.5, seed=7))
    assert (int(samp[0][1]), float(samp[1][1])) == (7, 0.5)
    assert not S.set_row(samp, 1, SamplerConfig(temperature=0.5, seed=7))
    assert S.set_row(samp, 1, None)               # slot reuse resets
    assert float(samp[1][1]) == 0.0               # temperature 0 = argmax
    assert [a.tolist() for a in samp] == \
        [a.tolist() for a in S.init_row_state(3)]


# ---------------------------------------------------------------------------
# grammar compilation + arena units
# ---------------------------------------------------------------------------

def test_token_dfa_walk_and_eos(abc_grammar):
    g = abc_grammar
    # token ids: 1=a 2=b 3=c 4=d 5=e, 0=<eos>
    st = _assert_legal_stream(g, [1, 2, 3, 4, 5])
    assert bool(g.accepting[st])
    assert g.legal(st, 0)                         # EOS only once accepted
    assert not g.legal(g.start, 0)
    assert not g.legal(g.start, 2)                # 'b' cannot start
    assert g.advance(g.start, 2) == -1
    assert set(g.allowed_tokens(g.start)) == {1, 3, 5}


def test_compile_regex_rejects_stuck_grammar():
    # 'ab' is expressible but 'b' is not in this vocab: after 'a' the
    # automaton has no legal continuation and no legal EOS
    vocab = ["<eos>", "a", "c"] + ["x"] * 29
    with pytest.raises(ValueError, match="stuck"):
        compile_regex("ab", vocab, eos_token_id=0)


def test_grammar_arena_register_dedupe_capacity(abc_grammar):
    g = abc_grammar
    arena = GrammarArena(CFG.vocab_size,
                         capacity_states=g.n_states + 2)
    off = arena.register(g)
    assert arena.register(g) == off               # same fingerprint
    assert arena.used == g.n_states
    other = compile_regex("(ab)*e", _abc_vocab(), eos_token_id=0)
    with pytest.raises(ValueError, match="grammar_states"):
        arena.register(other)
    with pytest.raises(ValueError, match="vocab"):
        GrammarArena(16).register(g)


def test_mask_logits_is_noop_for_unconstrained_rows(abc_grammar):
    arena = GrammarArena(CFG.vocab_size, capacity_states=8)
    arena.register(abc_grammar)
    lg = jnp.asarray(np.random.RandomState(0)
                     .randn(2, CFG.vocab_size).astype(np.float32))
    gstate = jnp.asarray([-1, 0], jnp.int32)
    out = np.asarray(mask_logits(lg, gstate, arena.device_table()))
    np.testing.assert_array_equal(out[0], np.asarray(lg[0]))  # untouched
    legal = set(abc_grammar.allowed_tokens(0))
    assert all((t in legal) == np.isfinite(out[1][t])
               for t in range(CFG.vocab_size))


# ---------------------------------------------------------------------------
# rejection sampling: lossless (distribution-identical) speculation
# ---------------------------------------------------------------------------

def test_rejection_sampling_distribution_identity():
    """The verifier's first emitted token — accepted draft or residual
    resample — marginally matches the target softmax exactly; the non-
    speculative epilogue matches the same target. Chi-square-free: the
    PRNG is deterministic given seeds, so the empirical deviation bound
    is a fixed number, not a flaky tail event."""
    R, V, k = 4000, 8, 1
    L_row = jnp.asarray([2.0, 1.0, 0.5, 0.0, -0.5, -1.0, -1.5, -2.0])
    target = np.asarray(jax.nn.softmax(L_row))
    samp = (jnp.arange(R, dtype=jnp.uint32),
            jnp.ones((R,), jnp.float32),
            jnp.zeros((R,), jnp.int32),
            jnp.ones((R,), jnp.float32))
    gstate = jnp.full((R,), -1, jnp.int32)
    gtable = GrammarArena(V, 1).device_table()
    pos = jnp.zeros((R,), jnp.int32)

    # point-mass drafter proposing the MOST probable token: acceptance
    # is then exactly p_target(draft), and rejection must resample the
    # residual — the regime where a naive greedy-match verifier skews
    drafts = jnp.zeros((R, k), jnp.int32)
    toks, acc, _ = S.spec_sample_rows(
        jnp.broadcast_to(L_row, (R, k + 1, V)), drafts,
        jnp.ones((R,), jnp.int32), pos, samp, gstate, gtable)
    acc = np.asarray(acc)
    assert set(np.unique(acc)) <= {0, 1}
    assert abs(acc.mean() - target[0]) < 0.03     # P(accept)=p_target(d)
    delivered = np.where(acc >= 1, 0, np.asarray(toks[:, 0]))
    emp_spec = np.bincount(delivered, minlength=V) / R

    nonspec, _ = S.sample_rows(
        jnp.broadcast_to(L_row, (R, V)), pos, samp, gstate, gtable)
    emp_plain = np.bincount(np.asarray(nonspec), minlength=V) / R

    assert np.abs(emp_spec - target).max() < 0.03
    assert np.abs(emp_plain - target).max() < 0.03


def test_spec_greedy_rows_prefix_match():
    """temperature<=0 rows keep the legacy verify rule: accept the
    longest prefix where the draft equals the argmax."""
    R, V, k = 2, 6, 2
    lg = np.full((R, k + 1, V), -5.0, np.float32)
    lg[:, 0, 3] = lg[:, 1, 1] = lg[:, 2, 4] = 5.0  # argmax path 3,1,4
    samp = S.init_row_state(R)                     # defaults: greedy
    gstate = jnp.full((R,), -1, jnp.int32)
    gtable = GrammarArena(V, 1).device_table()
    drafts = jnp.asarray([[3, 1], [3, 2]], jnp.int32)
    toks, acc, _ = S.spec_sample_rows(
        jnp.asarray(lg), drafts, jnp.full((R,), k, jnp.int32),
        jnp.zeros((R,), jnp.int32), samp, gstate, gtable)
    assert list(np.asarray(acc)) == [2, 1]
    assert int(toks[0, 2]) == 4                    # bonus after full accept
    assert int(toks[1, 1]) == 1                    # correction at mismatch


# ---------------------------------------------------------------------------
# engine: greedy byte-identity + seeded replay
# ---------------------------------------------------------------------------

def test_greedy_byte_identity_across_tails():
    """With the sampling subsystem present, default greedy decode is
    byte-identical across the unified step, the fused tail, and
    speculation — the epilogue's temperature<=0 path IS the old argmax."""
    prompts = _prompts(4)
    base = _run(_engine(), prompts)
    assert _run(_engine().enable_fused_tail(), prompts) == base
    assert _run(_engine(speculative=True), prompts) == base
    # explicit temperature-0 sampler == no sampler, byte for byte
    sc = SamplerConfig(temperature=0.0, seed=123)
    assert _run(_engine(), prompts, sampler=sc) == base


@pytest.mark.parametrize("speculative,fused", [
    (False, False), (False, True), (True, False), (True, True),
])
def test_seeded_replay_byte_identity(speculative, fused):
    prompts = _prompts(3)
    sc = SamplerConfig(temperature=0.9, top_k=12, top_p=0.95, seed=77)
    streams = []
    for _ in range(2):
        eng = _engine(speculative=speculative)
        if fused:
            eng.enable_fused_tail()
        streams.append(_run(eng, prompts, sampler=sc))
    assert streams[0] == streams[1]
    assert streams[0] != _run(_engine(speculative=speculative), prompts)


@pytest.mark.parametrize("mp", [1, 2])
def test_seeded_replay_sharded(mp):
    prompts = _prompts(3)
    sc = SamplerConfig(temperature=0.8, top_p=0.9, seed=5)
    a = _run(_engine(mp=mp), prompts, sampler=sc)
    b = _run(_engine(mp=mp), prompts, sampler=sc)
    assert a == b and len(a[0]) == 8


# ---------------------------------------------------------------------------
# engine: constrained decoding
# ---------------------------------------------------------------------------

def test_constrained_rows_emit_only_legal_tokens(abc_grammar):
    g = abc_grammar
    eng = _engine(num_slots=4, grammar_states=g.n_states)
    sc = SamplerConfig(temperature=1.2, seed=11)
    outs = _run(eng, _prompts(4), sampler=sc, grammar=g)
    for t in outs:
        _assert_legal_stream(g, t)


def test_constrained_spec_matches_unified(abc_grammar):
    """Constrained rows never draft — speculation around them changes
    nothing, byte for byte."""
    g = abc_grammar
    prompts = _prompts(3)
    sc = SamplerConfig(temperature=1.2, seed=11)
    a = _run(_engine(num_slots=4, grammar_states=g.n_states),
             prompts, sampler=sc, grammar=g)
    b = _run(_engine(num_slots=4, grammar_states=g.n_states,
                     speculative=True), prompts, sampler=sc, grammar=g)
    assert a == b
    for t in a:
        _assert_legal_stream(g, t)


def test_grammar_prefix_resumes_mid_string(abc_grammar):
    g = abc_grammar
    pre = [1, 2, 3]                                # 'a b c' mid-pair
    eng = _engine(grammar_states=g.n_states)
    prompt = np.concatenate([_prompts(1)[0],
                             np.asarray(pre, np.int32)])
    out = _run(eng, [prompt], sampler=SamplerConfig(seed=4),
               grammar=g, grammar_prefix=pre)[0]
    _assert_legal_stream(g, out, prefix=pre)
    with pytest.raises(ValueError, match="illegal"):
        eng.submit(prompt, grammar=g, grammar_prefix=[2])  # 'b' first


def test_json_constrained_storm_all_tokens_parse(json_grammar_dfa):
    """The headline constrained workload: every token of every stream
    in a JSON-grammar storm is DFA-legal (host-replayed), under both
    greedy and sampled epilogues, with speculation enabled."""
    g = json_grammar_dfa
    eng = _engine(max_new=12, num_slots=4, grammar_states=g.n_states,
                  speculative=True)
    prompts = _prompts(6, seed=3)
    subs = [dict(grammar=g),                      # greedy constrained
            dict(grammar=g,
                 sampler=SamplerConfig(temperature=1.0, seed=21)),
            dict(grammar=g,
                 sampler=SamplerConfig(temperature=1.5, top_p=0.9,
                                       seed=22))]
    rids = [eng.submit(p, **subs[i % 3]) for i, p in enumerate(prompts)]
    out, steps = {}, 0
    while len(out) < len(prompts):
        eng.step(PARAMS)
        out.update(eng.collect())
        steps += 1
        assert steps < 3000
    for r in rids:
        assert out[r]
        _assert_legal_stream(g, out[r])
    # the device mask made the host audit a formality: zero violations
    assert get_registry().get(
        "paddle_sampling_violations_total").value() == 0.0


# ---------------------------------------------------------------------------
# mixed storm: O(1) recompiles + telemetry
# ---------------------------------------------------------------------------

def test_mixed_storm_o1_recompiles_and_metrics(abc_grammar):
    """Greedy, sampled, and constrained rows share ONE program: a mixed
    storm with mid-decode admissions compiles at most twice (cold +
    optional remat), reuses one program object, and the per-mode
    telemetry lands."""
    g = abc_grammar
    eng = _engine(max_new=6, num_slots=4, grammar_states=g.n_states)
    prompts = _prompts(10, seed=5)
    subs = [dict(),
            dict(sampler=SamplerConfig(temperature=0.9, seed=31)),
            dict(sampler=SamplerConfig(temperature=1.1, top_k=9,
                                       seed=32), grammar=g)]
    reg = get_registry()
    v0 = reg.get("paddle_sampling_requests_total").value(
        mode="constrained")
    rc0 = recompiles.count("cbe.unified_step")
    all_subs = [subs[i % 3] for i in range(len(prompts))]
    rids = [eng.submit(p, **s)
            for p, s in zip(prompts[:5], all_subs[:5])]
    out, steps, prog = {}, 0, None
    while len(out) < len(prompts):
        eng.step(PARAMS)
        if prog is None:
            prog = eng._unified_step
        assert eng._unified_step is prog          # never rebuilt
        out.update(eng.collect())
        if steps == 2:                            # mid-decode trickle
            rids += [eng.submit(p, **s)
                     for p, s in zip(prompts[5:], all_subs[5:])]
        steps += 1
        assert steps < 3000
    assert recompiles.count("cbe.unified_step") - rc0 <= 2
    for i, r in enumerate(rids):
        if i % 3 == 2:
            _assert_legal_stream(g, out[r])
    assert reg.get("paddle_sampling_requests_total").value(
        mode="constrained") - v0 >= 3
    assert reg.get("paddle_sampling_tokens_total").value(
        mode="sampled") > 0
    assert reg.get("paddle_sampling_grammar_states").value() \
        == g.n_states


# ---------------------------------------------------------------------------
# admission writes host mirrors, not the device (ISSUE 30)
# ---------------------------------------------------------------------------
TAILS = ["unified", "spec", "fused"]


def _tail_engine(tail, **kw):
    """An engine on one of the three step paths."""
    eng = _engine(speculative=tail == "spec", **kw)
    return eng.enable_fused_tail() if tail == "fused" else eng


def _guard_admission(eng):
    """Every admission of ``eng`` from now on runs with host<->device
    transfers forbidden; returns the list its admitted counts land in."""
    admit, counts = eng._admit, []

    def guarded():
        with jax.transfer_guard("disallow_explicit"):
            counts.append(admit())
        return counts[-1]
    eng._admit = guarded
    return counts


@pytest.mark.parametrize("tail", TAILS)
def test_greedy_admission_does_no_device_work(tail):
    """Greedy unconstrained requests entering slots (fresh ones, then
    reused ones) transfer nothing and launch nothing inside admission:
    the row state's device arrays are the SAME objects afterwards, no
    mirror is dirty, and the step's grammar reset is the cached all-KEEP
    array. The parent's ``_set_row_sampler`` ran five eager
    ``.at[s].set`` programs here (a scalar transfer each)."""
    eng = _tail_engine(tail)                     # 2 slots, 6 requests
    counts = _guard_admission(eng)
    samp_dev, keep = eng._samp_dev, eng._greset_keep_dev
    prompts = _prompts(6, seed=9)
    rids = [eng.submit(p) for p in prompts]
    out, steps = {}, 0
    while len(out) < len(prompts):
        eng.step(PARAMS)
        assert eng._samp_dev is samp_dev
        assert not (eng._samp_dirty or eng._greset_dirty)
        assert eng._upload_row_state() is keep   # nothing rides the plan
        out.update(eng.collect())
        steps += 1
        assert steps < 3000
    assert sum(counts) == len(prompts) and max(counts) <= 2
    assert_greedy(PARAMS, CFG, prompts, [out[r] for r in rids], n_new=8)


def test_changed_row_state_rides_one_upload(abc_grammar):
    """A sampled or constrained admission does no device work either: it
    marks a mirror dirty, and the step's upload refreshes the device
    copies once. The same values again leave the mirrors clean."""
    g = abc_grammar
    eng = _engine(num_slots=1, grammar_states=g.n_states)
    counts = _guard_admission(eng)
    sc = SamplerConfig(temperature=0.9, seed=5)
    eng.submit(_prompts(1)[0], sampler=sc)
    samp_dev = eng._samp_dev
    with jax.transfer_guard("disallow_explicit"):
        assert eng._admit() == 1
    assert eng._samp_dirty and not eng._greset_dirty
    assert eng._samp_dev is samp_dev             # not yet: with the plan
    eng.step(PARAMS)
    assert eng._samp_dev is not samp_dev and not eng._samp_dirty
    assert float(np.asarray(eng._samp_dev[1])[0]) == np.float32(0.9)
    while not eng.collect():
        eng.step(PARAMS)
    # the same sampler into the same slot: what the row holds already
    samp_dev = eng._samp_dev
    eng.submit(_prompts(1)[0], sampler=sc, grammar=g)
    eng.step(PARAMS)
    assert eng._samp_dev is samp_dev             # sampler unchanged
    assert not eng._greset_dirty and eng._gheld[0]     # grammar rode it
    assert (np.asarray(eng._greset) == -2).all()       # KEEP again
    assert counts == [1] + [0] * (len(counts) - 2) + [1]


def _aligned(a, align=64):
    """A copy of ``a`` at an address the CPU backend maps zero-copy."""
    buf = np.empty(a.nbytes + align, np.uint8)
    off = (-buf.ctypes.data) % align
    out = buf[off:off + a.nbytes].view(a.dtype)
    out[:] = a
    return out


def test_row_state_uploads_hand_over_private_copies(abc_grammar):
    """The mirrors are written in place by later admissions (and the
    grammar reset is cleared right after its upload), while a transfer
    may still be reading its source or alias it outright: with other
    device work in flight (a handoff's page import, a COW copy) an
    upload of the mirror ITSELF let that next write through, and a
    constrained row lost its start state. Aligned mirrors and a matmul
    in flight make the hazard deterministic on the CPU."""
    g = abc_grammar
    sc = SamplerConfig(temperature=1.2, seed=11)
    kw = dict(num_slots=2, grammar_states=g.n_states)
    want = _run(_engine(**kw), _prompts(4), sampler=sc, grammar=g)
    eng = _engine(**kw)
    eng._samp = tuple(_aligned(a) for a in eng._samp)
    eng._greset = _aligned(eng._greset)
    big, upload = jnp.ones((1024, 1024)), eng._upload_row_state
    busy = jax.jit(lambda x: x @ x @ x)

    def upload_behind_other_work():
        busy(big)                                 # in flight, not awaited
        return upload()
    eng._upload_row_state = upload_behind_other_work
    outs = _run(eng, _prompts(4), sampler=sc, grammar=g)
    for t in outs:
        _assert_legal_stream(g, t)
    assert outs == want
    held = [np.array(d) for d in eng._samp_dev]
    for a in eng._samp:
        a[:] = 7                                  # a later admission
    assert all((np.asarray(d) == h).all()
               for d, h in zip(eng._samp_dev, held))


def _reuse_kinds(g):
    return {"greedy": dict(),
            "sampled": dict(sampler=SamplerConfig(
                temperature=0.9, top_k=12, top_p=0.95, seed=77)),
            "constrained": dict(grammar=g)}


@pytest.mark.parametrize("tail", TAILS)
@pytest.mark.parametrize("first,second", [
    ("sampled", "greedy"), ("greedy", "sampled"),
    ("constrained", "greedy"), ("greedy", "constrained")])
def test_slot_reuse_never_inherits_row_state(abc_grammar, tail, first,
                                             second):
    """ONE slot, two requests: the second enters the row the first left
    and decodes as if the row were new — a greedy one equals the full
    re-forward (no inherited temperature, no stale grammar mask), a
    sampled or constrained one equals its run on a fresh engine."""
    g = abc_grammar
    kinds = _reuse_kinds(g)
    prompts = _prompts(2, seed=21)
    kw = dict(num_slots=1, grammar_states=g.n_states)
    eng = _tail_engine(tail, **kw)
    _guard_admission(eng)
    reused = _run_each(eng, prompts, [kinds[first], kinds[second]])
    assert len(reused[0]) == len(reused[1]) == 8
    if second == "greedy":
        assert_greedy(PARAMS, CFG, prompts[1:], reused[1:], n_new=8)
    else:
        alone = _run_each(_tail_engine(tail, **kw), prompts[1:],
                          [kinds[second]])
        assert reused[1] == alone[0]
    if second == "constrained":
        _assert_legal_stream(g, reused[1])
    if first == "constrained":
        _assert_legal_stream(g, reused[0])


def test_mixed_samplers_over_many_steps_compile_nothing_new(abc_grammar):
    """Sampler configurations and grammars admitted one at a time over
    many steps are DATA: after the greedy program and the one sticky
    epilogue flip, the step program is never rebuilt and never retraced
    (the uploaded mirrors have the dtypes of the arrays they replace)."""
    g = abc_grammar
    eng = _engine(max_new=4, num_slots=2, grammar_states=g.n_states)
    _guard_admission(eng)
    rc0 = recompiles.count("cbe.unified_step")
    _run(eng, _prompts(2))                        # the greedy program
    assert recompiles.count("cbe.unified_step") - rc0 == 1
    subs = [dict(sampler=SamplerConfig(temperature=0.5 + 0.1 * i,
                                       top_k=i % 4, seed=100 + i),
                 **(dict(grammar=g) if i % 3 == 0 else {}))
            if i % 2 else dict() for i in range(12)]
    prompts = _prompts(12, seed=8)
    out, prog = {}, None
    for p, sub in zip(prompts, subs):             # one admission a time
        rid = eng.submit(p, **sub)
        while rid not in out:
            eng.step(PARAMS)
            if eng._epilogue_on:                  # from the flip on: ONE
                prog = prog or eng._unified_step
                assert eng._unified_step is prog
            out.update(eng.collect())
    assert recompiles.count("cbe.unified_step") - rc0 == 2   # the flip
    assert prog._cache_size() == 1
    assert len(out) == 12


def test_catalog_declares_sampling_surface():
    from paddle_tpu.observability import catalog
    assert catalog.declared_metric(
        "paddle_sampling_requests_total") == ("counter", ("mode",))
    assert catalog.declared_metric(
        "paddle_sampling_grammar_states") == ("gauge", ())
    assert catalog.declared_event("constraint_violation")


# ---------------------------------------------------------------------------
# serving: scheduler + router failover replay
# ---------------------------------------------------------------------------

def test_router_materializes_seed_and_failover_replays(abc_grammar):
    """A sampled+constrained stream survives replica death byte-
    identically: the router pins the seed at submit, re-dispatches with
    the streamed tokens as prompt + grammar_prefix, and the position-
    keyed epilogue PRNG continues the exact stream on the sibling."""
    from paddle_tpu.serving import FleetRouter, RouterConfig
    from paddle_tpu.serving.replica import ReplicaHandle
    g = abc_grammar

    def fleet():
        return FleetRouter(
            [ReplicaHandle(i, _engine(grammar_states=g.n_states))
             for i in (0, 1)], RouterConfig())

    def drain(f, kill_after=None):
        req, steps, killed = next(iter(f._requests.values())), 0, False
        while not all(q.done for q in f._requests.values()):
            f.step(PARAMS)
            steps += 1
            if (kill_after is not None and not killed
                    and len(req.stream.tokens) >= kill_after):
                f.replicas[req.replica_id].kill()
                killed = True
            assert steps < 10000

    prompt = _prompts(1)[0]
    f1 = fleet()
    r1 = f1.submit(prompt, sampler=SamplerConfig(temperature=0.8),
                   grammar=g)
    assert r1.sampler.seed is not None            # pinned at the router
    drain(f1, kill_after=2)
    assert r1.failovers >= 1

    f2 = fleet()
    r2 = f2.submit(prompt, sampler=r1.sampler, grammar=g)
    drain(f2)
    assert r1.stream.tokens == r2.stream.tokens
    _assert_legal_stream(g, r1.stream.tokens)
