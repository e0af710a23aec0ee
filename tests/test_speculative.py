"""Speculative decoding inside the unified ragged step (ISSUE 9):
drafters, verify-in-one-dispatch byte-identity, paged rollback,
O(1) recompiles, telemetry/statusz surfaces."""

import json

import numpy as np
import pytest

from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.inference.speculative import (DraftModel, Drafter,
                                              NgramDrafter)
from paddle_tpu.models import llama as L
from paddle_tpu.observability import get_registry
from paddle_tpu.observability.events import configure_event_log

CFG = L.llama_tiny(num_hidden_layers=2)
PARAMS = L.init_stacked_params(CFG, seed=0)


def _prompts(n=6, lens=(4, 12), seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, CFG.vocab_size,
                        (int(rng.randint(*lens)),)).astype(np.int32)
            for _ in range(n)]


def _engine(max_new=16, num_slots=2, page_size=16, max_seq_len=64,
            **kw):
    return ContinuousBatchingEngine(
        CFG, GenerationConfig(max_new_tokens=max_new),
        num_slots=num_slots, page_size=page_size,
        max_seq_len=max_seq_len, chunk=2, **kw)


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------

def test_ngram_drafter_prompt_lookup():
    d = NgramDrafter(max_ngram=3, min_ngram=1)
    # trailing [7, 8] occurred earlier; continuation is [9, 1, 2, ...]
    assert d.draft([7, 8, 9, 1, 2, 7, 8], 3) == [9, 1, 2]
    # most RECENT earlier occurrence wins (5 follows the later [1, 2])
    assert d.draft([1, 2, 3, 1, 2, 5, 9, 1, 2], 1) == [5]
    # longest n-gram wins over a shorter, more recent match
    assert d.draft([1, 2, 3, 8, 4, 3, 9, 1, 2, 3], 1) == [8]
    # no earlier occurrence of any trailing n-gram: no draft
    assert d.draft([1, 2, 3, 4], 2) == []
    # min_ngram=2 refuses 1-token evidence
    assert NgramDrafter(max_ngram=3, min_ngram=2).draft(
        [5, 1, 2, 3, 5], 2) == []
    assert NgramDrafter(max_ngram=3, min_ngram=1).draft(
        [5, 1, 2, 3, 5], 2) == [1, 2]
    # k caps the proposal; short continuations come back short (the
    # drafter only replays what it has seen — it never extrapolates)
    assert d.draft([4, 4, 4], 2) == [4]
    assert d.draft([1, 9, 1], 5) == [9, 1]
    with pytest.raises(ValueError):
        NgramDrafter(max_ngram=0)


def test_draft_model_hook_drafts_the_small_models_greedy_chain():
    import jax.numpy as jnp
    dm = DraftModel(PARAMS, CFG, window=32)
    hist = [3, 7, 11, 2]
    got = dm.draft(hist, 3)
    assert len(got) == 3
    # oracle: iterative cache-less greedy with forward_stacked
    toks = list(hist)
    for _ in range(3):
        ids = np.zeros((1, 32), np.int32)
        ids[0, :len(toks)] = toks[-32:]
        lg = L.forward_stacked(PARAMS, jnp.asarray(ids), CFG)
        toks.append(int(jnp.argmax(lg[0, len(toks) - 1]
                                   .astype(jnp.float32))))
    assert got == toks[len(hist):]
    # a Drafter (duck-typed) plugs straight into the engine
    eng = _engine(max_new=6, speculative=True, spec_k=2, drafter=dm)
    ref = _engine(max_new=6).serve(PARAMS, _prompts(2))
    assert [list(o) for o in eng.serve(PARAMS, _prompts(2))] == \
        [list(o) for o in ref]
    # self-drafting with the TARGET model accepts heavily: the draft IS
    # the greedy chain (only cross-program/windowing ties may reject)
    st = eng.spec.snapshot()
    assert st["drafted"] > 0 and st["acceptance_ratio"] > 0.8


# ---------------------------------------------------------------------------
# byte-identity: speculative on/off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefix_cache", [False, True])
def test_spec_byte_identical_greedy(prefix_cache):
    """Greedy output is byte-identical speculative on/off — cache off
    and on (warm suffixes + COW riding the same speculative rounds)."""
    prompts = _prompts(6)
    ref = _engine(prefix_cache=prefix_cache).serve(PARAMS, prompts)
    eng = _engine(prefix_cache=prefix_cache, speculative=True, spec_k=4)
    out = eng.serve(PARAMS, prompts)
    assert [list(o) for o in out] == [list(o) for o in ref]
    assert eng.spec.stats["drafted"] > 0      # speculation actually ran


def test_spec_byte_identical_cow_wave():
    """Full-prompt resubmissions (COW wave): the copy-on-write admission
    path and speculative rounds compose byte-identically."""
    p = _prompts(1, lens=(8, 9))[0]
    wave = [p, p, p[:4], p, p]
    ref = _engine(page_size=4, prefix_cache=True).serve(PARAMS, wave)
    eng = _engine(page_size=4, prefix_cache=True, speculative=True)
    out = eng.serve(PARAMS, wave)
    assert [list(o) for o in out] == [list(o) for o in ref]
    assert eng.cache.stats["cow_copies"] > 0


def test_spec_mid_decode_admission_byte_identical():
    """Requests submitted while other rows are mid-decode join the same
    speculative dispatch; outputs match a fresh non-speculative engine."""
    prompts = _prompts(5)
    ref = _engine().serve(PARAMS, prompts)
    eng = _engine(speculative=True)
    rids = [eng.submit(p) for p in prompts[:2]]
    results = {}
    i = 2
    steps = 0
    while len(results) < len(prompts):
        if i < len(prompts) and steps % 2 == 0:
            rids.append(eng.submit(prompts[i]))
            i += 1
        eng.step(PARAMS)
        results.update(eng.collect())
        steps += 1
        assert steps < 500
    assert [results[r] for r in rids] == [list(o) for o in ref]


def test_spec_eos_retires_identically():
    """An EOS landing inside an accepted draft span retires the row at
    the EOS, exactly like the non-speculative engine."""
    prompts = _prompts(4, seed=7)
    ref_eng = _engine()
    ref_eng.config.eos_token_id = 5
    ref = ref_eng.serve(PARAMS, prompts)
    eng = _engine(speculative=True)
    eng.config.eos_token_id = 5
    out = eng.serve(PARAMS, prompts)
    assert [list(o) for o in out] == [list(o) for o in ref]


# ---------------------------------------------------------------------------
# paged rollback
# ---------------------------------------------------------------------------

class _WrongDrafter(Drafter):
    """Deterministically drafts the WRONG continuation (true greedy
    token + 1 mod vocab) — every draft token is rejected."""

    def __init__(self, refs):
        self.refs = refs    # prompt-key -> full greedy continuation

    def draft(self, history, k):
        for plen, ref in self.refs:
            if history[:plen] == list(map(int, ref["prompt"])):
                done = len(history) - plen
                cont = ref["out"][done:done + k]
                wrong = [(int(t) + 1) % CFG.vocab_size for t in cont]
                # keep drafting past the reference's end so the span
                # always grows the page table before being rejected
                return wrong + [1] * (k - len(wrong))
        raise AssertionError("unknown history")


def test_rejection_rolls_back_and_drafts_never_overdraft(tmp_path):
    """Full rejection every round: the committed length rolls back to
    carry+0 each time, ``spec_rollback`` fires per rejection,
    conservation holds after every step, output is byte-identical —
    and drafts are clamped to the remaining budget (positions past it
    could never commit), so the span always fits the admission
    reservation and rejections strand nothing."""
    p = np.asarray([3, 9, 4, 11], np.int32)   # lp=4, budget=4
    ref = _engine(max_new=4, num_slots=1, page_size=4,
                  max_seq_len=16).serve(PARAMS, [p])
    refs = [(4, {"prompt": p, "out": ref[0]})]
    configure_event_log(str(tmp_path / "ev.jsonl"))
    try:
        eng = _engine(max_new=4, num_slots=1, page_size=4,
                      max_seq_len=16, speculative=True, spec_k=4,
                      drafter=_WrongDrafter(refs))
        out = eng.serve(PARAMS, [p])
    finally:
        configure_event_log(None)
    assert list(out[0]) == list(ref[0])
    st = eng.spec.stats
    assert st["accepted"] == 0 and st["rejected"] == st["drafted"]
    # budget clamp: decode rounds at rem=3/2/1 draft 2/1/0 tokens —
    # never the k=4 the drafter offers
    assert st["drafted"] == 3 and st["rollbacks"] == 2
    assert st["rollback_pages"] == 0      # spans fit the reservation
    events = [json.loads(l) for l in
              (tmp_path / "ev.jsonl").read_text().splitlines()]
    rb = [e for e in events if e["kind"] == "spec_rollback"]
    assert len(rb) == 2
    assert all(e["accepted"] == 0 and e["freed_pages"] == 0 for e in rb)
    # pool fully drained after retire
    assert eng.mgr.num_free_pages == eng.mgr.usable_pages
    eng.mgr.check_conservation()


def test_truncate_frees_stranded_pages_engine_safety_net():
    """The engine's rejection rollback reclaims pages past the
    reservation if an allocation policy ever leaves them (the lazy-
    growth future; forced here by growing a live row's table by hand):
    truncate frees exactly the stranded tail, never below the
    admission reservation, and the books stay balanced."""
    p = np.asarray([3, 9, 4, 11], np.int32)   # lp=4, budget=8, page=4
    ref = _engine(max_new=8, num_slots=1, page_size=4,
                  max_seq_len=16).serve(PARAMS, [p])
    refs = [(4, {"prompt": p, "out": ref[0]})]
    eng = _engine(max_new=8, num_slots=1, page_size=4, max_seq_len=16,
                  speculative=True, spec_k=4, drafter=_WrongDrafter(refs))
    rid = eng.submit(p)
    eng.step(PARAMS)                  # prefill + first sample
    eng.step(PARAMS)                  # one rejected speculative round
    # strand a page past the reservation (pages_for(4+8) = 3)
    eng.mgr.grow_to(rid, 16)
    assert len(eng.mgr._tables[rid]) == 4
    eng.mgr.check_conservation()      # grown-but-uncommitted balances
    eng.step(PARAMS)                  # rejection -> truncate to floor
    assert len(eng.mgr._tables[rid]) == 3
    assert eng.spec.stats["rollback_pages"] == 1
    results = {}
    steps = 0
    while not results:
        eng.step(PARAMS)
        results.update(eng.collect())
        steps += 1
        assert steps < 100
    assert results[rid] == list(ref[0])
    assert eng.mgr.num_free_pages == eng.mgr.usable_pages


def test_pool_pressure_clamps_draft_instead_of_failing():
    """With zero spare pages beyond the admission reservation, grow_to
    raises and the engine shrinks the draft — the round still runs and
    output stays byte-identical."""
    p = np.asarray([3, 9, 4, 11], np.int32)
    ref = _engine(max_new=4, num_slots=1, page_size=4,
                  max_seq_len=16).serve(PARAMS, [p])
    eng = _engine(max_new=4, num_slots=1, page_size=4, max_seq_len=16,
                  num_pages=3,      # usable 2 == reservation exactly
                  speculative=True, spec_k=4)
    out = eng.serve(PARAMS, [p])
    assert list(out[0]) == list(ref[0])
    eng.mgr.check_conservation()


def test_cancel_mid_flight_stays_conserved():
    prompts = _prompts(4)
    eng = _engine(speculative=True, prefix_cache=True)
    rids = [eng.submit(p) for p in prompts]
    eng.step(PARAMS)
    eng.step(PARAMS)
    assert eng.cancel(rids[0])
    eng.step(PARAMS)                 # conservation audited in-step
    while eng.step(PARAMS) or eng.num_queued:
        pass
    done = eng.collect()
    assert rids[0] not in done
    assert set(rids[1:]) <= set(done)


# ---------------------------------------------------------------------------
# O(1) recompiles
# ---------------------------------------------------------------------------

def test_spec_storm_recompiles_o1():
    """Length-diverse storm with mid-decode admissions on a speculative
    engine: ONE compiled program (<= 2 misses tolerated for the flag
    contract), one program object reused for every round."""
    from paddle_tpu.observability.runtime import recompiles
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, CFG.vocab_size,
                           (int(rng.randint(4, 48)),)).astype(np.int32)
               for _ in range(12)]
    eng = ContinuousBatchingEngine(
        CFG, GenerationConfig(max_new_tokens=8), num_slots=4,
        page_size=16, max_seq_len=64, chunk=2, speculative=True)
    rc0 = recompiles.count("cbe.spec_step")
    rids = [eng.submit(p) for p in prompts[:4]]
    prog = None
    i, steps, results = 4, 0, {}
    while len(results) < len(prompts):
        if i < len(prompts) and steps % 2 == 0:
            rids.append(eng.submit(prompts[i]))
            i += 1
        eng.step(PARAMS)
        if prog is None:
            prog = eng._spec_step
        assert eng._spec_step is prog     # never rebuilt
        results.update(eng.collect())
        steps += 1
        assert steps < 2000
    assert recompiles.count("cbe.spec_step") - rc0 <= 2
    assert len(results) == len(prompts)


# ---------------------------------------------------------------------------
# config surface + telemetry
# ---------------------------------------------------------------------------

def test_speculative_accepts_do_sample():
    """The old hard rejection of do_sample+speculative is gone: the
    rejection-sampling verifier makes sampled speculation lossless, so
    construction succeeds and sampled requests complete."""
    eng = ContinuousBatchingEngine(
        CFG, GenerationConfig(max_new_tokens=4, do_sample=True, seed=3),
        num_slots=2, page_size=16, max_seq_len=64, chunk=2,
        speculative=True)
    rids = [eng.submit(p) for p in _prompts(2)]
    out, steps = {}, 0
    while len(out) < 2:
        eng.step(PARAMS)
        out.update(eng.collect())
        steps += 1
        assert steps < 2000
    assert all(len(out[r]) == 4 for r in rids)


def test_spec_metrics_and_statusz():
    from paddle_tpu.serving import SchedulerConfig, ServingScheduler
    reg = get_registry()
    eng = _engine(speculative=True)
    eng.spec.replica = "7"                # what ReplicaHandle does
    sched = ServingScheduler(eng, SchedulerConfig(max_queue_depth=8))
    for p in _prompts(3):
        sched.submit(p)
    sched.run(PARAMS, max_steps=10_000)
    st = sched.statusz()["speculation"]
    assert st["drafted"] == eng.spec.stats["drafted"] > 0
    assert st["accepted"] == eng.spec.stats["accepted"]
    assert 0.0 <= st["acceptance_ratio"] <= 1.0
    # registry families carry the replica label
    assert eng.spec._c_drafted.value(replica="7") == st["drafted"]
    assert eng.spec._g_ratio.value(replica="7") == pytest.approx(
        st["acceptance_ratio"], abs=1e-4)
    # ... and show up in one valid /metrics exposition
    assert 'paddle_spec_drafted_tokens_total{replica="7"}' in \
        reg.prometheus_text()


def test_replica_handle_stamps_spec_label():
    from paddle_tpu.serving import ReplicaHandle
    eng = _engine(speculative=True)
    ReplicaHandle(3, eng)
    assert eng.spec.replica == "3"
