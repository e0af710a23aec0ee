"""The engine enqueues the next dispatch before it fences the one in flight
(``ContinuousBatchingEngine._step_unified``) while every slot is taken or a
request waits: what that may and may not change.

Every mix is served twice on the same weights, once by the engine and once
by the same engine fencing each dispatch in the round that enqueued it (the
loop as it was before: ``SyncEngine`` below), greedy, and the tokens of
every request must agree token for token; two mixes a family are also held
to the family's own reference, which shares no code with the engine. The
mixes: prefill beside decode, prefix-cache hits and inserts at retirement,
budget stops inside a dispatch, EOS inside a dispatch, ``cancel()`` of a
row in flight, and an engine that runs empty and is stepped again; for
Llama, an expert family (SmallThinker) and Jamba (a recurrent state a row).
After every step the page pool is conserved, at most one dispatch is in
flight, and ``step()`` returns 0 only with none. ``ahead`` on
``cbe.upload`` and ``dropped_tokens`` on ``cbe.unpack`` read what the mix
implies. Then each hazard of the change, on its own: the block tables a
dispatch reads, rows that stop without warning, a failure at the fence or
before the enqueue, the refill lag of a freed slot, an arrival into a free
slot (no lag: nothing is enqueued ahead there), and the speculative path,
which keeps its synchronous loop."""

import collections
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import decoding
from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.models import llama as L
from paddle_tpu.serving import (RequestState, SchedulerConfig,
                                ServingScheduler)

from test_jamba_model import _reference_logits as jamba_reference
from test_smallthinker_model import _reference_logits as st_reference

CHUNK, PAGE, SLOTS, STEP_TOKENS = 3, 4, 3, 12


class SyncEngine(ContinuousBatchingEngine):
    """The unified round with nothing left in flight: admit, enqueue one
    dispatch, fence and deliver it in the same call."""

    def _step_unified(self, params) -> int:
        admitted = self._admit()
        if self._plan_rows():
            self._launch(params, admitted, self._admitted_cached)
            self._land()
        if self.cache is not None:
            self.mgr.check_conservation()
        return len(self._live)


FAMILIES = {
    # tiny config, prefix cache (a state model refuses one), the weights
    # kept as drawn where the others are boosted (None: none boosted)
    "llama": (lambda m: m.llama_tiny(num_hidden_layers=2), True, None),
    "smallthinker": (lambda m: m.smallthinker_tiny(), True,
                     lambda m: m._NORM_KEYS),
    "jamba": (lambda m: m.jamba_tiny(), False, lambda m: (
        m._NORM_KEYS + m._F32_KEYS + ("conv_w", "conv_b"))),
}


class Family:
    """A family's weights and its two engines, built once: the mixes run
    on both in the same order, so whatever one mix leaves in the prefix
    cache the next finds in both."""

    def __init__(self, name):
        self.name = name
        make, self.prefix_cache, kept = FAMILIES[name]
        self.module = importlib.import_module(f"paddle_tpu.models.{name}")
        self.cfg = make(self.module)
        self.params = self.module.init_stacked_params(self.cfg, seed=3)
        if kept is not None:
            # every matrix 6 times the program's std of 0.02, as the
            # family's own tests draw them: at a hidden size of 64 the drawn
            # weights give a stream that repeats one token
            keep = kept(self.module)
            self.params = {k: v if k == "ln_f" or k in keep or k[2:] in keep
                           else v * 6.0 for k, v in self.params.items()}
        self.engines = {mode: self.engine(mode)
                        for mode in ("sync", "overlap")}

    def engine(self, mode, slots=SLOTS, **kw):
        cls = SyncEngine if mode == "sync" else ContinuousBatchingEngine
        kw = dict(dict(num_slots=slots, page_size=PAGE, max_seq_len=64,
                       chunk=CHUNK, step_tokens=STEP_TOKENS,
                       prefix_cache=self.prefix_cache), **kw)
        return cls(self.cfg, GenerationConfig(max_new_tokens=8, seed=3), **kw)

    def prompts(self, lengths, seed, prefix=()):
        rng = np.random.RandomState(seed)
        return [np.concatenate([np.asarray(prefix, np.int32), rng.randint(
            1, self.cfg.vocab_size, (n,)).astype(np.int32)])
            for n in lengths]


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    return Family(request.param)


class _Span:
    """Stands in for ``profiler.record.phase``: keeps the name and the
    integer stats a span was given, at its start and its end."""

    def __init__(self, log, name, stats):
        self.log, self.name, self.stats = log, name, stats

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.log.append((self.name, self.stats))

    def set_metadata(self, **stats):
        self.stats.update(stats)


def serve(eng, params, arrivals, cancels=(), monkeypatch=None):
    """Step ``eng`` until every arrival came, ran and the engine is empty.
    ``arrivals``: {call: [(prompt, budget)]}, submitted before that call's
    step; ``cancels``: {call: [index of a request]}, cancelled before it.
    Checks after every step: the pool is conserved, at most one dispatch
    is in flight, a return of 0 means none. Returns each request's
    delivered tokens, its finished output (None where it was cancelled)
    and, per call, the spans it wrote."""
    delivered = collections.defaultdict(list)
    eng.token_callback = lambda rid, t: delivered[rid].append(t)
    rids, finished, calls = [], {}, []
    log: list = []
    if monkeypatch is not None:
        monkeypatch.setattr(decoding, "phase",
                            lambda name, **kw: _Span(log, name, dict(kw)))
    last = max(arrivals)
    call = 0
    while True:
        for prompt, budget in arrivals.get(call, ()):
            rids.append(eng.submit(prompt, max_new_tokens=budget))
        for i in dict(cancels).get(call, ()):
            assert eng.cancel(rids[i])
        n = eng.step(params)
        finished.update(eng.collect())
        calls.append(list(log))
        log.clear()
        eng.mgr.check_conservation()
        assert len(eng._flights) <= 1
        assert n == len(eng._live)
        if n == 0:
            assert not eng._flights and not eng._held
        call += 1
        if call > last and n == 0 and not eng.num_queued:
            break
        assert call < 200
    if monkeypatch is not None:
        monkeypatch.undo()
    eng.token_callback = None
    return ([delivered[r] for r in rids], [finished.get(r) for r in rids],
            calls)


def phases(calls):
    """``ahead`` of each dispatch must say whether one was in flight when it
    was enqueued; returns the uploads' ``ahead`` and the unpacks'
    ``dropped_tokens``, in order."""
    flying, aheads, dropped = 0, [], []
    for spans in calls:
        names = [n for n, _ in spans]
        d, f = names.count("cbe.dispatch"), names.count("cbe.fence")
        ahead = [s["ahead"] for n, s in spans if n == "cbe.upload"]
        assert ahead == [int(flying + i > 0) for i in range(d)]
        assert d <= (2 if flying == 0 else 1)
        flying += d - f
        assert 0 <= flying <= 1
        aheads += ahead
        dropped += [s["dropped_tokens"] for n, s in spans
                    if n == "cbe.unpack"]
    assert flying == 0
    return aheads, dropped


def llama_reference(cfg, params, rows, spans):
    """``models.llama.forward_stacked`` over each whole row (no cache, no
    paging, no packing: ``tests/_oracle.py``'s reference, one pass a row),
    float32 logits at the positions of ``spans``."""
    return [np.asarray(L.forward_stacked(params, jnp.asarray(row[None]), cfg)
                       [0, a:b].astype(jnp.float32))
            for row, (a, b) in zip(rows, spans)]


def held_to_the_reference(family, prompts, outputs):
    """Each output against the family's reference, which shares no code
    with the engine, teacher-forced: each served token is the reference's
    argmax for its context (SmallThinker: within 1e-4 of the maximum, as
    its own tests read it)."""
    reference, tol = {"llama": (llama_reference, 0.0),
                      "smallthinker": (st_reference, 1e-4),
                      "jamba": (jamba_reference, 0.0)}[family.name]
    rows = [np.concatenate([p, np.asarray(o, np.int32)])
            for p, o in zip(prompts, outputs)]
    spans = [(len(p) - 1, len(p) - 1 + len(o))
             for p, o in zip(prompts, outputs)]
    for out, logits in zip(outputs, reference(family.cfg, family.params,
                                              rows, spans)):
        deficit = logits.max(-1) - logits[np.arange(len(out)), out]
        assert deficit.max() <= tol, (family.name, deficit)


def both(family, monkeypatch, arrivals, cancels=()):
    """Serve a mix on both engines; the tokens must agree. Returns the
    overlapped engine's (delivered, finished, aheads, dropped)."""
    out = {}
    for mode in ("sync", "overlap"):
        eng = family.engines[mode]
        delivered, finished, calls = serve(
            eng, family.params, arrivals, cancels, monkeypatch)
        aheads, dropped = phases(calls)
        out[mode] = (delivered, finished, aheads, dropped)
    assert out["overlap"][0] == out["sync"][0]
    assert out["overlap"][1] == out["sync"][1]
    # the synchronous loop never enqueues ahead and never drops a token
    assert not any(out["sync"][2]) and not any(out["sync"][3])
    return out["overlap"]


# ---------------------------------------------------------------------------
# the mixes, on every family
# ---------------------------------------------------------------------------
def test_prefill_beside_decode(family, monkeypatch):
    """Long prompts arrive while short ones decode, wait for a slot, and
    prefill over several micro-rounds of several dispatches beside them;
    the outputs are also the family reference's."""
    short = family.prompts((3, 5, 4), seed=1)
    long = family.prompts((30, 22, 17), seed=2)
    arrivals = {0: [(p, b) for p, b in zip(short, (4, 7, 10))],
                2: [(long[0], 6)], 3: [(long[1], 5), (long[2], 7)]}
    delivered, finished, aheads, dropped = both(family, monkeypatch,
                                                arrivals)
    assert [len(t) for t in delivered] == [4, 7, 10, 6, 5, 7]
    assert finished == delivered
    assert aheads[0] == 0 and sum(aheads) > len(aheads) / 2
    assert not any(dropped)
    held_to_the_reference(family, short + long, finished)


def test_prefix_hits_and_inserts_at_retirement(family, monkeypatch):
    """A first wave retires and inserts its sequences; a second wave shares
    their leading pages (a whole system prompt of three pages)."""
    system = family.prompts((3 * PAGE,), seed=3)[0]
    first = family.prompts((2, 6, 4), seed=4, prefix=system)
    second = family.prompts((3, 5, 1), seed=5, prefix=system)
    arrivals = {0: [(p, 5) for p in first], 6: [(p, 6) for p in second]}
    delivered, finished, aheads, dropped = both(family, monkeypatch,
                                                arrivals)
    assert [len(t) for t in delivered] == [5, 5, 5, 6, 6, 6]
    assert sum(aheads) > 0 and not any(dropped)
    held_to_the_reference(family, first + second, finished)
    eng = family.engines["overlap"]
    if family.prefix_cache:
        assert eng.cache.snapshot()["hits"] >= 3
        assert eng.cache.snapshot()["hits"] == \
            family.engines["sync"].cache.snapshot()["hits"]


def test_budget_stops_inside_a_dispatch(family, monkeypatch):
    """Budgets that are no multiple of the chunk end inside a dispatch: the
    row is planned into no later one, so nothing is computed and dropped
    for it, and the engine makes no more dispatches than the synchronous
    loop."""
    prompts = family.prompts((4, 7, 9), seed=6)
    arrivals = {0: [(p, b) for p, b in zip(prompts, (4, 5, 7))]}
    delivered, *_ = both(family, monkeypatch, arrivals)
    assert [len(t) for t in delivered] == [4, 5, 7]
    dispatches = {}
    for mode in ("sync", "overlap"):
        eng = family.engines[mode]
        n0 = eng._dispatches
        serve(eng, family.params, arrivals)
        dispatches[mode] = eng._dispatches - n0
    assert dispatches["overlap"] == dispatches["sync"]


def test_eos_inside_a_dispatch(family, monkeypatch):
    """A row whose EOS comes inside a dispatch was planned into the next
    one already: its tokens there are dropped (a whole chunk: a decoding
    row emits every round), and its output ends at the EOS."""
    prompts = family.prompts((5, 8, 3), seed=7)
    arrivals = {0: [(p, 12) for p in prompts]}
    plain, *_ = both(family, monkeypatch, arrivals)
    # an EOS that the first request meets mid-stream, at its third to
    # sixth token, and that it has not met before
    tokens = plain[0]
    eos = next(t for i, t in enumerate(tokens[:6])
               if i >= 2 and t not in tokens[:i])
    for eng in family.engines.values():
        eng.config.eos_token_id = eos
    try:
        delivered, finished, _, dropped = both(family, monkeypatch,
                                               arrivals)
    finally:
        for eng in family.engines.values():
            eng.config.eos_token_id = None
    stopped = [t for t in finished if t and t[-1] == eos and len(t) < 12]
    assert finished[0] == tokens[:tokens.index(eos) + 1]
    assert 0 < sum(dropped) <= CHUNK * len(stopped)
    assert sum(dropped) % CHUNK == 0


def test_cancel_of_a_row_in_flight(family, monkeypatch):
    """Rows cancelled while the dispatch in flight still holds them: what
    they delivered before the cancel agrees; their tokens in that dispatch
    are dropped; the last one cancelled leaves nothing live, so the engine
    fences at once and gives every page back."""
    prompts = family.prompts((4, 6, 3), seed=8)
    arrivals = {0: [(p, 12) for p in prompts]}
    delivered, finished, _, dropped = both(
        family, monkeypatch, arrivals, cancels={3: [1], 4: [0, 2]})
    assert finished == [None, None, None]
    assert all(0 < len(t) < 12 for t in delivered)
    assert sum(dropped) == 3 * CHUNK
    assert not family.engines["overlap"].mgr._tables     # no page held


def test_an_engine_that_runs_empty_is_stepped_again(family, monkeypatch):
    """Empty rounds return 0 with nothing in flight; later arrivals start
    the pipeline again (``ahead`` 0 on their first dispatch). Each wave
    fills every slot with rows in lockstep (one prompt length, one budget),
    so no slot is free before the wave ends."""
    waves = [family.prompts((5,) * SLOTS, seed=9 + i) for i in range(3)]
    arrivals = {0: [(p, 4) for p in waves[0]], 9: [(p, 5) for p in waves[1]],
                15: [(p, 3) for p in waves[2]]}
    delivered, _, aheads, _ = both(family, monkeypatch, arrivals)
    assert [len(t) for t in delivered] == [4] * 3 + [5] * 3 + [3] * 3
    eng = family.engines["overlap"]
    assert eng.step(family.params) == 0 and not eng._flights
    # three starts from empty: three dispatches enqueued with none ahead,
    # every other one ahead
    assert aheads.count(0) == 3 and len(aheads) > 3


# ---------------------------------------------------------------------------
# one slot, by hand: ahead and dropped_tokens
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def llama():
    return Family("llama")


def _one_slot(llama):
    # a prompt of one page fills the round: round 0 prefills it, round 1
    # emits the first token; then two tokens a dispatch
    eng = llama.engine("overlap", slots=1, chunk=2, step_tokens=PAGE)
    return eng, llama.prompts((PAGE,), seed=10)[0]


def test_one_slot_by_hand(llama, monkeypatch):
    """Budget 6 emits 1 + 2 + 2 + 2 tokens over four dispatches, one call
    each: the first call enqueues two, every later one enqueues the next
    before it fences, the last enqueues none (the budget ends inside the
    fourth dispatch, so no fifth is planned); nothing dropped."""
    eng, prompt = _one_slot(llama)
    delivered, _, calls = serve(eng, llama.params, {0: [(prompt, 6)]},
                                monkeypatch=monkeypatch)
    assert len(calls) == 4 and len(delivered[0]) == 6
    aheads, dropped = phases(calls)
    assert aheads == [0, 1, 1, 1] and dropped == [0, 0, 0, 0]


def test_one_slot_eos_and_cancel_drop_a_dispatch(llama, monkeypatch):
    """EOS at the third token is seen when the second dispatch is fenced,
    with the third in flight: nothing is left live, so the same call fences
    it and drops its two tokens. A cancel after the second call does the
    same."""
    eng, prompt = _one_slot(llama)
    plain, _, _ = serve(eng, llama.params, {0: [(prompt, 10)]})
    tokens = plain[0]
    assert tokens[2] not in tokens[:2]
    eng.config.eos_token_id = tokens[2]
    try:
        delivered, finished, calls = serve(
            eng, llama.params, {0: [(prompt, 10)]}, monkeypatch=monkeypatch)
    finally:
        eng.config.eos_token_id = None
    assert delivered[0] == finished[0] == tokens[:3]
    aheads, dropped = phases(calls)
    assert len(calls) == 2 and aheads == [0, 1, 1] and dropped == [0, 0, 2]
    delivered, finished, calls = serve(
        eng, llama.params, {0: [(prompt, 10)]}, cancels={2: [0]},
        monkeypatch=monkeypatch)
    assert delivered[0] == tokens[:3] and finished == [None]
    # the cancel fenced the third dispatch itself, before the third step
    assert len(calls) == 3 and phases(calls) == ([0, 1, 1], [0, 0, 2])


# ---------------------------------------------------------------------------
# the hazards
# ---------------------------------------------------------------------------
def test_a_dispatch_reads_a_copy_of_the_block_tables(llama, monkeypatch):
    """A retirement writes the block-table mirror while the dispatch in
    flight may still read its upload: what is uploaded never shares memory
    with the mirror (the CPU backend may alias an aligned host buffer)."""
    eng = llama.engines["overlap"]
    uploaded = []
    real = jnp.asarray

    def spy(a, *args, **kw):
        if isinstance(a, np.ndarray):
            uploaded.append(a)
        return real(a, *args, **kw)
    monkeypatch.setattr(decoding.jnp, "asarray", spy)
    prompts = llama.prompts((5, 9, 3), seed=11)
    serve(eng, llama.params, {0: [(p, b) for p, b in zip(prompts, (2, 7, 4))]})
    monkeypatch.undo()
    tables = [a for a in uploaded if a.shape == eng._bt.shape]
    assert tables and not any(np.shares_memory(a, eng._bt) for a in tables)


def test_a_stopped_row_keeps_its_pages_until_its_last_dispatch_is_fenced(
        llama):
    """A row that stops by EOS while the next dispatch holds it: its output
    is finished at once, but its slot, its pages and the insert of its
    prefix wait for the fence of that dispatch (the next step's: another
    row keeps the engine live)."""
    quick, slow = llama.prompts((PAGE, 5), seed=12)
    plain, _, _ = serve(llama.engines["sync"], llama.params,
                        {0: [(quick, 10), (slow, 10)]})
    eos = plain[0][4]
    assert eos not in plain[0][:4] and eos not in plain[1]
    eng = llama.engine("overlap", slots=2)
    eng.config.eos_token_id = eos
    rids = [eng.submit(quick, max_new_tokens=10),
            eng.submit(slow, max_new_tokens=10)]
    while rids[0] not in eng._finished:
        eng.step(llama.params)
        eng.mgr.check_conservation()
    assert eng._finished[rids[0]] == plain[0][:5]
    slot = eng._slot_rid.index(rids[0])
    assert slot in eng._held and eng.mgr.sequence_pages(rids[0])
    assert slot not in eng._plan_rows()
    # nothing of it indexed yet: a prompt of its first two blocks and one
    # token more finds no page to share
    again = np.concatenate([quick, plain[0][:PAGE], [1]]).astype(np.int32)
    assert not eng.cache.peek(again)[0]
    eng.step(llama.params)
    assert eng._slot_rid[slot] is None and rids[0] not in eng.mgr._tables
    assert len(eng.cache.peek(again)[0]) == 2
    while eng.step(llama.params):
        pass
    eng.config.eos_token_id = None


class _FlakyFence:
    """Stands for a dispatch's tokens: the fence raises ``fails`` times (a
    negative count: always), then reads the real array."""

    def __init__(self, real, fails):
        self.real, self.fails = real, fails

    def __array__(self, dtype=None, copy=None):
        if self.fails:
            self.fails -= 1
            raise RuntimeError("injected fence fault")
        return np.asarray(self.real, dtype)


def _scheduled(llama, fails, **sched_kw):
    eng = llama.engine("overlap", slots=2)
    sched = ServingScheduler(eng, SchedulerConfig(
        retry_backoff_s=0.0, **sched_kw), sleep=lambda s: None)
    prompts = llama.prompts((4, 6), seed=13)
    handles = [sched.submit(p, max_new_tokens=9) for p in prompts]
    sched.step(llama.params)
    sched.step(llama.params)
    f = eng._flights[0]
    f.toks = _FlakyFence(f.toks, fails)
    return eng, sched, prompts, handles


def test_a_failed_fence_is_retried_on_the_same_dispatch(llama):
    """The fence of the dispatch in flight raises once: the scheduler's
    retry finds two in flight, enqueues nothing more and fences the same
    dispatch again; every token arrives, once, as without the fault."""
    eng, sched, prompts, handles = _scheduled(llama, 1, max_step_retries=2)
    sched.run(llama.params, max_steps=100)
    assert sched.metrics.counters["step_retries_total"] == 1
    assert all(h.state == RequestState.DONE for h in handles)
    sync = llama.engines["sync"]
    want, _, _ = serve(sync, llama.params,
                       {0: [(p, 9) for p in prompts]})
    assert [h.stream.result() for h in handles] == want
    assert not eng._flights and not eng._held


def test_a_fence_that_keeps_failing_degrades_and_frees_every_page(llama):
    """A dispatch whose fence always raises: the retries run out, the
    scheduler degrades and cancels every request; the last cancel fences
    what is in flight, fails again, and still gives every page back."""
    eng, sched, _, handles = _scheduled(llama, -1, max_step_retries=1)
    sched.run(llama.params, max_steps=100)
    assert sched.degraded
    assert all(h.state == RequestState.FAILED for h in handles)
    assert not eng._flights and not eng._held and not eng._live
    assert eng.mgr.num_free_pages == eng.mgr.usable_pages
    eng.mgr.check_conservation()


def test_a_freed_slot_is_refilled_one_dispatch_later(llama):
    """The price of planning ahead in a closed loop: a request sent when
    another finishes joins the dispatch after the one it would have joined,
    so its first token comes one step later than in the synchronous loop;
    its tokens are the same."""
    first_call = {}
    for mode in ("sync", "overlap"):
        eng = llama.engine(mode, slots=2)
        short, long, late = llama.prompts((3, 4, 5), seed=14)
        a = eng.submit(short, max_new_tokens=3)
        eng.submit(long, max_new_tokens=20)
        calls = 0
        while a not in eng._finished:
            eng.step(llama.params)
            calls += 1
        b = eng.submit(late, max_new_tokens=4)
        seen = collections.defaultdict(list)
        eng.token_callback = lambda rid, t: seen[rid].append(t)
        while not seen[b]:
            eng.step(llama.params)
            calls += 1
        first_call[mode] = calls
        while eng.step(llama.params):
            pass
        first_call[mode, "tokens"] = eng._finished[b]
    assert first_call["overlap"] == first_call["sync"] + 1
    assert first_call["overlap", "tokens"] == first_call["sync", "tokens"]


def test_an_arrival_into_a_free_slot_waits_no_dispatch_more(llama):
    """An open loop below capacity: with a slot free and no request
    waiting, nothing is enqueued ahead (every round fences the dispatch it
    enqueued, as the synchronous loop does), so a request that arrives
    between two rounds joins the next dispatch and its first token comes
    in the same call as in the synchronous loop; once it fills the last
    slot, the next dispatch is enqueued ahead again."""
    first_call = {}
    for mode in ("sync", "overlap"):
        eng = llama.engine(mode, slots=2)
        running, late = llama.prompts((4, 5), seed=16)
        eng.submit(running, max_new_tokens=20)
        for _ in range(3):
            eng.step(llama.params)
            assert not eng._flights
        b = eng.submit(late, max_new_tokens=4)
        seen = collections.defaultdict(list)
        eng.token_callback = lambda rid, t: seen[rid].append(t)
        calls = 0
        while not seen[b]:
            eng.step(llama.params)
            calls += 1
        first_call[mode] = calls
        first_call[mode, "ahead"] = len(eng._flights)
        while eng.step(llama.params):
            pass
        first_call[mode, "tokens"] = eng._finished[b]
    assert first_call["overlap"] == first_call["sync"]
    assert first_call["overlap", "ahead"] == 1
    assert first_call["sync", "ahead"] == 0
    assert first_call["overlap", "tokens"] == first_call["sync", "tokens"]


@pytest.mark.parametrize("where", ["plan", "dispatch"])
def test_a_dispatch_that_fails_before_its_enqueue_is_planned_again(
        llama, where):
    """A step raises once between planning a dispatch and enqueueing it:
    after the planner advanced the rows (``plan``: the work record after
    it), or at the enqueue itself (``dispatch``), with another dispatch in
    flight. Nothing of it counts as planned and the positions and pending
    prompts go back, so the scheduler's retry plans the same rows again:
    every request completes, with the reference's tokens, and every page
    returns."""
    # 24 token slots a dispatch: the long prompt still prefills in the
    # third dispatch, the one that fails
    eng = llama.engine("overlap", slots=2, step_tokens=8)
    sched = ServingScheduler(eng, SchedulerConfig(
        retry_backoff_s=0.0, max_step_retries=2), sleep=lambda s: None)
    prompts = llama.prompts((4, 50, 5), seed=17)
    handles = [sched.submit(p, max_new_tokens=9) for p in prompts]
    sched.step(llama.params)          # compiles; two dispatches enqueued
    assert len(eng._flights) == 1 and eng._pend[1] is not None
    name = "_dispatch_record" if where == "plan" else "_unified_step"
    real, calls = getattr(eng, name), []

    def once(*args):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("injected enqueue fault")
        return real(*args)
    setattr(eng, name, once)
    sched.run(llama.params, max_steps=100)
    assert sched.metrics.counters["step_retries_total"] == 1
    assert all(h.state == RequestState.DONE for h in handles)
    outputs = [h.stream.result() for h in handles]
    assert [len(o) for o in outputs] == [9, 9, 9]
    held_to_the_reference(llama, prompts, outputs)
    assert not eng._flights and not eng._held and not eng.mgr._tables
    eng.mgr.check_conservation()


def test_the_speculative_path_keeps_its_synchronous_loop(llama):
    """``_step_spec`` fences its dispatch in the round that enqueued it:
    nothing is ever left in flight, and no row is held back."""
    eng = llama.engine("overlap", slots=2, speculative=True,
                       prefix_cache=False)
    prompts = llama.prompts((5, 8), seed=15)
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    while eng.step(llama.params):
        assert not eng._flights and not eng._held
    assert not eng._flights
    assert all(len(t) == 6 for t in eng.collect().values())
