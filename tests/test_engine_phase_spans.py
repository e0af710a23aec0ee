"""Engine step phases and the per-dispatch work record in the PROFILER'S own
trace (ISSUE 24): ``paddle_serving.step`` > ``cbe.step`` > the seven inner
phases of ``_step_unified``, with the record's ten integers on
``cbe.dispatch`` — in any ``jax.profiler`` session, no arming. Since ISSUE
30 ``cbe.upload`` carries two more: ``admitted`` and ``row_state_uploads``.

Since ISSUE 35 the host work that had no name has one: the scheduler's
``paddle_serving.admit``, the prefix index's ``paddle_serving.prefix_peek``
/ ``prefix_lookup`` / ``prefix_insert`` / ``prefix_evict`` and the
collector's ``paddle_serving.gc``, each inside the phase it runs in.

A tiny engine as ``tests/test_serving.py`` builds one, traced on the CPU
with ``python_tracer_level = 0``."""

import gc
import glob
import os
import types

import jax
import numpy as np
import pytest

from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.inference.sampling import SamplerConfig
from paddle_tpu.models import llama as L
from paddle_tpu.observability.runtime import collections
from paddle_tpu.ops.paged_attention import ragged_block_pages
from paddle_tpu.profiler import Profiler, ProfilerTarget
from paddle_tpu.serving import SchedulerConfig, ServingScheduler

INNER = ["cbe.admit", "cbe.plan", "cbe.upload", "cbe.dispatch", "cbe.fence",
         "cbe.unpack", "cbe.audit"]
RECORD_KEYS = {"n", "rounds", "token_slots", "prefill_tokens",
               "decode_tokens", "live_rows", "attended_pages", "grid_steps",
               "causal_pairs", "page_size"}
PAGE, SLOTS, MAX_SEQ, MAX_NEW = 4, 3, 32, 6
#: the host spans of ISSUE 35 and the integer stats each carries
HOST_SPANS = {
    "paddle_serving.admit": {"queued", "handed", "deferred"},
    "paddle_serving.prefix_peek": {"tokens", "blocks"},
    "paddle_serving.prefix_lookup": {"tokens", "blocks"},
    "paddle_serving.prefix_insert": {"tokens", "pages"},
    "paddle_serving.prefix_evict": {"asked", "pages"},
    "paddle_serving.gc": {"generation"},
}
GC = "paddle_serving.gc"


def _build(chunk, fused_tail):
    cfg = L.llama_tiny(num_hidden_layers=6)
    params = L.init_stacked_params(cfg, seed=3)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=MAX_NEW, seed=3),
        num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_SEQ, chunk=chunk,
        prefix_cache=True, fused_tail=fused_tail)
    sched = ServingScheduler(eng, SchedulerConfig(max_queue_depth=64))
    return cfg, params, eng, sched


def _prompts(cfg, n, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, (int(rng.randint(3, 14)),)
                        ).astype(np.int32) for _ in range(n)]


def _drain(sched, params):
    rounds = 0
    while sched.pending:
        sched.step(params)
        rounds += 1
    return rounds


def _warm(cfg, params, sched):
    """Compile outside the traced run (one request, drained)."""
    sched.submit(_prompts(cfg, 1, seed=99)[0], max_new_tokens=2)
    _drain(sched, params)


def _host_events(trace_dir):
    """Every ``cbe.*`` / ``paddle_serving.*`` host event of the newest
    xplane under ``trace_dir``: (name, start, end, stats) by start."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("cbe.", "paddle_serving.")):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(events, outer):
    return [e for e in events
            if e is not outer and outer[1] <= e[1] and e[2] <= outer[2]]


def _traced(tmp_path, body):
    """``body()`` inside a profiler session; the session's host events."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_events(str(tmp_path))


def _traced_serve(tmp_path, chunk, fused_tail, n_requests=5):
    cfg, params, eng, sched = _build(chunk, fused_tail)
    _warm(cfg, params, sched)
    # one traced serve: the events, what the planner laid out for each
    # dispatch (token_row, positions, kv_lens), what was sent and delivered
    run = types.SimpleNamespace(plans=[], log=[])
    # what each scheduler round found and did, seen from outside the spans:
    # its queue's depth on entry and the sequences it retired (in tokens)
    admit, retire = sched._admit, eng._retire

    def admit_spy():
        run.log.append({"queued": len(sched._queue), "retired": []})
        return admit()

    def retire_spy(s, cancelled=False):
        req = eng._live[eng._slot_rid[s]]
        run.log[-1]["retired"].append(
            len(req.prompt) + len(req.tokens[:eng._budget(req)]))
        return retire(s, cancelled)
    sched._admit, eng._retire = admit_spy, retire_spy
    if fused_tail:
        packed = eng._plan_step_packed

        def spy():
            out = packed()
            run.plans.append((out[0][2].copy(), out[0][3].copy(),
                              out[1][0].copy()))
            return out
        eng._plan_step_packed = spy
    else:
        plain = eng._plan_step

        def spy():
            out = plain()
            run.plans.append(tuple(a.copy() for a in out[0][2:5]))
            return out
        eng._plan_step = spy
    run.prompts = _prompts(cfg, n_requests, seed=1)

    def body():
        run.handles = [sched.submit(p, max_new_tokens=MAX_NEW)
                       for p in run.prompts]
        run.rounds = _drain(sched, params)
    run.events = _traced(tmp_path, body)
    run.eng = eng
    run.delivered = sum(len(h.stream.tokens) for h in run.handles)
    return run


@pytest.fixture(scope="module", params=[(1, False), (3, False), (3, True)],
                ids=["chunk1-plain", "chunk3-plain", "chunk3-fused"])
def run(request, tmp_path_factory):
    chunk, fused = request.param
    r = _traced_serve(tmp_path_factory.mktemp("trace"), chunk, fused)
    r.chunk = chunk
    return r


def test_one_round_and_one_engine_step_per_scheduler_step(run):
    rounds = [e for e in run.events if e[0] == "paddle_serving.step"]
    steps = [e for e in run.events if e[0] == "cbe.step"]
    assert len(rounds) == run.rounds == len(steps)
    for rnd, step in zip(rounds, steps):
        assert rnd[1] <= step[1] and step[2] <= rnd[2]      # round > step


def test_phases_once_in_order_disjoint_and_covering(run):
    steps = [e for e in run.events if e[0] == "cbe.step"]
    dispatching, uncovered = 0, 0
    for step in steps:
        inside = _inside(run.events, step)
        inner = [e for e in inside if e[0].startswith("cbe.")]
        names = [e[0] for e in inner]
        if "cbe.dispatch" not in names:
            assert set(names) <= {"cbe.admit", "cbe.audit"}
            continue
        dispatching += 1
        # prefix cache on: every phase's code runs in a dispatching step
        assert names == INNER
        for a, b in zip(inner, inner[1:]):
            assert a[2] <= b[1], (a[0], b[0])               # no overlap
        # nothing of the program's lies BETWEEN two phases: every other
        # span of the step is a phase's child (a collection may start
        # anywhere, between two phases too)
        for e in inside:
            assert e[0] == GC or e[0].startswith("cbe.") or any(
                p[1] <= e[1] and e[2] <= p[2] for p in inner), e[0]
        uncovered += (step[2] - step[1]) - sum(e[2] - e[1] for e in inner)
    assert dispatching == len(run.plans) > 0
    # what is left is the frame's teardown (the uploads' device buffers)
    # and the taps between phases: ~60 us of a ~2 ms CPU step here. No
    # share of the step: on a machine six workers share the clock runs on
    # while the process does not. A phase gone missing around real work (a
    # compile, a transfer) is seconds
    assert 0 <= uncovered < 0.25e9 * dispatching


def _within(e, outers):
    return any(o[1] <= e[1] and e[2] <= o[2] for o in outers)


def test_host_spans_sit_in_their_phases_with_their_stats(run):
    """The table of ISSUE 35, round by round, against what the spies saw:
    the scheduler's ``admit`` (and its ``prefix_peek``) in the round before
    the engine's step, ``prefix_lookup`` in ``cbe.admit``, ``prefix_insert``
    in ``cbe.unpack``, an eviction in either admission; none of them on a
    round whose queue was empty, that admitted nobody and retired nobody."""
    named = {n: [e for e in run.events if e[0] == n] for n in HOST_SPANS}
    for name, keys in HOST_SPANS.items():
        for e in named[name]:
            assert set(e[3]) == keys, (name, e[3])
            assert all(isinstance(v, int) for v in e[3].values())
    phases = {n: [e for e in run.events if e[0] == n]
              for n in ("cbe.admit", "cbe.unpack")}
    assert all(_within(e, phases["cbe.admit"])
               for e in named["paddle_serving.prefix_lookup"])
    assert all(_within(e, phases["cbe.unpack"])
               for e in named["paddle_serving.prefix_insert"])
    assert all(_within(e, named["paddle_serving.admit"])
               for e in named["paddle_serving.prefix_peek"])
    assert all(_within(e, phases["cbe.admit"] + named["paddle_serving.admit"])
               for e in named["paddle_serving.prefix_evict"])

    rounds = [e for e in run.events if e[0] == "paddle_serving.step"]
    assert len(rounds) == len(run.log)
    lengths = [len(p) for p in run.prompts]
    looked, handed_all = [], 0
    for rnd, log in zip(rounds, run.log):
        inside = _inside(run.events, rnd)
        mine = {n: [e for e in inside if e[0] == n] for n in HOST_SPANS}
        step, = [e for e in inside if e[0] == "cbe.step"]
        uploads = [e[3] for e in inside if e[0] == "cbe.upload"]
        admitted = sum(u["admitted"] for u in uploads)
        admits = mine["paddle_serving.admit"]
        if log["queued"] == 0:
            assert not admits and not mine["paddle_serving.prefix_peek"]
        else:
            (admit,) = admits
            assert admit[2] <= step[1]          # before the engine's step
            stats = admit[3]
            assert stats["queued"] == log["queued"]
            assert stats["handed"] == admitted and stats["deferred"] in (0, 1)
            # one sizing walk a request the loop looked at
            assert len(mine["paddle_serving.prefix_peek"]) == \
                stats["handed"] + stats["deferred"]
            handed_all += stats["handed"]
        # the engine walks once for each request it admits
        lookups = mine["paddle_serving.prefix_lookup"]
        assert len(lookups) == admitted
        looked += [e[3] for e in lookups]
        inserts = [e[3] for e in mine["paddle_serving.prefix_insert"]]
        assert [i["tokens"] for i in inserts] == log["retired"]
        # distinct prompts: every full block of a sequence is new to the tree
        assert [i["pages"] for i in inserts] == \
            [t // PAGE for t in log["retired"]]
        if log["queued"] == 0 and admitted == 0 and not log["retired"]:
            assert not [e for e in inside
                        if e[0] in HOST_SPANS and e[0] != GC]
    assert handed_all == len(run.prompts)
    # in the order the requests were sent, nothing cached for any of them
    assert [s["tokens"] for s in looked] == lengths
    assert all(s["blocks"] == 0 for s in looked)
    peeks = [e[3] for e in named["paddle_serving.prefix_peek"]]
    assert sorted(set(p["tokens"] for p in peeks)) == sorted(set(lengths))
    assert sorted(t for log in run.log for t in log["retired"]) == \
        sorted(n + MAX_NEW for n in lengths)


def test_a_repeated_prompt_walks_its_cached_blocks_and_gc_is_named(tmp_path):
    """The same prompt a second time: both walks match ``len(prompt) //
    page`` blocks, the second insert adopts nothing; and with the automatic
    collector off, the session holds one ``paddle_serving.gc`` span a forced
    collection, with its generation."""
    cfg, params, eng, sched = _build(3, False)
    _warm(cfg, params, sched)
    prompt = _prompts(cfg, 1, seed=5)[0][:3].tolist() + [7] * 8     # 11
    was_enabled = gc.isenabled()
    gc.disable()
    collections.reset()

    def body():
        for generation in (2, 0, 2):
            sched.submit(np.asarray(prompt, np.int32), max_new_tokens=MAX_NEW)
            _drain(sched, params)
            gc.collect(generation)
    try:
        events = _traced(tmp_path, body)
    finally:
        if was_enabled:
            gc.enable()
    stats = {n: [e[3] for e in events if e[0] == n] for n in HOST_SPANS}
    n, cached = len(prompt), len(prompt) // PAGE
    assert stats["paddle_serving.prefix_peek"] == [
        {"tokens": n, "blocks": b} for b in (0, cached, cached)]
    assert stats["paddle_serving.prefix_lookup"] == \
        stats["paddle_serving.prefix_peek"]
    assert stats["paddle_serving.prefix_insert"] == [
        {"tokens": n + MAX_NEW, "pages": p}
        for p in ((n + MAX_NEW) // PAGE, 0, 0)]
    assert stats[GC] == [{"generation": g} for g in (2, 0, 2)]
    counted = collections.snapshot()["generations"]
    assert [counted[g]["collections"] for g in "012"] == [1, 0, 2]
    # every collection's span is as long as its counted pause, within the
    # hook's own two clock reads
    spans_ns = sum(e[2] - e[1] for e in events if e[0] == GC)
    paused_ns = sum(counted[g]["pause_ns_total"] for g in "012")
    assert 0 < paused_ns <= spans_ns


def test_a_hit_a_miss_and_a_retirement_keep_their_spans_and_stats(tmp_path):
    """ISSUE 36 (the index reads a sequence once a walk and keys a block by
    its bytes): what ``prefix.walk_ms_per_dispatch`` and
    ``prefix.blocks_walked_per_dispatch`` read is still written, with the
    counts a hand walk gives. A prompt that misses, one that shares the
    first's two leading blocks and then diverges, one that shares nothing:
    ``blocks`` is the full blocks matched, ``pages`` the blocks of the
    retired sequence that were new to the tree."""
    cfg, params, eng, sched = _build(3, False)
    _warm(cfg, params, sched)
    first = list(range(5, 14))                       # 9 tokens: 2 full blocks
    second = first[:2 * PAGE] + [20, 21, 22]         # diverges in block 2
    third = [30, 31, 32, 33, 34]
    prompts = [first, second, third]

    def body():
        for prompt in prompts:
            sched.submit(np.asarray(prompt, np.int32), max_new_tokens=MAX_NEW)
            _drain(sched, params)
    events = _traced(tmp_path, body)
    stats = {n: [e[3] for e in events if e[0] == n] for n in HOST_SPANS}
    walked = [{"tokens": len(p), "blocks": b}
              for p, b in zip(prompts, (0, 2, 0))]
    assert stats["paddle_serving.prefix_peek"] == walked
    assert stats["paddle_serving.prefix_lookup"] == walked
    assert stats["paddle_serving.prefix_insert"] == [
        {"tokens": len(p) + MAX_NEW,
         "pages": (len(p) + MAX_NEW) // PAGE - shared}
        for p, shared in zip(prompts, (0, 2, 0))]


def test_a_model_without_a_state_layout_carries_its_pools_alone(run):
    """Llama names no ``state_layout``: the engine keeps no state pool, what
    its step takes and returns is the page pools and nothing else, and its
    record has the ten keys and none of a state model's three
    (``tests/test_jamba_model.py`` holds those)."""
    mgr = run.eng.mgr
    assert mgr.state is None
    assert mgr.arrays == mgr.pools and len(mgr.pools) == 2
    records = [e[3] for e in run.events if e[0] == "cbe.dispatch"]
    assert records and all(
        not {"state_row_rounds", "state_resets", "state_bytes_per_row"}
        & set(r) for r in records)


def test_record_matches_the_plan_and_the_tokens(run):
    eng = run.eng
    records = [e[3] for e in run.events if e[0] == "cbe.dispatch"]
    assert len(records) == len(run.plans)
    assert all(set(r) == RECORD_KEYS for r in records)
    assert all(isinstance(v, int) for r in records for v in r.values())
    ordinals = [r["n"] for r in records]
    assert ordinals == list(range(ordinals[0], ordinals[0] + len(records)))
    width = eng._table_width
    for rec, (token_row, positions, kv_lens) in zip(records, run.plans):
        assert rec["rounds"] == run.chunk and rec["page_size"] == PAGE
        assert rec["token_slots"] == run.chunk * eng._step_tokens
        # the kernel walks each micro-round's live blocks of G pages, and
        # one step in a round that has none: G page slots a step
        group = ragged_block_pages(PAGE, width)
        assert rec["grid_steps"] == group * sum(
            max(1, sum(-(-min(-(-int(kv_lens[k, s]) // PAGE), width) // group)
                       for s in range(SLOTS)))
            for k in range(run.chunk))
        assert rec["attended_pages"] <= rec["grid_steps"] <= \
            group * (rec["attended_pages"] + rec["rounds"])
        assert 1 <= rec["live_rows"] <= SLOTS
        # the kernel's own test, step by step: page j of row s runs in
        # round k iff j * page_size < kv_lens[k, s]
        assert rec["attended_pages"] == sum(
            1 for k in range(run.chunk) for s in range(SLOTS)
            for j in range(width) if j * PAGE < kv_lens[k, s])
        assert rec["causal_pairs"] == sum(
            int(positions[k, t]) + 1 for k in range(run.chunk)
            for t in range(token_row.shape[1]) if token_row[k, t] >= 0)
        assert rec["prefill_tokens"] + rec["decode_tokens"] == \
            int((token_row >= 0).sum())
    # distinct prompts, nothing cached: every prompt token is fed once
    assert sum(r["prefill_tokens"] for r in records) == \
        sum(len(p) for p in run.prompts)
    decoded = sum(r["decode_tokens"] for r in records)
    assert run.delivered == len(run.prompts) * MAX_NEW
    if run.chunk == 1:
        assert decoded == run.delivered
    else:   # a request that ends inside a dispatch leaves planned rounds
        assert run.delivered <= decoded <= \
            run.delivered + len(run.prompts) * (run.chunk - 1)


UPLOAD_KEYS = {"admitted", "row_state_uploads", "cached_tokens"}


def test_upload_span_counts_admissions_and_no_row_state_upload(run):
    """``cbe.upload`` says how often admission happens and how often it
    costs a transfer: ``admitted`` sums to the requests the traced serve
    brought in, and all-greedy traffic never uploads a row's state."""
    uploads = [e[3] for e in run.events if e[0] == "cbe.upload"]
    assert len(uploads) == len(run.plans)
    assert all(set(u) == UPLOAD_KEYS for u in uploads)
    assert all(isinstance(v, int) for u in uploads for v in u.values())
    assert sum(u["admitted"] for u in uploads) == len(run.prompts)
    assert all(0 <= u["admitted"] <= SLOTS for u in uploads)
    assert all(u["row_state_uploads"] == 0 for u in uploads)
    # ``cached_tokens``: prompt tokens the prefix cache already held for the
    # requests a step admitted; none where it admitted nobody
    assert all(u["cached_tokens"] >= 0 for u in uploads)
    assert all(u["cached_tokens"] == 0 for u in uploads
               if u["admitted"] == 0)


@pytest.mark.parametrize("fused_tail", [False, True], ids=["plain", "fused"])
def test_row_state_uploads_once_for_a_changed_row(tmp_path, fused_tail):
    """A sampled request changes its row's mirrors: the step that admits
    it uploads them (1), every later step of its decode does not, and a
    greedy request into the same slot resets the row with one more."""
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=3)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=MAX_NEW, seed=3),
        num_slots=1, page_size=PAGE, max_seq_len=MAX_SEQ, chunk=2,
        prefix_cache=True, fused_tail=fused_tail)
    prompts = _prompts(cfg, 3, seed=4)
    subs = [dict(sampler=SamplerConfig(temperature=0.8, seed=11)), {}, {}]
    eng.submit(prompts[0], **subs[0])       # compile the full epilogue
    while not eng.collect():
        eng.step(params)
    eng.submit(prompts[0])                  # leave the row greedy again
    while not eng.collect():
        eng.step(params)

    def body():
        for p, sub in zip(prompts, subs):
            eng.submit(p, **sub)
        done = 0
        while done < len(prompts):
            eng.step(params)
            done += len(eng.collect())
    uploads = [e[3] for e in _traced(tmp_path, body)
               if e[0] == "cbe.upload"]
    admitting = [u for u in uploads if u["admitted"]]
    # one slot: sampled (dirty), greedy after sampled (the reset: dirty),
    # greedy after greedy (nothing to send)
    assert [u["admitted"] for u in admitting] == [1, 1, 1]
    assert [u["row_state_uploads"] for u in admitting] == [1, 1, 0]
    assert sum(u["row_state_uploads"] for u in uploads) == 2


def test_record_counts_one_grid_step_for_an_empty_round():
    """A serve never plans a micro-round without a token while a row is
    live, so the empty round is laid out by hand: it costs the kernel the
    one step that zeroes its output, a starved row costs none, and a span
    past the table clamps to the table's width. The table here is one
    block wide (G = its 8 pages), so every live row is one step."""
    _, _, eng, _ = _build(3, False)
    width = eng._table_width
    assert ragged_block_pages(PAGE, width) == width
    kv_lens = np.zeros((3, SLOTS), np.int32)
    kv_lens[0] = [5, 0, 9]                      # 2 + 0 + 3 pages
    kv_lens[2] = [0, PAGE * width + 3, 0]       # past the table: width
    token_row = np.full((3, eng._step_tokens), -1, np.int32)
    positions = np.zeros_like(token_row)
    token_row[0, :2], positions[0, :2] = [0, 2], [4, 8]
    token_row[2, 0], positions[2, 0] = 1, PAGE * width + 2
    rec = eng._dispatch_record(token_row, positions, kv_lens,
                               [1, 1, 1], [0, 0, 0])
    assert rec["rounds"] == 3
    assert rec["attended_pages"] == 5 + width
    # two live rows, the empty round's one step, one live row: G slots each
    assert rec["grid_steps"] == (2 + 1 + 1) * width


def test_record_counts_the_page_slots_of_rows_of_several_blocks():
    """``grid_steps`` is the page slots the kernel's steps hold, G a block,
    a row's last block as short as its pages leave it: a table of 64 pages
    of 4 keys folds G = 32 pages a step."""
    cfg = L.llama_tiny(num_hidden_layers=2)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=MAX_NEW, seed=3),
        num_slots=SLOTS, page_size=PAGE, max_seq_len=256, chunk=2,
        prefix_cache=True)
    width = eng._table_width
    assert (width, ragged_block_pages(PAGE, width)) == (64, 32)
    kv_lens = np.zeros((2, SLOTS), np.int32)
    # pages 1, 32 and 33: blocks 1, 1 and 2; then 45 pages, past the table
    # (64 pages: 2 blocks) and a starved row
    kv_lens[0] = [3, 128, 129]
    kv_lens[1] = [180, PAGE * width + 9, 0]
    token_row = np.full((2, eng._step_tokens), -1, np.int32)
    positions = np.zeros_like(token_row)
    token_row[0, :3], positions[0, :3] = [0, 1, 2], [2, 127, 128]
    token_row[1, :2], positions[1, :2] = [0, 1], [179, PAGE * width - 1]
    rec = eng._dispatch_record(token_row, positions, kv_lens,
                               [1, 1, 1], [0, 0, 0])
    assert rec["attended_pages"] == 1 + 32 + 33 + 45 + 64
    assert rec["grid_steps"] == 32 * (1 + 1 + 2 + 2 + 2)


@pytest.mark.parametrize("hook", [True, False], ids=["hook", "nohook"])
@pytest.mark.parametrize("fused_tail", [False, True], ids=["plain", "fused"])
def test_token_streams_identical_with_and_without_a_session(
        tmp_path, fused_tail, hook):
    """... and with and without the collector's hook: the traced serve runs
    with it (its engine installed it), the plain one without."""
    traced = _traced_serve(tmp_path, 3, fused_tail)
    cfg, params, eng, sched = _build(3, fused_tail)
    assert collections.installed
    if not hook:
        gc.callbacks.remove(collections._hook)
    try:
        _warm(cfg, params, sched)
        handles = [sched.submit(p, max_new_tokens=MAX_NEW)
                   for p in traced.prompts]
        _drain(sched, params)
    finally:
        if not hook:
            gc.callbacks.append(collections._hook)
    assert [list(h.stream.tokens) for h in handles] == \
        [list(h.stream.tokens) for h in traced.handles]


def test_profiler_capture_holds_each_round_once(tmp_path):
    """Under ``paddle_tpu.profiler.Profiler`` the light round span enters
    ONE annotation (and one HostSpan), the engine phases ride along."""
    cfg, params, eng, sched = _build(3, False)
    _warm(cfg, params, sched)
    prof = Profiler(targets=[ProfilerTarget.TPU], log_dir=str(tmp_path))
    with prof:
        for p in _prompts(cfg, 3, seed=2):
            sched.submit(p, max_new_tokens=MAX_NEW)
        rounds = _drain(sched, params)
    events = _host_events(str(tmp_path))
    count = {n: sum(1 for e in events if e[0] == n)
             for n in ["paddle_serving.step", "cbe.step"] + INNER}
    assert count["paddle_serving.step"] == rounds == count["cbe.step"]
    dispatches = count["cbe.dispatch"]
    assert 0 < dispatches <= rounds
    assert all(count[n] == dispatches for n in INNER[1:6])
    assert all(set(e[3]) == RECORD_KEYS
               for e in events if e[0] == "cbe.dispatch")
    assert sum(1 for s in prof.collected_spans
               if s.name == "paddle_serving.step") == rounds


def test_phase_is_inert_outside_a_session():
    from paddle_tpu.profiler.record import RecordEvent, phase
    with phase("cbe.step"), phase("cbe.dispatch", n=1, rounds=2):
        pass
    light = RecordEvent("paddle_serving.step", light=True)
    for _ in range(2):                  # reusable, as the scheduler's is
        with light:
            assert light._start_ns is None      # no HostSpan off-capture
        assert light._jax_ann is None


# ---------------------------------------------------------------------------
# a model with window layers and experts (models.afmoe): what its records add
# ---------------------------------------------------------------------------
def _afmoe_engine(chunk=3):
    from paddle_tpu.models import afmoe as A
    cfg = A.afmoe_tiny()                # window 8: sliding, sliding, full
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=MAX_NEW, seed=3),
        num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_SEQ, chunk=chunk,
        prefix_cache=True)
    return A, cfg, eng


def test_afmoe_record_counts_the_windows_pages_by_hand():
    """Two of three layers slide over 8 positions: the record's page counts
    are the per-layer MEAN, and ``window_skipped_pages`` the mean of what
    the window left out, for a plan laid out by hand."""
    _, _, eng = _afmoe_engine()
    kv_lens = np.zeros((3, SLOTS), np.int32)
    token_row = np.full((3, 8), -1, np.int32)   # a wider packed axis
    positions = np.zeros_like(token_row)
    # round 0: row 0 decodes at position 20 (6 pages; the window reaches
    # positions 13..20 = pages 3..5), row 2 prefills positions 2..6 (2
    # pages, all inside every token's window)
    kv_lens[0] = [21, 0, 7]
    token_row[0, :6] = [0, 2, 2, 2, 2, 2]
    positions[0, :6] = [20, 2, 3, 4, 5, 6]
    # round 1: nothing; round 2: row 1 prefills positions 9..13 (4 pages;
    # its earliest token sees 2..9, so the list starts at page 0)
    kv_lens[2] = [0, 14, 0]
    token_row[2, :5] = 1
    positions[2, :5] = [9, 10, 11, 12, 13]
    rec = eng._dispatch_record(token_row, positions, kv_lens,
                               [1, 0, 0], [0, 5, 5])
    full, windowed = 6 + 2 + 4, 3 + 2 + 4
    assert set(rec) == RECORD_KEYS | {"window_skipped_pages"}
    assert rec["attended_pages"] == round((full + 2 * windowed) / 3) == 10
    assert rec["window_skipped_pages"] == round(
        full - (full + 2 * windowed) / 3) == 2
    # every layer's kernel takes one step a live row (a table of 8 pages
    # is one block) and one in the empty round: 4 steps of G = 8 slots
    assert rec["grid_steps"] == 8 * (2 + 1 + 1)
    pairs_full = 21 + (3 + 4 + 5 + 6 + 7) + (10 + 11 + 12 + 13 + 14)
    pairs_win = 8 + (3 + 4 + 5 + 6 + 7) + 5 * 8
    assert rec["causal_pairs"] == round((pairs_full + 2 * pairs_win) / 3)
    # a Llama engine's record of the same plan has no such key
    _, _, llama_eng, _ = _build(3, False)
    assert set(llama_eng._dispatch_record(
        token_row, positions, kv_lens, [1, 0, 0], [0, 5, 5])) == RECORD_KEYS


def test_afmoe_unpack_span_carries_the_routing_stats(tmp_path):
    """``cbe.unpack`` of a model with experts: four integer sums over the
    dispatch's rounds x expert layers; Llama's span carries none."""
    A, cfg, eng = _afmoe_engine()
    # laid out by hand: 2 rounds x 2 expert layers of (hit, max, made)
    aux = np.array([[[5, 3, 12], [4, 6, 12]], [[0, 0, 0], [1, 2, 2]]])
    assert eng._expert_stats(aux) == {
        "experts_hit": 10, "expert_calls": 4, "expert_assignments": 26,
        "max_expert_load": 11}
    params = A.init_stacked_params(cfg, seed=3)
    sched = ServingScheduler(eng, SchedulerConfig(max_queue_depth=64))
    _warm(cfg, params, sched)
    plans, plain = [], eng._plan_step

    def spy():
        out = plain()
        plans.append(out[0][2].copy())
        return out
    eng._plan_step = spy

    def body():
        for p in _prompts(cfg, 4, seed=1):
            sched.submit(p, max_new_tokens=MAX_NEW)
        _drain(sched, params)
    events = _traced(tmp_path, body)
    unpacks = [e[3] for e in events if e[0] == "cbe.unpack"]
    records = [e[3] for e in events if e[0] == "cbe.dispatch"]
    assert len(unpacks) == len(plans) == len(records) > 0
    for stats, token_row, rec in zip(unpacks, plans, records):
        assert set(stats) == {"experts_hit", "expert_calls",
                              "expert_assignments", "max_expert_load"}
        assert stats["expert_calls"] == 3 * cfg.num_expert_layers
        # dropless: every packed token's every choice, in every layer
        assert stats["expert_assignments"] == int((token_row >= 0).sum()) \
            * cfg.num_experts_per_tok * cfg.num_expert_layers
        assert stats["max_expert_load"] <= stats["expert_assignments"]
        assert 0 < stats["experts_hit"] <= \
            stats["expert_calls"] * cfg.num_experts
        assert "window_skipped_pages" in rec


def test_llama_unpack_span_carries_no_stats(run):
    assert all(e[3] == {} for e in run.events if e[0] == "cbe.unpack")


# ---------------------------------------------------------------------------
# a model whose router has zero-compute experts (models.longcat_flash): two
# more routing stats; the other expert families' stats stay key for key
# ---------------------------------------------------------------------------
FOUR_STATS = {"experts_hit", "expert_calls", "expert_assignments",
              "max_expert_load"}


def _expert_engine(family):
    import importlib
    module = importlib.import_module(f"paddle_tpu.models.{family}")
    cfg = getattr(module, family + "_tiny")()
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=MAX_NEW, seed=3),
        num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_SEQ, chunk=3,
        prefix_cache=True)
    return module, cfg, eng


@pytest.mark.parametrize("family,extra", [
    ("afmoe", set()), ("axk1", set()),
    ("longcat_flash", {"zero_expert_assignments", "router_assignments"})])
def test_unpack_stats_by_expert_family(tmp_path, family, extra):
    """``cbe.unpack`` of each expert family's dispatches: the four stats of
    the experts HELD for ``afmoe`` and ``axk1``, key for key what they were;
    for a router with zero-compute experts also the assignments that chose
    an identity and the router's own (valid tokens x k), and held +
    identities are all of them where every routed expert is held."""
    module, cfg, eng = _expert_engine(family)
    params = module.init_stacked_params(cfg, seed=3)
    sched = ServingScheduler(eng, SchedulerConfig(max_queue_depth=64))
    _warm(cfg, params, sched)
    plans, plain = [], eng._plan_step

    def spy():
        out = plain()
        plans.append(out[0][2].copy())
        return out
    eng._plan_step = spy

    def body():
        for p in _prompts(cfg, 4, seed=1):
            sched.submit(p, max_new_tokens=MAX_NEW)
        _drain(sched, params)
    events = _traced(tmp_path, body)
    unpacks = [e[3] for e in events if e[0] == "cbe.unpack"]
    assert len(unpacks) == len(plans) > 0
    assert all(set(u) == FOUR_STATS | extra for u in unpacks)
    if extra:
        for stats, token_row in zip(unpacks, plans):
            assert stats["expert_calls"] == 3 * cfg.num_layers
            assert stats["router_assignments"] == int(
                (token_row >= 0).sum()) * cfg.moe_topk * cfg.num_layers
            assert stats["expert_assignments"] \
                + stats["zero_expert_assignments"] \
                == stats["router_assignments"]
        assert sum(u["zero_expert_assignments"] for u in unpacks) > 0
        assert sum(u["expert_assignments"] for u in unpacks) > 0


def test_expert_stats_of_a_record_with_zero_compute_experts_by_hand():
    """Laid out by hand: 2 rounds x 2 layers of (hit, max, made, identities,
    the router's); a record of three numbers a call gives the four stats it
    always gave."""
    stats = ContinuousBatchingEngine._expert_stats
    aux = np.array([[[5, 3, 12, 20, 48], [4, 6, 12, 16, 48]],
                    [[0, 0, 0, 4, 12], [1, 2, 2, 3, 12]]])
    assert stats(aux) == {
        "experts_hit": 10, "expert_calls": 4, "expert_assignments": 26,
        "max_expert_load": 11, "zero_expert_assignments": 43,
        "router_assignments": 120}
    assert stats(aux[..., :3]) == {
        "experts_hit": 10, "expert_calls": 4, "expert_assignments": 26,
        "max_expert_load": 11}
