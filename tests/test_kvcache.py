"""Prefix cache for the paged KV pool (paddle_tpu.kvcache): radix index,
refcounted shared ownership, copy-on-write, LRU eviction — and the e2e
acceptance bar: byte-identical generation with the cache enabled vs
disabled, >=50% of prefill tokens skipped on warm shared-prefix traffic,
and the page conservation invariant holding after every engine step."""

import re
from collections.abc import Sequence

import numpy as np
import pytest

from paddle_tpu.kvcache import (LRUEvictionPolicy, PrefixCache, RadixNode,
                                RefcountedKVCacheManager, RadixTree)



def _mgr(num_pages=12, page_size=4):
    # tiny device arrays: 1 layer, 1 kv head, dim 2 — metadata is the test
    return RefcountedKVCacheManager(1, num_pages, page_size, 1, 2)


def _toks(*blocks):
    out = []
    for b in blocks:
        out.extend(b)
    return out


# ---------------------------------------------------------------------------
# radix tree
# ---------------------------------------------------------------------------

def test_radix_match_full_blocks_only():
    t = RadixTree(page_size=4)
    t.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 11])
    assert [n.page for n in t.match([1, 2, 3, 4, 5, 6, 7, 8, 9])] == [10, 11]
    # divergence after one block
    assert [n.page for n in t.match([1, 2, 3, 4, 9, 9, 9, 9])] == [10]
    # partial block never matches
    assert t.match([1, 2, 3]) == []
    assert t.match([2, 2, 3, 4]) == []


def test_radix_insert_reports_duplicates_not_adoption():
    t = RadixTree(page_size=2)
    adopted, dup = t.insert([1, 2, 3, 4], [5, 6])
    assert (adopted, dup) == ([5, 6], [])
    # same blocks under different pages: nothing adopted, dups reported
    adopted, dup = t.insert([1, 2, 3, 4, 9, 9], [7, 8, 9])
    assert adopted == [9] and dup == [7, 8]
    assert len(t) == 3


def test_radix_remove_leaf_only():
    t = RadixTree(page_size=2)
    t.insert([1, 2, 3, 4], [5, 6])
    inner = t.match([1, 2])[0]
    with pytest.raises(ValueError):
        t.remove(inner)
    leaf = t.match([1, 2, 3, 4])[-1]
    t.remove(leaf)
    assert t.match([1, 2, 3, 4]) == [inner]
    t.remove(inner)          # now a leaf
    assert len(t) == 0


class _TupleKeyedTree(RadixTree):
    """The index as it was before ISSUE 36, kept here as the reference:
    every block's key built as ``tuple(int(t) ...)``, a token at a time.
    ``remove`` / ``leaves`` / ``tick`` never look inside a key."""

    def _block(self, tokens, i):
        ps = self.page_size
        return tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])

    def match(self, tokens, touch=True):
        node, out = self.root, []
        stamp = self.tick() if touch else None
        for i in range(len(tokens) // self.page_size):
            child = node.children.get(self._block(tokens, i))
            if child is None:
                break
            if stamp is not None:
                child.last_access = stamp
            out.append(child)
            node = child
        return out

    def insert(self, tokens, pages):
        node = self.root
        stamp = self.tick()
        adopted, dup = [], []
        for i in range(min(len(tokens) // self.page_size, len(pages))):
            blk = self._block(tokens, i)
            child = node.children.get(blk)
            if child is None:
                child = RadixNode(parent=node, key=blk, page=int(pages[i]),
                                  last_access=stamp)
                node.children[blk] = child
                self._by_page[child.page] = child
                adopted.append(child.page)
            else:
                child.last_access = stamp
                if int(pages[i]) != child.page:
                    dup.append(int(pages[i]))
            node = child
        return adopted, dup


_AS = {"int32": lambda seq: np.asarray(seq, np.int32),
       "int64": lambda seq: np.asarray(seq, np.int64),
       "list": lambda seq: [int(t) for t in seq]}


@pytest.mark.parametrize("kind", ["int32", "int64", "list", "by_turns"])
def test_radix_walks_equal_the_tuple_keyed_reference(kind):
    """ISSUE 36: a key is equal exactly when the block's tokens are, so
    the index keyed by a block's bytes and the one keyed by its tuple of
    Python ints answer alike under one seeded interleaving of matches
    (touching and peeking), inserts and LRU evictions, whatever type the
    caller hands the sequence in: the matched pages, ``(adopted,
    duplicates)``, the eviction order, the size and every node's stamp
    agree at every step."""
    ps = 4
    rng = np.random.RandomState(36)
    new, ref = RadixTree(ps), _TupleKeyedTree(ps)
    policy = LRUEvictionPolicy()
    # ids whose int32 bytes differ in every byte position
    vocab = np.array([0, 1, 2, 255, 256, 65536, 2 ** 24, 2 ** 31 - 1])
    docs = [vocab[rng.randint(0, len(vocab), 40)] for _ in range(5)]
    kinds = sorted(_AS)
    next_page = 0
    for step in range(600):
        doc = docs[rng.randint(len(docs))]
        seq = np.concatenate([                   # a shared prefix of random
            doc[:rng.randint(0, len(doc) + 1)],  # length and a ragged tail
            vocab[rng.randint(0, len(vocab), rng.randint(0, 9))]])
        as_kind = _AS[kinds[step % 3] if kind == "by_turns" else kind]
        op = rng.rand()
        if op < 0.4:
            n = rng.randint(0, len(seq) // ps + 2)   # short AND long tables
            pages = list(range(next_page, next_page + n))
            next_page += n
            assert new.insert(as_kind(seq), pages) \
                == ref.insert(seq.tolist(), pages)
        elif op < 0.8:
            touch = bool(rng.randint(2))
            assert [nd.page for nd in new.match(as_kind(seq), touch=touch)] \
                == [nd.page for nd in ref.match(seq.tolist(), touch=touch)]
        else:
            n = rng.randint(1, 6)
            protect = [p for p in ref.pages if rng.rand() < 0.2]
            victims = policy.select(new, lambda _p: 0, n, protect)
            ref_victims = policy.select(ref, lambda _p: 0, n, protect)
            assert [v.page for v in victims] == [v.page for v in ref_victims]
            for v, rv in zip(victims, ref_victims):
                new.remove(v)
                ref.remove(rv)
        assert len(new) == len(ref)
        assert {p: nd.last_access for p, nd in new._by_page.items()} \
            == {p: nd.last_access for p, nd in ref._by_page.items()}
    assert len(new) > 20 and next_page > 5 * len(new)   # it grew AND evicted


class _CountedTokens(Sequence):
    """A token sequence that counts how often it is read, by what."""

    def __init__(self, tokens):
        self._tokens = np.asarray(tokens, np.int32)
        self.reads = {"getitem": 0, "iter": 0, "array": 0}

    def __len__(self):
        return len(self._tokens)

    def __getitem__(self, i):
        self.reads["getitem"] += 1
        return self._tokens[i]

    def __iter__(self):
        self.reads["iter"] += 1
        return iter(self._tokens)

    def __array__(self, dtype=None, copy=None):
        self.reads["array"] += 1
        return self._tokens.astype(dtype or np.int32, copy=bool(copy))


@pytest.mark.parametrize("walk", ["match", "peek", "insert", "reinsert"])
def test_a_walk_reads_its_sequence_once(walk):
    """ISSUE 36: ``match`` and ``insert`` turn the sequence into block
    keys ONCE, not once a block or once a token: 64 blocks, at most two
    reads (numpy may ask ``__array__`` twice), whatever the walk finds."""
    ps, n_blocks = 4, 64
    toks = np.random.RandomState(1).randint(0, 1000, ps * n_blocks + 3)
    tree = RadixTree(ps)
    if walk != "insert":
        tree.insert(toks, list(range(n_blocks)))
    counted = _CountedTokens(toks)
    if walk in ("match", "peek"):
        assert len(tree.match(counted, touch=walk == "match")) == n_blocks
    else:
        adopted, dup = tree.insert(counted, list(range(100, 100 + n_blocks)))
        assert len(adopted if walk == "insert" else dup) == n_blocks
    assert 1 <= sum(counted.reads.values()) <= 2, counted.reads


# ---------------------------------------------------------------------------
# refcounted pool
# ---------------------------------------------------------------------------

def test_shared_allocation_refcounts_and_release():
    mgr = _mgr(num_pages=8, page_size=4)
    a = mgr.allocate("a", 8)                       # 2 owned pages
    b = mgr.allocate("b", 12, shared=a)            # shares both + 1 fresh
    assert b[:2] == a and len(b) == 3
    assert mgr.refcount(a[0]) == 2 and mgr.refcount(b[2]) == 1
    mgr.free("a")
    assert mgr.refcount(a[0]) == 1                 # b still holds them
    mgr.check_conservation()
    mgr.free("b")
    assert mgr.num_free_pages == mgr.usable_pages  # nothing cached
    mgr.check_conservation()


def test_cached_pages_survive_release_and_evict_to_free():
    mgr = _mgr(num_pages=6, page_size=4)
    pages = mgr.allocate("a", 8)
    for p in pages:
        mgr.adopt_cached(p)
    mgr.free("a")
    assert mgr.num_free_pages == mgr.usable_pages - 2
    assert mgr.num_cached_pages == 2
    mgr.check_conservation()
    mgr.evict_cached(pages[0])
    assert mgr.num_free_pages == mgr.usable_pages - 1
    mgr.check_conservation()


def test_conservation_detects_violations():
    mgr = _mgr()
    mgr.allocate("a", 4)
    # corruption injection MUST bypass the public surface — that is the
    # point of the test  # tpu-lint: disable=private-kvcache
    mgr._free.append(mgr._tables["a"][0])          # free a live page
    with pytest.raises(RuntimeError, match="overlap"):
        mgr.check_conservation()
    mgr = _mgr()
    mgr.allocate("a", 4)
    mgr._tables.pop("a")                           # leak: refs != tables
    with pytest.raises(RuntimeError, match="diverge"):
        mgr.check_conservation()


def test_copy_page_copies_device_content():
    import jax.numpy as jnp
    mgr = _mgr(num_pages=6, page_size=4)
    src, dst = 1, 2
    mgr.k_pages = mgr.k_pages.at[:, src].set(7.0)
    mgr.v_pages = mgr.v_pages.at[:, src].set(3.0)
    mgr.copy_page(src, dst)
    assert float(jnp.abs(mgr.k_pages[:, dst] - 7.0).max()) == 0.0
    assert float(jnp.abs(mgr.v_pages[:, dst] - 3.0).max()) == 0.0


# ---------------------------------------------------------------------------
# prefix cache orchestration
# ---------------------------------------------------------------------------

def test_lookup_caps_full_prompt_match_with_cow():
    mgr = _mgr(num_pages=12, page_size=4)
    cache = PrefixCache(mgr)
    prompt = list(range(1, 9))                     # exactly 2 blocks
    table = mgr.allocate(0, 8)
    cache.insert(prompt, table)
    mgr.free(0)
    shared, n_cached, cow = cache.lookup(prompt)
    # full match: last page goes copy-on-write, one token recomputed
    assert shared == table[:1] and n_cached == 7 and cow == table[1]
    # longer prompt with the same prefix: plain 2-page share, no COW
    shared, n_cached, cow = cache.lookup(prompt + [77])
    assert shared == table and n_cached == 8 and cow is None


def test_lru_eviction_prefers_coldest_leaf():
    mgr = _mgr(num_pages=12, page_size=4)
    cache = PrefixCache(mgr)
    pa = _toks(range(4), range(4))                 # prefix A: 2 blocks
    pb = _toks(range(10, 14), range(20, 24))       # prefix B: 2 blocks
    ta = mgr.allocate("a", 8)
    cache.insert(pa, ta)
    mgr.free("a")
    tb = mgr.allocate("b", 8)
    cache.insert(pb, tb)
    mgr.free("b")
    cache.lookup(pa + [9])                         # touch A: B is now LRU
    assert cache.evict(1) == 1
    # B's leaf died; A fully resident
    assert len(cache.tree.match(pb, touch=False)) == 1
    assert len(cache.tree.match(pa, touch=False)) == 2
    mgr.check_conservation()


def test_evict_respects_protect_and_pinned_pages():
    mgr = _mgr(num_pages=12, page_size=4)
    cache = PrefixCache(mgr)
    prompt = _toks(range(4), range(4))
    table = mgr.allocate("a", 8)
    cache.insert(prompt, table)
    mgr.free("a")
    # protected pages never die, so only the unprotected leaf can go
    assert cache.evict(5, protect=table) == 0
    # pin via a live sharer: nothing evictable at all
    mgr.allocate("b", 8, shared=table)
    assert cache.evict(5) == 0
    mgr.free("b")
    assert cache.evict(5) == 2
    assert mgr.num_free_pages == mgr.usable_pages
    mgr.check_conservation()


# ---------------------------------------------------------------------------
# satellite: randomized interleaving property test
# ---------------------------------------------------------------------------

def test_pool_invariants_random_interleavings():
    """submit/draft(grow+verify/rollback)/extend/accept/reject/cancel/
    retire/evict in random order: conservation holds after every op,
    refcounts never negative (check_conservation cross-checks refs
    against block-table occupancy, so a page in two tables with a dead
    refcount cannot hide). The ``spec`` op is the speculative row's
    lifecycle at pool level: grow the table for a drafted span past the
    committed length, then commit a random prefix and truncate the rest
    — exactly what the engine's verify/rollback does per row.

    The HBM ledger rides along: after EVERY op (mid-draft grow/truncate
    included) its byte conservation audit — free + live + spec + cached
    bytes == pool bytes — must balance too, with speculative tails
    (pages past each sequence's committed length) split into their own
    class."""
    from paddle_tpu.observability.memory import MemoryLedger
    for seed in (0, 1, 2):
        rng = np.random.RandomState(seed)
        mgr = _mgr(num_pages=16, page_size=2)
        cache = PrefixCache(mgr)
        led = MemoryLedger()
        live = {}
        next_sid = 0

        def audit_bytes():
            # committed reservation per live sequence: pages covering
            # its committed length — anything beyond is a drafted tail
            led.observe(mgr, reserved={
                sid: mgr.pages_for(mgr.seq_len(sid)) for sid in live})

        for _ in range(300):
            op = rng.choice(["submit", "extend", "retire", "cancel",
                             "evict", "spec"],
                            p=[0.3, 0.1, 0.2, 0.1, 0.1, 0.2])
            if op == "submit":
                lp = int(rng.randint(1, 9))
                prompt = [int(t) for t in rng.randint(0, 3, lp)]
                budget = int(rng.randint(1, 5))
                total = lp + budget
                if mgr.pages_for(total) > mgr.usable_pages:
                    continue
                shared, n_cached, cow = cache.lookup(prompt)
                need = mgr.pages_for(total) - len(shared)
                if mgr.num_free_pages < need:
                    cache.evict(need - mgr.num_free_pages,
                                protect=shared + [cow])
                if mgr.num_free_pages < need and cow is not None:
                    cow, n_cached = None, len(shared) * mgr.page_size
                    cache.evict(need - mgr.num_free_pages, protect=shared)
                if mgr.num_free_pages < need:
                    continue                     # engine would defer
                table = mgr.allocate(next_sid, total, shared=shared)
                if cow is not None:
                    mgr.copy_page(cow, table[len(shared)])
                live[next_sid] = {"prompt": prompt, "gen": [],
                                  "budget": budget}
                next_sid += 1
            elif op == "extend" and live:
                sid = int(rng.choice(list(live)))
                try:
                    mgr.extend(sid, 1)
                except MemoryError:
                    cache.evict(1)
                    try:
                        mgr.extend(sid, 1)
                    except MemoryError:
                        continue             # genuinely full: defer
                live[sid]["gen"].append(int(rng.randint(0, 3)))
            elif op == "retire" and live:
                sid = int(rng.choice(list(live)))
                st = live.pop(sid)
                cache.insert(st["prompt"] + st["gen"], mgr._tables[sid])
                mgr.free(sid)
            elif op == "cancel" and live:
                sid = int(rng.choice(list(live)))
                live.pop(sid)
                mgr.free(sid)                    # cancelled: no insert
            elif op == "evict":
                cache.evict(int(rng.randint(1, 4)))
            elif op == "spec" and live:
                sid = int(rng.choice(list(live)))
                cur = mgr.seq_len(sid)
                span = int(rng.randint(1, 6))
                try:
                    mgr.grow_to(sid, cur + span)     # draft the span
                except MemoryError:
                    cache.evict(mgr.pages_for(cur + span)
                                - len(mgr._tables[sid]))
                    try:
                        mgr.grow_to(sid, cur + span)
                    except MemoryError:
                        mgr.check_conservation()
                        audit_bytes()
                        continue                 # engine clamps instead
                mgr.check_conservation()         # mid-draft books balance
                audit_bytes()                    # ... in bytes too (the
                # drafted tail shows up as kv_spec until the verify)
                tail = mgr.pages_for(cur + span) - mgr.pages_for(cur)
                assert led.class_bytes("kv_spec") == \
                    tail * mgr.page_nbytes
                accepted = int(rng.randint(0, span + 1))
                committed = cur + accepted
                # verify: commit the accepted prefix, roll the rest back
                mgr.truncate_pages(sid, mgr.pages_for(committed))
                mgr._lens[sid] = committed
                live[sid]["gen"].extend(
                    int(t) for t in rng.randint(0, 3, accepted))
            mgr.check_conservation()
            audit_bytes()
        for sid in list(live):
            mgr.free(sid)
        live.clear()
        mgr.check_conservation()
        audit_bytes()
        assert led.class_bytes("kv_live") == 0
        assert led.class_bytes("kv_spec") == 0
        # everything unreferenced: full eviction must drain to all-free
        cache.evict(mgr.usable_pages)
        assert mgr.num_free_pages == mgr.usable_pages
        led.observe(mgr)
        assert led.class_bytes("kv_free") == \
            mgr.usable_pages * mgr.page_nbytes


# ---------------------------------------------------------------------------
# engine integration (e2e acceptance)
# ---------------------------------------------------------------------------

def _setup_engine(prefix_cache, max_new=6, num_slots=2, num_pages=None,
                  seed=3, speculative=False):
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.models import llama as L
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=seed)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=max_new),
        num_slots=num_slots, page_size=4, max_seq_len=32, chunk=3,
        num_pages=num_pages, prefix_cache=prefix_cache,
        speculative=speculative)
    return cfg, params, eng


def _shared_prefix_prompts(cfg, n=4, sys_len=12, seed=0):
    rng = np.random.RandomState(seed)
    sys_p = rng.randint(1, cfg.vocab_size, (sys_len,)).astype(np.int32)
    return [np.concatenate([sys_p,
                            rng.randint(1, cfg.vocab_size,
                                        (int(rng.randint(2, 8)),)
                                        ).astype(np.int32)])
            for _ in range(n)]


@pytest.mark.parametrize("speculative", [False, True],
                         ids=["plain", "speculative"])
def test_retire_hands_the_index_one_array(speculative):
    """ISSUE 36: a retiring request's prompt and kept output reach
    ``cache.insert`` as ONE integer array (the walk reads it once; no
    per-token conversion ahead of it): as long as the prompt plus the
    output, one token short under speculation (the verify bonus's K/V
    slot may never have been written)."""
    cfg, params, eng = _setup_engine(prefix_cache=True,
                                     speculative=speculative)
    handed = []
    insert = eng.cache.insert

    def counting_insert(tokens, pages):
        handed.append(tokens)
        return insert(tokens, pages)

    eng.cache.insert = counting_insert
    prompts = _shared_prefix_prompts(cfg, n=3)
    outs = eng.serve(params, prompts)
    assert len(handed) == len(prompts)
    for got in handed:
        assert isinstance(got, np.ndarray) and got.dtype.kind == "i"
    want = [np.concatenate([p, np.asarray(o, np.int32)])
            [:-1 if speculative else None] for p, o in zip(prompts, outs)]
    # retirement order is not submission order
    assert sorted(a.tolist() for a in handed) \
        == sorted(a.tolist() for a in want)
    eng.mgr.check_conservation()


def test_generation_byte_identical_cache_on_vs_off():
    """THE acceptance bar: same prompts, same seed — the cache-enabled
    engine (cold AND warm waves, COW included) produces exactly the
    token lists of the cache-disabled engine."""
    cfg, params, eng_off = _setup_engine(prefix_cache=False)
    _, _, eng_on = _setup_engine(prefix_cache=True)
    prompts = _shared_prefix_prompts(cfg)
    # one prompt of exactly 4 pages forces the full-match COW path on
    # its second wave
    prompts.append(prompts[0][:16])
    assert len(prompts[-1]) == 16
    for wave in range(2):
        expect = eng_off.serve(params, prompts)
        got = eng_on.serve(params, prompts)
        assert got == expect, f"wave {wave} diverged"
    st = eng_on.cache.snapshot()
    assert st["hits"] > 0 and st["cow_copies"] > 0
    assert st["cached_tokens"] > 0
    eng_on.mgr.check_conservation()


def test_warm_wave_skips_half_the_prefill_tokens():
    """Shared-system-prompt traffic: the warm wave computes < 50% of the
    prefill tokens the cold wave did (>= 50% skipped)."""
    cfg, params, eng = _setup_engine(prefix_cache=True)
    prompts = _shared_prefix_prompts(cfg, sys_len=16)
    eng.serve(params, prompts)
    cold = eng._prefill_tokens
    eng.serve(params, prompts)
    warm = eng._prefill_tokens - cold
    assert warm <= cold / 2, (cold, warm)
    assert eng.cache.stats["hits"] >= len(prompts)


def test_cache_disabled_engine_unchanged():
    """prefix_cache=False keeps the plain manager: no cache attribute
    consulted, no refcount bookkeeping."""
    from paddle_tpu.ops.paged_attention import PagedKVCacheManager
    _, _, eng = _setup_engine(prefix_cache=False)
    assert eng.cache is None
    assert type(eng.mgr) is PagedKVCacheManager


def test_over_reject_uses_whole_pool_capacity():
    """Satellite fix: a request bigger than the WHOLE pool raises; one
    that merely exceeds the transient free count (pool full of cached
    pages) evicts and admits instead of raising."""
    cfg, params, eng = _setup_engine(prefix_cache=True, max_new=4,
                                     num_slots=1, num_pages=7)
    rng = np.random.RandomState(1)
    # fill the cache: one request retires and leaves its pages cached
    p0 = rng.randint(1, cfg.vocab_size, (12,)).astype(np.int32)
    eng.serve(params, [p0])
    assert eng.mgr.num_cached_pages > 0
    free_before = eng.mgr.num_free_pages
    # needs more than the free count but fits the pool: must evict, admit
    p1 = rng.randint(1, cfg.vocab_size, (20,)).astype(np.int32)
    assert eng.mgr.pages_for(len(p1) + 4) > free_before
    out = eng.serve(params, [p1])
    assert len(out[0]) == 4
    assert eng.cache.stats["evictions"] > 0
    # permanently infeasible: beyond usable_pages raises MemoryError
    eng.submit(rng.randint(1, cfg.vocab_size, (28,)).astype(np.int32))
    with pytest.raises(MemoryError, match="pool only holds"):
        eng.step(params)


def test_scheduler_charges_uncached_suffix_and_reports_gauges():
    """ServingScheduler over a cache-enabled engine: warm requests admit
    against suffix-only page budgets, and the cached/live gauge split is
    sampled."""
    from paddle_tpu.serving import SchedulerConfig, ServingScheduler
    cfg, params, eng = _setup_engine(prefix_cache=True)
    prompts = _shared_prefix_prompts(cfg)
    sched = ServingScheduler(eng, SchedulerConfig(max_queue_depth=32))
    handles = [sched.submit(p) for p in prompts]    # cold wave
    sched.run(params, max_steps=1000)
    handles += [sched.submit(p) for p in prompts]   # warm wave
    sched.run(params, max_steps=1000)
    assert all(h.done for h in handles)
    assert eng.cache.stats["hits"] >= len(prompts)
    g = sched.metrics.gauges
    assert "cached_page_utilization" in g and "live_page_utilization" in g
    assert g["cached_page_utilization"] > 0.0      # retired prefixes resident
    # registry carries the kvcache counters + page-state gauge split
    from paddle_tpu.observability import get_registry
    text = get_registry().prometheus_text()
    assert re.search(r"paddle_kvcache_hits_total [1-9]", text)
    assert 'paddle_kvcache_pages{state="cached"}' in text


def test_cache_hit_and_evict_events_logged(tmp_path):
    from paddle_tpu.observability.events import configure_event_log
    import json
    path = str(tmp_path / "events.jsonl")
    configure_event_log(path)
    try:
        cfg, params, eng = _setup_engine(prefix_cache=True, num_slots=1,
                                         num_pages=9, max_new=4)
        rng = np.random.RandomState(2)
        p = rng.randint(1, cfg.vocab_size, (10,)).astype(np.int32)
        eng.serve(params, [p])
        eng.serve(params, [p])                     # hit
        big = rng.randint(1, cfg.vocab_size, (20,)).astype(np.int32)
        eng.serve(params, [big])                   # pressure -> evict
    finally:
        configure_event_log(None)
    kinds = [json.loads(l)["kind"] for l in open(path)]
    assert "cache_hit" in kinds and "cache_evict" in kinds


# ---------------------------------------------------------------------------
# lint: pool internals stay behind the ops/kvcache boundary
# ---------------------------------------------------------------------------

def test_no_private_pool_access_outside_ops_and_kvcache():
    """Forbid `._free` / `._pages_for` outside paddle_tpu/ops/ and
    paddle_tpu/kvcache/: every other layer sizes requests via the public
    ``pages_for()``/``usable_pages`` surface, and only the pool itself
    touches the free list (the refcount/cached states make direct free-
    list surgery unsound). Ported to tpu-lint (rule ``private-kvcache``
    — AST attribute analysis, so this file no longer needs to exclude
    itself: the deliberate corruption-injection above carries an inline
    ``# tpu-lint: disable=`` instead)."""
    from paddle_tpu import analysis
    bad = analysis.cached_report().new_for_rule("private-kvcache")
    assert not bad, (
        "private page-pool access:\n" + "\n".join(f.text() for f in bad)
        + "\nuse pages_for()/usable_pages, or route page ownership "
        "through paddle_tpu.kvcache")
