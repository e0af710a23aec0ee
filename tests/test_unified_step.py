"""Unified ragged paged-attention step (ROADMAP item 1, per PAPERS.md
"Ragged Paged Attention"): ONE Pallas/XLA kernel and ONE compiled engine
step serve mixed prefill+decode rows of arbitrary lengths — greedy output
equal to the full re-forward oracle (``_oracle``), O(1) recompiles across
a length-diverse storm, conservation after every ragged step."""

import numpy as np
import pytest
import jax.numpy as jnp

from paddle_tpu.models import llama as L
from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.observability.runtime import recompiles
from paddle_tpu.ops import paged_attention as pa

from _oracle import assert_greedy


# ---------------------------------------------------------------------------
# kernel parity: the ragged composition vs plainer references
# ---------------------------------------------------------------------------

def _mixed_batch(seed=0, PAGE=4, NPAGES=32, NKV=2, NH=4, D=8):
    """A packed mixed batch: row 0 decodes (1 token), rows 1-2 prefill
    suffixes at different offsets (one warm: q_start > 0)."""
    rng = np.random.RandomState(seed)
    mgr = pa.PagedKVCacheManager(1, NPAGES, PAGE, NKV, D, dtype=jnp.float32)
    k_pool = rng.randn(NPAGES, PAGE, NKV, D).astype(np.float32)
    v_pool = rng.randn(NPAGES, PAGE, NKV, D).astype(np.float32)
    # row 0: decode at kv_len 9 -> one token at position 8
    # row 1: cold prefill of 6 tokens (positions 0..5)
    # row 2: warm suffix of 3 tokens at q_start 5 (positions 5..7)
    kv_lens = [9, 6, 8]
    for sid, n in enumerate(kv_lens):
        mgr.allocate(sid, n)
    bt, _ = mgr.block_tables([0, 1, 2])
    token_row = np.array([0] + [1] * 6 + [2] * 3 + [-1, -1], np.int32)
    positions = np.array([8] + list(range(6)) + [5, 6, 7] + [0, 0],
                         np.int32)
    T = len(token_row)
    q = rng.randn(T, NH, D).astype(np.float32)
    return (q, k_pool, v_pool, bt.astype(np.int32), token_row, positions,
            np.asarray(kv_lens, np.int32))


def _dense_causal(q, k, v, positions):
    """Dense reference in numpy: token i of ``q`` (n, nh, d) sits at
    ``positions[i]`` and attends keys [0, positions[i]] of ONE row's
    contiguous ``k`` / ``v`` (S, nkv, d); query heads share KV heads in
    groups (GQA)."""
    rep = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, rep, axis=1), np.repeat(v, rep, axis=1)
    scores = np.einsum("thd,shd->ths", q, k) / np.sqrt(q.shape[-1])
    seen = np.arange(k.shape[0])[None, :] <= np.asarray(positions)[:, None]
    scores = np.where(seen[:, None, :], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("ths,shd->thd", p, v)


def test_ragged_array_matches_decode_reference_and_dense_causal():
    """Elementwise parity of the ragged XLA reference against plainer
    ones: ``paged_attention_array`` for the decode token, and for the
    prefill and warm-suffix rows dense causal attention over the row's
    keys gathered out of the pool."""
    q, kp, vp, bt, token_row, positions, kv_lens = _mixed_batch()
    out = np.asarray(pa.ragged_paged_attention_array(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(token_row), jnp.asarray(positions),
        jnp.asarray(kv_lens)))

    # decode token (row 0): the decode op with kv_len = pos + 1
    dec = np.asarray(pa.paged_attention_array(
        jnp.asarray(q[:1]), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt[:1]), jnp.asarray([9], np.int32)))
    np.testing.assert_allclose(out[0], dec[0], rtol=1e-5, atol=1e-6)

    # prefill rows: a cold one (positions 0..5) and a suffix at offset 5
    for row, sl in ((1, slice(1, 7)), (2, slice(7, 10))):
        k_row = kp[bt[row]].reshape(-1, *kp.shape[2:])
        v_row = vp[bt[row]].reshape(-1, *vp.shape[2:])
        ref = _dense_causal(q[sl], k_row, v_row, positions[sl])
        np.testing.assert_allclose(out[sl], ref, rtol=1e-5, atol=1e-5)


def _packed_case(kv_lens, spans, shared=(), page=4, width=4, t=12):
    """A packed batch from row spans: ``spans`` = [(row, first position,
    tokens)], packed in order and padded to ``t`` with pad slots. Each row
    owns ``width`` private pages of ``page`` keys; ``shared`` = [(row,
    table slot, other row)] points a table slot at the other row's page
    (prefix cache). The kernel folds G = ``pa.ragged_block_pages(page,
    width)`` pages a step: 4 (the whole table) at the default geometry, 8
    at ``**_BLOCKS`` (page 16, width 20: rows of several blocks)."""
    PAGE, T, NKV, NH, D = page, t, 2, 4, 8
    rng = np.random.RandomState(0)
    n_rows = len(kv_lens)
    pool = 1 + n_rows * width
    kp = rng.randn(pool, PAGE, NKV, D).astype(np.float32)
    vp = rng.randn(pool, PAGE, NKV, D).astype(np.float32)
    bt = (1 + np.arange(n_rows * width, dtype=np.int32)
          ).reshape(n_rows, width)
    for row, slot, other in shared:
        bt[row, slot] = bt[other, slot]
    token_row = np.full((T,), -1, np.int32)
    positions = np.zeros((T,), np.int32)
    at = 0
    for row, first, n in spans:
        token_row[at:at + n] = row
        positions[at:at + n] = first + np.arange(n)
        at += n
    q = rng.randn(T, NH, D).astype(np.float32)
    return (q, kp, vp, bt, token_row, positions,
            np.asarray(kv_lens, np.int32))


_BLOCKS = dict(page=16, width=20)      # G = 8 pages a step, 2.5 blocks a table

_RAGGED_CASES = {
    # the mixed batch above: decode + cold prefill + warm suffix + pads
    "mixed_batch": lambda: _mixed_batch(seed=3),
    # all pad slots: the one grid step only zeroes the output
    "no_live_row": dict(kv_lens=[0, 0, 0], spans=[]),
    "starved_rows_between_live": dict(
        kv_lens=[0, 5, 0, 0, 9, 0], spans=[(1, 4, 1), (4, 8, 1)]),
    "exact_page_multiple_and_one_past": dict(
        kv_lens=[8, 9], spans=[(0, 7, 1), (1, 8, 1)]),
    "row_fills_table_width": dict(
        kv_lens=[16, 3], spans=[(0, 12, 4), (1, 2, 1)]),
    # row 1's first two table slots are row 0's pages
    "shared_prefix_pages": dict(
        kv_lens=[10, 11], spans=[(0, 9, 1), (1, 8, 3)],
        shared=[(1, 0, 0), (1, 1, 0)]),
    # over-decoded row: kv_lens past width * PAGE = 16 clamps to the table
    "kv_lens_past_table_span": dict(
        kv_lens=[21, 6], spans=[(0, 15, 1), (1, 5, 1)]),
    "prefill_chunk_plus_decode_rows": dict(
        kv_lens=[7, 13, 6, 2],
        spans=[(0, 6, 1), (1, 12, 1), (2, 0, 6), (3, 0, 2)]),
    # the blocked walk, G = 8: rows of 1 page, exactly G, G + 1 and 13 pages
    "blocks_of_1_G_G_plus_1_and_13_pages": dict(
        kv_lens=[5, 128, 130, 200],
        spans=[(0, 4, 1), (1, 127, 1), (2, 126, 4), (3, 194, 6)], **_BLOCKS),
    # 2.5 blocks with starved rows around them; a row whose last block is
    # one page, beside a one-page row
    "blocks_with_starved_rows_between": dict(
        kv_lens=[0, 150, 0, 0, 300, 0, 17],
        spans=[(1, 149, 1), (4, 296, 4), (6, 16, 1)], **_BLOCKS),
    # over-decoded: kv_lens past width * page = 320 clamps to the table,
    # whose last block holds 4 of its 8 slots
    "blocks_kv_lens_past_table_span": dict(
        kv_lens=[333, 40], spans=[(0, 319, 1), (1, 38, 2)], **_BLOCKS),
    # a prefill chunk that crosses a block boundary beside decode rows, and
    # row 2 reading row 0's first block through the prefix cache
    "blocks_prefill_across_a_boundary_shared_prefix": dict(
        kv_lens=[131, 260, 140],
        spans=[(0, 124, 7), (1, 259, 1), (2, 137, 3)],
        shared=[(2, slot, 0) for slot in range(8)], **_BLOCKS),
    # a table narrower than 128 keys: G is the table's width, 3
    "table_narrower_than_a_block": dict(
        kv_lens=[48, 17, 33], spans=[(0, 47, 1), (1, 14, 3), (2, 32, 1)],
        page=16, width=3),
    # a page of 128 keys: G = 1, a step is a page again
    "one_page_blocks": dict(
        kv_lens=[300, 128], spans=[(0, 297, 3), (1, 127, 1)],
        page=128, width=3),
}


@pytest.mark.parametrize("case", sorted(_RAGGED_CASES))
def test_ragged_pallas_interpret_matches_array(case):
    """The Pallas ragged kernel (interpret mode on CPU) must match the
    XLA gather/mask reference elementwise, pad slots included, on the
    mixes that shape its work list: a mixed batch, no work at all, gaps
    between live rows, page boundaries, a full table row, shared physical
    pages, a span past the table, prefill beside decode; and, where a row
    takes several blocks of G pages, page counts on both sides of a
    multiple of G, a table narrower than a block and blocks of one page."""
    spec = _RAGGED_CASES[case]
    args = spec() if callable(spec) else _packed_case(**spec)
    token_row = args[4]
    jargs = [jnp.asarray(a) for a in args]
    ref = np.asarray(pa.ragged_paged_attention_array(*jargs))
    out = np.asarray(pa.ragged_paged_attention_pallas(*jargs,
                                                      interpret=True))
    real = token_row >= 0
    np.testing.assert_allclose(out[real], ref[real], rtol=1e-5, atol=1e-6)
    # pad slots must come out finite (zeros): garbage there would be
    # scattered into the pool and could poison other rows' masked lanes
    assert np.all(np.isfinite(out))
    assert np.all(out[~real] == 0.0)


_WORK_LIST_KV_LENS = [
    [0, 0, 0], [0, 5, 0, 0, 9, 0], [8, 9], [16, 3], [21, 6], [1],
    [16, 16, 16], [5, 128, 130, 200], [0, 150, 0, 0, 300, 0, 17], [333, 40],
]
# (page, table width): G = 4 (the table), 8, 8 past a table of 9, 2, 1, 1
_WORK_LIST_GEOMETRY = [(4, 4), (16, 20), (16, 9), (64, 5), (128, 3), (256, 3)]


def test_ragged_block_pages_fills_the_lanes():
    assert [pa.ragged_block_pages(p, w) for p, w in _WORK_LIST_GEOMETRY] == \
        [4, 8, 8, 2, 1, 1]
    assert pa.ragged_block_pages(16, 256) == 8      # the serving default
    assert pa.ragged_block_pages(8, 64) == 16


@pytest.mark.parametrize("page,width", _WORK_LIST_GEOMETRY)
def test_ragged_work_list_matches_python_loop(page, width):
    """The in-program work list is the row-major list of live blocks: (row,
    first page of the block), a block every G pages of the row, each
    flagged on its row's first block (page 0 where no window cuts the
    list) and on its last."""
    group = pa.ragged_block_pages(page, width)
    for kv_lens in _WORK_LIST_KV_LENS:
        want = []
        for r, n in enumerate(kv_lens):
            blocks = -(-min(-(-n // page), width) // group)
            want += [(r, b * group, int(b == 0), int(b == blocks - 1))
                     for b in range(blocks)]
        items, n_live = pa._ragged_work_list(
            jnp.asarray(kv_lens, jnp.int32), page, width)
        assert items.shape == (len(kv_lens) * -(-width // group),)
        bits = pa._work_item_bits(width)
        items = np.asarray(items)
        got = [tuple(int(x) for x in pa._unpack_work_item(it, bits))
               for it in items[:int(n_live)]]
        assert got == want, kv_lens
        # entries past the list still index inside the block table
        rows, js, _, _ = pa._unpack_work_item(items, bits)
        assert rows.min() >= 0 and rows.max() < len(kv_lens)
        assert js.min() >= 0 and js.max() < width


@pytest.mark.parametrize("page,width", _WORK_LIST_GEOMETRY)
def test_ragged_host_helpers_match_in_program_n_live(page, width):
    """The engine's work record counts the kernel's steps and the pages
    they hold with numpy helpers on the host: the blocks must equal the
    in-program ``n_live``, call by call, and the pages fit in them."""
    group = pa.ragged_block_pages(page, width)
    for kv_lens in _WORK_LIST_KV_LENS:
        _, n_live = pa._ragged_work_list(
            jnp.asarray(kv_lens, jnp.int32), page, width)
        blocks = pa.ragged_live_blocks(kv_lens, page, width)
        assert blocks == int(n_live), kv_lens
        pages = pa.ragged_live_pages(kv_lens, page, width)
        assert pages == sum(min(-(-n // page), width) for n in kv_lens)
        assert blocks <= pages <= group * blocks
    # a dispatch's (rounds, rows) plan: one count per micro-round
    rounds = np.asarray([[0, 0, 0], [5, 0, 17], [16, 16, 16]], np.int32)
    assert pa.ragged_live_pages(rounds, 4, 4).tolist() == [0, 6, 12]
    assert pa.ragged_live_blocks(rounds, 4, 4).tolist() == [0, 2, 3]
    assert pa.ragged_live_blocks(rounds, 4, 3).tolist() == [0, 2, 3]
    assert pa.ragged_live_blocks(rounds * 40, 16, 64).tolist() == [0, 8, 15]


# ---------------------------------------------------------------------------
# engine: greedy output equal to the full re-forward oracle
# ---------------------------------------------------------------------------

def _engine(prefix_cache=False, max_new=6, num_slots=2, chunk=3, kv_heads=4,
            **kw):
    cfg = L.llama_tiny(num_hidden_layers=2, num_key_value_heads=kv_heads)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=max_new),
        num_slots=num_slots, page_size=4, max_seq_len=64, chunk=chunk,
        prefix_cache=prefix_cache, **kw)
    return cfg, eng


def _ragged_prompts(cfg, n, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size,
                        (int(lens[i % len(lens)]),)).astype(np.int32)
            for i in range(n)]


@pytest.mark.parametrize("kv_heads", [4, 2])
@pytest.mark.parametrize("prefix_cache", [False, True])
def test_engine_matches_full_reforward(prefix_cache, kv_heads):
    """The whole acceptance surface in one sweep: ragged lengths, slot
    reuse, (with the cache) warm suffix + COW rows, and grouped-query
    attention (2 KV heads under 4 query heads) beside multi-head — the
    single-dispatch engine must emit exactly the oracle's greedy tokens."""
    cfg, eng = _engine(prefix_cache=prefix_cache, kv_heads=kv_heads)
    params = L.init_stacked_params(cfg, seed=3)
    prompts = _ragged_prompts(cfg, 8, (5, 12, 3, 9, 17, 2, 7, 30), seed=1)
    if prefix_cache:
        # shared prefixes + an exact repeat (the COW wave: full-prompt
        # match forces a copy-on-write of the final page)
        prompts[3] = np.concatenate([prompts[1], prompts[2]])
        prompts[5] = prompts[1].copy()
    outs = eng.serve(params, prompts)
    assert_greedy(params, cfg, prompts, outs, n_new=6)
    if prefix_cache:
        snap = eng.cache.snapshot()
        # the warm rows were warm, the exact repeat copied its last page
        assert snap["hits"] >= 2 and snap["cow_copies"] >= 1, snap


def test_mid_decode_admission_matches_oracle_and_conserved():
    """A request admitted while others are mid-decode joins the current
    ragged step immediately and still produces the greedy tokens of a
    fresh engine and of the oracle; page conservation holds after every
    ragged step (engine-internal check + explicit audits)."""
    cfg, eng = _engine(prefix_cache=True, max_new=6, num_slots=2)
    params = L.init_stacked_params(cfg, seed=3)
    early = _ragged_prompts(cfg, 2, (11, 4), seed=5)
    late = _ragged_prompts(cfg, 1, (7,), seed=9)[0]
    r_early = [eng.submit(p) for p in early]
    for _ in range(2):                      # early requests now mid-decode
        eng.step(params)
        eng.mgr.check_conservation()
    assert any(len(eng._live[eng._slot_rid[s]].tokens) > 0
               for s in range(eng.num_slots)
               if eng._slot_rid[s] is not None)
    r_late = eng.submit(late)               # mid-decode admission
    results = {}
    for _ in range(60):
        eng.step(params)
        eng.mgr.check_conservation()        # incl. COW/suffix rows
        results.update(eng.collect())
        if len(results) == 3:
            break
    assert set(results) == set(r_early) | {r_late}
    # early and late rows alike are what the oracle generates alone
    assert_greedy(params, cfg, early + [late],
                  [results[r] for r in r_early + [r_late]], n_new=6)


# ---------------------------------------------------------------------------
# O(1) recompiles across a length-diverse storm
# ---------------------------------------------------------------------------

def test_storm_recompiles_o1():
    """A length-diverse request storm (the recompile cliff of a compile
    per prompt bucket): the engine's step cache misses at most twice (one
    compile, one optional remat) and every output is the oracle's."""
    cfg, eng = _engine(max_new=4, num_slots=4)
    params = L.init_stacked_params(cfg, seed=3)
    lens = (2, 3, 5, 7, 9, 12, 17, 23, 31, 44)
    prompts = _ragged_prompts(cfg, 12, lens, seed=7)

    u0 = recompiles.count("cbe.unified_step")
    outs = eng.serve(params, prompts)
    u_misses = recompiles.count("cbe.unified_step") - u0
    assert u_misses <= 2, u_misses          # O(1): the acceptance bound
    assert_greedy(params, cfg, prompts, outs, n_new=4)

    # compile wall time surfaced for warmup visibility (/metrics + bench)
    assert recompiles.compile_seconds_total("cbe.unified_step") > 0


def test_unified_single_program_reused_across_admission_mixes():
    """Every step — pure prefill, mixed, pure decode, re-admission into
    freed slots — runs the SAME compiled program object."""
    cfg, eng = _engine(max_new=4, num_slots=2)
    params = L.init_stacked_params(cfg, seed=3)
    [eng.submit(p) for p in _ragged_prompts(cfg, 5, (3, 13, 6, 21, 2),
                                            seed=11)]
    eng.step(params)
    prog = eng._unified_step
    assert prog is not None
    while eng.step(params) or eng._queue:
        assert eng._unified_step is prog
    assert eng._unified_step is prog
