"""The latent (MLA) ragged paged-attention kernel and the cache layout it
reads: the Pallas kernel in interpret mode against its XLA twin on mixed
prefill / decode rows across page and block boundaries, the twin against
plain attention, the absorbed form against the expanded one, and a manager
that allocates what a model's layout says (one array, no V). Small sizes,
seeded, float32, on the CPU."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kvcache import PrefixCache, RefcountedKVCacheManager
from paddle_tpu.ops import paged_attention as pa

# page 16, table of 20 pages: G = 8, so rows of several blocks (PR 28's
# lesson: at a table of one block a fault in multi-block rows passes)
_BLOCKS = dict(page=16, width=20)
_TINY = dict(page=4, width=8)          # G = 8: the whole table is one block


def _case(rows, t, n_rows=4, heads=4, d=48, page=16, width=20, seed=0,
          share=()):
    """Packed arguments of one call: ``rows`` = [(row, first position,
    tokens)], packed in that order into ``t`` slots; the rest are pads.
    ``share`` = [(row, other row, pages)]: the row's table names the other
    row's first ``pages`` pages (a borrowed prefix, or ids gone stale where
    they lie past the row's live pages)."""
    rng = np.random.RandomState(seed)
    n_pages = n_rows * width + 1
    pool = rng.randn(n_pages, page, d).astype(np.float32)
    tables = (1 + rng.permutation(n_pages - 1)).reshape(n_rows, width)
    for row, other, pages in share:
        tables[row, :pages] = tables[other, :pages]
    token_row = np.full((t,), -1, np.int32)
    positions = np.zeros((t,), np.int32)
    kv_lens = np.zeros((n_rows,), np.int32)
    at = 0
    for row, first, n in rows:
        token_row[at:at + n] = row
        positions[at:at + n] = first + np.arange(n)
        kv_lens[row] = first + n
        at += n
    q = rng.randn(t, heads, d).astype(np.float32)
    return (q, pool, tables.astype(np.int32), token_row, positions, kv_lens)


# a table of 40 pages = five blocks of G = 8 pages = 128 positions each
_SHARED = dict(page=16, width=40)

_MIXES = {
    # decode rows on both sides of a page and a block (128 keys) boundary,
    # a prefill span that starts mid-page and crosses a block boundary
    "blocks_decode_and_prefill": dict(
        rows=[(0, 15, 1), (1, 16, 1), (2, 127, 1), (3, 120, 20)], t=24,
        **_BLOCKS),
    "blocks_row_of_three_blocks": dict(
        rows=[(1, 300, 1), (0, 128, 1), (3, 257, 6)], t=8, **_BLOCKS),
    "blocks_prefill_from_zero": dict(
        rows=[(2, 0, 19), (0, 200, 1)], t=20, **_BLOCKS),
    # tiles of 4 tokens, then single tokens: spans of 1..9 tokens
    "blocks_span_lengths_around_the_tile": dict(
        rows=[(0, 40, 4), (1, 70, 5), (2, 33, 9), (3, 150, 3)], t=24,
        **_BLOCKS),
    "blocks_starved_rows_between_live_ones": dict(
        rows=[(0, 140, 1), (3, 250, 2)], t=8, **_BLOCKS),
    "blocks_span_to_the_tables_end": dict(
        rows=[(3, 16 * 20 - 4, 4), (0, 5, 1)], t=8, **_BLOCKS),
    "blocks_empty_call": dict(rows=[], t=8, **_BLOCKS),
    "tiny_one_block_tables": dict(
        rows=[(0, 3, 1), (1, 4, 1), (3, 9, 11)], t=16, **_TINY),
    # rows whose tables name the same leading pages (a borrowed prefix):
    # four rows on three shared full blocks + private tails, decode and
    # prefill members mixed (9 tokens: more than one tile)
    "shared_three_blocks_decode_and_prefill": dict(
        rows=[(0, 400, 1), (1, 390, 6), (2, 500, 1), (3, 385, 1)], t=12,
        share=[(1, 0, 24), (2, 0, 24), (3, 0, 24)], **_SHARED),
    # more members than one tile of tokens, all decoding; row 4 alone
    "shared_more_members_than_a_tile": dict(
        rows=[(r, 260 + 17 * r, 1) for r in range(7)], t=8, n_rows=7,
        share=[(r, 0, 16) for r in (1, 2, 3, 5, 6)], **_SHARED),
    # the group's lowest row has no token this call: the next one leads
    "shared_lowest_row_idle": dict(
        rows=[(1, 300, 1), (2, 280, 3), (3, 520, 1)], t=8,
        share=[(1, 0, 16), (2, 0, 16), (3, 0, 16)], **_SHARED),
    # two depths: rows 0 and 1 share four blocks, row 2 two of them; row 3
    # shares three PAGES with them, not a block: nothing
    "shared_two_depths": dict(
        rows=[(0, 530, 1), (1, 515, 2), (2, 300, 1), (3, 200, 5)], t=12,
        share=[(1, 0, 32), (2, 0, 16), (3, 0, 3)], **_SHARED),
    # equal ids past a row's live pages are stale: row 1's table is row 0's
    # whole, its 200 positions hold ONE full block; row 2 sits at exactly
    # two full blocks (its newest token's block is its own)
    "shared_stale_ids_and_a_full_last_block": dict(
        rows=[(0, 600, 1), (1, 199, 1), (2, 250, 6)], t=8,
        share=[(1, 0, 40), (2, 0, 40)], **_SHARED),
}

#: shared leading blocks of each row (``nshared``), where a mix shares any
_SHARED_BLOCKS = {
    "shared_three_blocks_decode_and_prefill": [0, 3, 3, 3],
    "shared_more_members_than_a_tile": [0, 2, 2, 2, 0, 2, 2],
    "shared_lowest_row_idle": [0, 0, 2, 2],
    "shared_two_depths": [0, 4, 2, 0],
    "shared_stale_ids_and_a_full_last_block": [0, 1, 1, 0],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mix,values", [(mix, "lanes") for mix in sorted(_MIXES)]
                         + [(mix, "pool") for mix in sorted(_SHARED_BLOCKS)]
                         + [("blocks_decode_and_prefill", "pool")])
def test_mla_pallas_interpret_matches_its_twin(mix, values, dtype):
    """The kernel (interpret mode) against the gather/mask twin: pad slots
    exactly 0, everything finite, live tokens equal to float32 rounding;
    in the served dtype (bfloat16 operands, float32 softmax state) to a
    bfloat16 step or two of the outputs' size. ``values`` ``"pool"``: V a
    pool of its own (multi-query attention through this kernel, as Jamba's
    attention layers run it), against the GQA twin on one KV head."""
    args = _case(**_MIXES[mix])
    token_row = args[3]
    jargs = [jnp.asarray(a) for a in args]
    jargs[:2] = [a.astype(dtype) for a in jargs[:2]]        # queries, pool
    if values == "pool":
        v_pool = jnp.asarray(np.random.RandomState(9).randn(
            *args[1].shape[:2], 32), dtype)
        ref = pa.ragged_paged_attention_array(
            jargs[0], jargs[1][:, :, None],
            jnp.pad(v_pool, ((0, 0), (0, 0), (0, 16)))[:, :, None],
            *jargs[2:], scale=0.3)[..., :32]
        out = pa.mla_paged_attention_pallas(
            *jargs, scale=0.3, v_pool=v_pool, interpret=True)
    else:
        ref = pa.mla_paged_attention_array(*jargs, scale=0.3, value_dim=32)
        out = pa.mla_paged_attention_pallas(
            *jargs, scale=0.3, value_dim=32, interpret=True)
    ref, out = np.asarray(ref, np.float32), np.asarray(out, np.float32)
    real = token_row >= 0
    assert out.shape == ref.shape == (len(token_row), 4, 32)
    tol = dict(rtol=1e-5, atol=2e-6) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(out[real], ref[real], **tol)
    assert np.all(np.isfinite(out)) and np.all(out[~real] == 0.0)


@pytest.mark.parametrize("mix", sorted(_MIXES))
def test_groups_of_rows_that_share_leading_blocks(mix):
    """Which rows a call folds under another row's item: the in-program
    groups (``nshared``, each row's leader and the followers' token lists
    the kernel's steps read), the host's numpy twin that the
    engine's ``shared_pages`` counts from, and the work list: a follower
    lists what lies past its shared blocks, a table with nothing shared
    gives the list it gave before, item for item."""
    case = _MIXES[mix]
    _, _, tables, token_row, _, kv_lens = _case(**case)
    page, width = case["page"], case["width"]
    group = pa.ragged_block_pages(page, width)
    nshared, lead = [np.asarray(x) for x in pa._shared_block_groups(
        jnp.asarray(tables) + 1000, jnp.asarray(kv_lens), page)]
    want = _SHARED_BLOCKS.get(mix, [0] * len(kv_lens))
    assert nshared.tolist() == want
    host = pa.ragged_shared_blocks(tables, np.stack([kv_lens, kv_lens]), page)
    assert host.tolist() == [want, want]
    # the followers' tokens, as the kernel reads them: each leader's stretch
    # holds its followers' tokens and no other, most shared blocks first
    n_rows = len(kv_lens)
    assert all(lead[r] == r or (nshared[r] > 0 and nshared[lead[r]] == 0
                                and lead[r] < r) for r in range(n_rows))
    mine = token_row[None, :] == np.arange(n_rows)[:, None]
    ftok, fshare, fstart, flen = [np.asarray(x) for x in pa._follower_tokens(
        jnp.asarray(mine), jnp.asarray(nshared), jnp.asarray(lead),
        width // group)]
    assert sorted(ftok.tolist()) == list(range(len(token_row)))
    listed = []
    for r in range(n_rows):
        stretch = slice(fstart[r], fstart[r] + flen[r])
        toks, shares = ftok[stretch], fshare[stretch]
        assert all(lead[token_row[t]] == r and token_row[t] != r
                   for t in toks)
        assert shares.tolist() == [nshared[token_row[t]] for t in toks]
        assert (np.diff(shares) <= 0).all()
        listed += toks.tolist()
    assert sorted(listed) == [t for t, m in enumerate(token_row)
                              if m >= 0 and nshared[m] > 0]
    # the work list: its length is the host's count of blocks, and with
    # nothing shared it is the list of a call that knows no groups
    work, n_live = pa._ragged_work_list(
        jnp.asarray(kv_lens), page, width, first_pages=jnp.asarray(
            nshared * group))
    assert int(n_live) == int(pa.ragged_live_blocks(
        kv_lens, page, width, nshared * group))
    plain, n_plain = pa._ragged_work_list(jnp.asarray(kv_lens), page, width)
    saved = sum(want)
    assert int(n_plain) - int(n_live) == saved
    if not saved:
        assert np.array_equal(np.asarray(work), np.asarray(plain))
    else:
        row, j, _, last = pa._unpack_work_item(
            np.asarray(work)[:int(n_live)], pa._work_item_bits(width))
        for r in np.nonzero(nshared)[0]:
            # a follower's items start past its shared blocks and end with
            # an item of its own
            assert j[row == r].min() == nshared[r] * group
            assert last[row == r].sum() == 1


def test_twin_is_plain_attention_over_the_rows_entries():
    """The twin against softmax attention written out for one token at a
    time: keys the whole entry, values its first ``value_dim`` numbers, the
    same for every head."""
    args = _case(rows=[(0, 21, 1), (2, 30, 5)], t=8, **_BLOCKS)
    q, pool, tables, token_row, positions, _ = args
    got = np.asarray(pa.mla_paged_attention_array(
        *[jnp.asarray(a) for a in args], scale=0.25, value_dim=32))
    for t in np.nonzero(token_row >= 0)[0]:
        entries = pool[tables[token_row[t]]].reshape(-1, pool.shape[-1])
        entries = entries[:positions[t] + 1]
        scores = 0.25 * q[t] @ entries.T                   # (heads, keys)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(got[t], p @ entries[:, :32], rtol=1e-4,
                                   atol=1e-5)


def test_absorbed_form_equals_the_expanded_form():
    """``score = q_nope . (c W_UK) + q_rope . k_r`` and ``o = p (c W_UV)``
    (expanded: keys and values of every head for every position) against
    ``(q_nope W_UK^T) . c + q_rope . k_r`` and ``(p c) W_UV`` through the
    twin over a latent pool (absorbed), on random inputs."""
    from paddle_tpu.models import axk1 as X
    rng = np.random.RandomState(3)
    heads, nope, rope, v, ckv, entry = 4, 16, 8, 12, 32, 128
    rows = [(0, 37, 1), (1, 17, 6)]
    q_full, pool, tables, token_row, positions, kv_lens = _case(
        rows=rows, t=8, heads=heads, d=entry, **_BLOCKS)
    pool[..., ckv + rope:] = 0.0                 # the entry's pad lanes
    w_uk = rng.randn(ckv, heads, nope).astype(np.float32) * 0.2
    w_uv = rng.randn(ckv, heads, v).astype(np.float32) * 0.2
    q_nope = rng.randn(8, heads, nope).astype(np.float32)
    q_rope = rng.randn(8, heads, rope).astype(np.float32)
    absorbed = X.absorb_queries(jnp.asarray(q_nope), jnp.asarray(q_rope),
                                jnp.asarray(w_uk), entry)
    assert absorbed.shape == (8, heads, entry)
    o_lat = pa.mla_paged_attention_array(
        absorbed, jnp.asarray(pool), jnp.asarray(tables),
        jnp.asarray(token_row), jnp.asarray(positions),
        jnp.asarray(kv_lens), scale=0.2, value_dim=ckv)
    got = np.einsum("thc,chv->thv", np.asarray(o_lat), w_uv)
    for t in np.nonzero(token_row >= 0)[0]:
        entries = pool[tables[token_row[t]]].reshape(-1, entry)
        entries = entries[:positions[t] + 1]
        c, k_r = entries[:, :ckv], entries[:, ckv:ckv + rope]
        k_nope = np.einsum("sc,chn->shn", c, w_uk)
        values = np.einsum("sc,chv->shv", c, w_uv)
        scores = 0.2 * (np.einsum("hn,shn->hs", q_nope[t], k_nope)
                        + q_rope[t] @ k_r.T)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(got[t], np.einsum("hs,shv->hv", p, values),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the cache layout is the model's
# ---------------------------------------------------------------------------
def test_default_layout_is_k_and_v_with_a_head_axis():
    mgr = pa.PagedKVCacheManager(3, 9, 4, 2, 8, dtype=jnp.float32)
    assert mgr.layout == pa.kv_cache_layout(2, 8)
    assert [p.shape for p in mgr.pools] == [(3, 9, 4, 2, 8)] * 2
    assert mgr.k_pages is mgr.pools[0] and mgr.v_pages is mgr.pools[1]
    assert mgr.page_nbytes == 2 * 3 * 4 * 2 * 8 * 4
    assert mgr.layout.token_elems == 2 * 2 * 8
    specs = mgr.layout.pool_specs("mp")
    assert [tuple(s) for s in specs] == [(None, None, None, "mp", None)] * 2
    mgr.k_pages = mgr.k_pages + 1.0             # the old names still write
    assert float(mgr.pools[0].min()) == 1.0 and float(mgr.pools[1].max()) == 0


@pytest.mark.parametrize("cls", [pa.PagedKVCacheManager,
                                 RefcountedKVCacheManager])
def test_a_latent_layout_allocates_one_array_and_no_v(cls):
    layout = pa.CacheLayout(((640,),), head_axis=None)
    mgr = cls(7, 33, 16, dtype=jnp.bfloat16, layout=layout)
    assert len(mgr.pools) == 1 and mgr.pools[0].shape == (7, 33, 16, 640)
    assert mgr.pools[0].dtype == jnp.bfloat16
    assert not hasattr(mgr, "v_pages") and mgr.k_pages is mgr.pools[0]
    assert mgr.page_nbytes == 7 * 16 * 640 * 2
    # a page is a page: allocation knows nothing of what an entry holds
    pages = mgr.allocate("a", 40)
    assert len(pages) == 3 and mgr.num_free_pages == 32 - 3
    mgr.free("a")
    mgr.check_conservation()


def test_a_layout_without_a_head_axis_refuses_a_mesh_of_several_chips():
    layout = pa.CacheLayout(((640,),), head_axis=None)
    layout.check_degree(1)
    with pytest.raises(ValueError, match="no head axis"):
        layout.check_degree(4)
    with pytest.raises(ValueError, match="must divide by the TP degree"):
        pa.kv_cache_layout(6, 8).check_degree(4)
    assert [tuple(s) for s in layout.pool_specs("mp")] == [(None,) * 4]


def test_copy_export_and_write_of_a_page_follow_the_layout():
    """Copy-on-write, export and import of a page on a one-array pool, and
    the prefix index over it: the same calls as on K and V."""
    layout = pa.CacheLayout(((128,),), head_axis=None)
    mgr = RefcountedKVCacheManager(2, 9, 4, dtype=jnp.float32, layout=layout)
    cache = PrefixCache(mgr)
    mgr.pools = (mgr.pools[0].at[:, 3].set(7.0),)
    mgr.copy_page(3, 5)
    assert float(jnp.abs(mgr.pools[0][:, 5] - 7.0).max()) == 0.0
    slab, = mgr.export_page(5)
    assert slab.shape == (2, 4, 128) and float(slab.min()) == 7.0
    mgr.write_page(6, slab * 2)
    assert float(mgr.pools[0][:, 6].max()) == 14.0
    with pytest.raises(ValueError, match="slabs for a pool of 1"):
        mgr.write_page(6, slab, slab)
    pages = mgr.allocate("a", 8)
    cache.insert(list(range(8)), pages)
    mgr.free("a")
    shared, n_cached, _ = cache.lookup(list(range(8)) + [99, 98])
    assert shared == pages and n_cached == 8
    mgr.check_conservation()


def test_capacity_planner_counts_a_latent_entry():
    from paddle_tpu.observability.memory import page_nbytes, plan_capacity
    assert page_nbytes(7, 16, None, None, 2, token_elems=640) == 7 * 16 * 1280
    assert page_nbytes(7, 16, 8, 128, 2) == 2 * 7 * 16 * 8 * 128 * 2
    plan = plan_capacity(num_layers=7, page_size=16, dtype_bytes=2,
                         token_elems=640, hbm_bytes=12289 * 7 * 16 * 1280)
    assert plan.total_pages == 12289 and plan.max_pages == 12288
