"""Paged KV-cache attention + block-table cache manager.

The serving-side replacement for the reference's contiguous CacheKV in
fused_multi_transformer (paddle/fluid/operators/fused/
fused_multi_transformer_op.cu.h:§0 — SURVEY.md §2.2, §2.7 #18): KV lives in
fixed-size *pages*; each sequence owns a list of pages via a block table,
so ragged batches don't reserve max_len × batch HBM and finished sequences
return pages to the pool immediately (vLLM-style, and the layout of the
TPU ragged-paged-attention kernels referenced in PAPERS.md).

One kernel serves every row, :func:`ragged_paged_attention` (a decode
row is a row of one token): the Pallas kernel
(:func:`ragged_paged_attention_pallas`) on TPU — block tables ride scalar
prefetch, each grid step folds a block of one row's pages HBM→VMEM with
online-softmax accumulation in VMEM scratch, so HBM traffic is the pages
each sequence owns — and its XLA reference
(:func:`ragged_paged_attention_array`: page gather + masked softmax)
everywhere else, CPU tests included. :func:`paged_attention_array` is the
decode-shaped reference the tests hold the ragged composition to.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # additive mask fill AND m_ref init — must stay identical


# ---------------------------------------------------------------------------
# Array-level op
# ---------------------------------------------------------------------------

def paged_attention_array(q, k_pages, v_pages, block_tables, seq_lens,
                          scale: Optional[float] = None):
    """Decode-time attention over paged KV.

    q:            (B, nh, d)        — one query token per sequence
    k_pages:      (P, page, nkv, d) — global page pool
    v_pages:      (P, page, nkv, d)
    block_tables: (B, max_pages) int32 — page ids per sequence (pad: 0)
    seq_lens:     (B,) int32 — valid KV length per sequence
    Returns (B, nh, d).
    """
    b, nh, d = q.shape
    page = k_pages.shape[1]
    nkv = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    rep = nh // nkv

    # gather each sequence's pages: (B, max_pages, page, nkv, d)
    k = jnp.take(k_pages, block_tables, axis=0)
    v = jnp.take(v_pages, block_tables, axis=0)
    k = k.reshape(b, max_pages * page, nkv, d)
    v = v.reshape(b, max_pages * page, nkv, d)

    s = scale if scale is not None else 1.0 / math.sqrt(d)
    mask = jnp.arange(max_pages * page)[None, :] < seq_lens[:, None]
    if rep > 1:
        # grouped attention without materializing repeated KV (a
        # jnp.repeat here streamed rep x the gathered cache bytes — the
        # exact bandwidth GQA exists to save)
        qg = q.reshape(b, nkv, rep, d)
        scores = jnp.einsum("bgrd,bsgd->bgrs", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * s
        scores = jnp.where(mask[:, None, None, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bgrs,bsgd->bgrd", probs.astype(v.dtype), v)
        return out.reshape(b, nh, d)
    scores = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * s
    scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", probs.astype(v.dtype), v)


def paged_write_array(k_pages, v_pages, k_new, v_new, block_tables, positions):
    """Write one token's K/V into its page slot.

    k_new/v_new: (B, nkv, d); positions: (B,) absolute position of the new
    token. Returns updated (k_pages, v_pages).
    """
    page = k_pages.shape[1]
    page_idx = positions // page          # (B,) which logical page
    page_off = positions % page           # (B,) slot within the page
    phys = jnp.take_along_axis(block_tables, page_idx[:, None], axis=1)[:, 0]
    k_pages = k_pages.at[phys, page_off].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[phys, page_off].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


# ---------------------------------------------------------------------------
# Ragged paged attention: ONE program for mixed prefill+decode rows
# ---------------------------------------------------------------------------

def ragged_paged_attention_array(q, k_pages, v_pages, block_tables, token_row,
                                 positions, kv_lens=None,
                                 scale: Optional[float] = None, window=None):
    """XLA reference of the unified ragged kernel (gather/mask composition).

    The serving engine's single-dispatch step packs every live row's
    tokens — decode rows contribute one token, prefill rows a chunk of
    their prompt — into one flat token axis. Each token attends to ITS
    row's pages under the one mask rule that subsumes both phases::

        key_pos <= positions[t]            (self-inclusive causality)

    A decode token at absolute position p sees keys [0, p] — exactly
    ``paged_attention_array``'s ``pos < kv_len`` with ``kv_len = p+1``;
    a prefill token at p sees the cached/scattered prefix plus itself
    (``key_pos <= q_start + t`` for a row whose chunk starts at
    ``q_start``).

    q:            (T, nh, d)   — packed queries (pad slots: token_row -1)
    k_pages:      (P, page, nkv, d)
    v_pages:      (P, page, nkv, d)
    block_tables: (R, max_pages) int32 (pad: reserved page 0)
    token_row:    (T,) int32 — owning row per token; -1 = pad slot
    positions:    (T,) int32 — absolute KV position per token
    kv_lens:      (R,) int32 — per-row attendable span (page-skip hint for
                  the Pallas kernel; unused by this reference)
    window:       None, or an int32 scalar (may be traced: a sliding-window
                  layer's span inside a layer scan). A token then also
                  needs ``key_pos > positions[t] - window``: it sees the
                  last ``window`` keys, itself included.
    Returns (T, nh, d).
    """
    t, nh, d = q.shape
    page = k_pages.shape[1]
    nkv = k_pages.shape[2]
    n_rows, max_pages = block_tables.shape
    rep = nh // nkv
    s = scale if scale is not None else 1.0 / math.sqrt(d)

    row_c = jnp.clip(token_row, 0, n_rows - 1)
    bt_tok = jnp.take(block_tables, row_c, axis=0)      # (T, max_pages)
    k = jnp.take(k_pages, bt_tok, axis=0)               # (T, W, page, ..)
    v = jnp.take(v_pages, bt_tok, axis=0)
    k = k.reshape(t, max_pages * page, nkv, d)
    v = v.reshape(t, max_pages * page, nkv, d)

    key_pos = jnp.arange(max_pages * page)[None, :]     # (1, S)
    mask = (key_pos <= positions[:, None]) & (token_row >= 0)[:, None]
    if window is not None:
        mask = mask & (key_pos > positions[:, None] - window)
    if rep > 1:
        # grouped attention without materializing repeated KV (same
        # bandwidth argument as paged_attention_array)
        qg = q.reshape(t, nkv, rep, d)
        scores = jnp.einsum("tgrd,tsgd->tgrs", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * s
        scores = jnp.where(mask[:, None, None, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("tgrs,tsgd->tgrd", probs.astype(v.dtype), v)
        return out.reshape(t, nh, d)
    scores = jnp.einsum("thd,tshd->ths", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * s
    scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("ths,tshd->thd", probs.astype(v.dtype), v)


def ragged_first_pages(token_row, positions, n_rows: int, page: int,
                       window: int) -> np.ndarray:
    """First page each row's work list holds under a sliding ``window``, on
    the host in numpy (the engine's work record): the page of the oldest
    key the row's EARLIEST token of the call still sees, ``max(0, min
    position - window + 1) // page``; 0 for a row with no token.
    ``token_row`` / ``positions``: (..., T); returns (..., n_rows)."""
    token_row = np.asarray(token_row)
    lead = token_row.shape[:-1]
    tr = token_row.reshape(-1, token_row.shape[-1])
    pos = np.asarray(positions, np.int64).reshape(tr.shape)
    big = np.iinfo(np.int64).max
    first = np.full((tr.shape[0], n_rows), big, np.int64)
    call, slot = np.nonzero(tr >= 0)
    np.minimum.at(first, (call, tr[call, slot]), pos[call, slot])
    first = np.where(first == big, 0, first)
    return (np.maximum(first - window + 1, 0) // page).reshape(
        lead + (n_rows,))


def ragged_block_pages(page: int, max_pages: int) -> int:
    """G: the pages of one row that one step of the ragged kernel folds
    together. Static, from the shapes a call sees: as many as fill the 128
    lanes with keys, never more than the table is wide."""
    return max(1, min(128 // page, max_pages))


def _listed_pages(kv_lens, page: int, max_pages: int, first_pages):
    """Pages each row's work list covers, on the host in numpy: (..., R)."""
    pages = np.minimum(-(-np.asarray(kv_lens, np.int64) // page), max_pages)
    if first_pages is not None:
        pages = pages - np.minimum(first_pages, pages)
    return pages


def ragged_live_pages(kv_lens, page: int, max_pages: int,
                      first_pages=None) -> np.ndarray:
    """Live (row, page) pairs of each ragged kernel call, on the host in
    numpy, for the engine's work record: the pages the call's blocks hold
    (:func:`ragged_live_blocks` counts the blocks). ``kv_lens``: (..., R)
    attendable spans, one call per row of the leading axes; ``first_pages``
    (same shape, from :func:`ragged_first_pages`) where a sliding window
    lets each row's list start past page 0."""
    return _listed_pages(kv_lens, page, max_pages, first_pages).sum(axis=-1)


def ragged_live_blocks(kv_lens, page: int, max_pages: int,
                       first_pages=None) -> np.ndarray:
    """Blocks of each ragged kernel call, on the host in numpy: the work
    list's ``n_live`` (:func:`_ragged_work_list`), every row's listed pages
    in blocks of :func:`ragged_block_pages`, a row's last block as short as
    its pages leave it. Arguments as :func:`ragged_live_pages`. A call's
    grid walks its blocks, and takes one step when it has none (the step
    that zeroes the output)."""
    g = ragged_block_pages(page, max_pages)
    pages = _listed_pages(kv_lens, page, max_pages, first_pages)
    return (-(-pages // g)).sum(axis=-1)


def ragged_shared_blocks(block_tables, kv_lens, page: int) -> np.ndarray:
    """Leading blocks each row of each latent kernel call folds under ANOTHER
    row's work item, on the host in numpy, for the engine's work record: the
    twin of :func:`_shared_block_groups` (its ``nshared``; tests hold the two
    equal). ``block_tables`` (R, W): ONE table for all the calls;
    ``kv_lens`` (..., R), one call per row of the leading axes; returns
    ``kv_lens``' shape. Rows pair up by their first block's ids, each
    (row, leader) pair that occurs is compared once, and a call bounds the
    result by both rows' full blocks."""
    tables = np.asarray(block_tables)
    n_rows, width = tables.shape
    kv = np.asarray(kv_lens, np.int64)
    group = ragged_block_pages(page, width)
    n_blocks = width // group
    calls = kv.reshape(-1, n_rows)
    full = np.minimum(calls // (group * page), n_blocks)
    rows = np.arange(n_rows)
    shared = np.zeros_like(calls)
    # candidates by the first page's id, then by the first block's G ids
    same = tables[:, None, 0] == tables[None, :, 0]         # (R, R)
    same &= (full > 0).any(0)[None, :]
    if same.sum() == np.count_nonzero(same.diagonal()):
        return shared.reshape(kv.shape)                     # nothing shared
    first = tables[:, :group]
    same &= (first[:, None] == first[None, :]).all(-1)
    # a call's leader of a row: the lowest row with a full first block of
    # the same ids (argmax: the first True; a row agrees with itself)
    lead = np.where(full > 0, (same[None] & (full > 0)[:, None, :]).argmax(-1),
                    rows)
    call, row = np.nonzero(lead != rows)
    if len(call):
        leader = lead[call, row]
        pairs, of = np.unique(row * n_rows + leader, return_inverse=True)
        cut = n_blocks * group
        differ = tables[pairs // n_rows, :cut] != tables[pairs % n_rows, :cut]
        common = np.where(differ.any(-1), differ.argmax(-1) // group,
                          n_blocks)
        blocks = -(-_listed_pages(calls, page, width, None) // group)
        shared[call, row] = np.minimum(
            np.minimum(common[of], blocks[call, row] - 1),
            np.minimum(full[call, row], full[call, leader]))
    return shared.reshape(kv.shape)


def _shared_block_groups(block_tables, kv_lens, page: int):
    """Which rows of one latent kernel call hold the same leading blocks, in
    the program, from the block table's equalities and ``kv_lens`` alone (a
    layer's page offset added to every entry changes nothing). A block is
    G = :func:`ragged_block_pages` pages; it is SHARED by two rows where
    all its G ids agree and it lies inside both rows' FULL blocks
    (``kv_lens // (G x page)``: ids past a row's live pages are stale, and
    the block a row writes this call is its own). Returns two (R,) int32
    vectors:

    - ``nshared[r]``: the leading blocks row ``r`` has in common with its
      leader; 0 for a leader, for a row that shares nothing and for a row
      without a full block (``kv_lens`` 0: no token this round). Never a
      row's last block: every row keeps an item of its own, the one that
      writes its output.
    - ``lead[r]``: the lowest row whose full first block has the same ids
      where ``nshared[r]`` > 0, else ``r`` itself.

    O(R^2 G + R W) integer work."""
    n_rows, max_pages = block_tables.shape
    group = ragged_block_pages(page, max_pages)
    n_blocks = max_pages // group
    rows = jnp.arange(n_rows, dtype=jnp.int32)
    kv = kv_lens.astype(jnp.int32)
    full = jnp.minimum(kv // (group * page), n_blocks)
    blocks = block_tables[:, :n_blocks * group].reshape(
        n_rows, n_blocks, group)
    first = blocks[:, 0]
    same = jnp.all(first[:, None] == first[None, :], axis=-1) \
        & (full > 0)[:, None] & (full > 0)[None, :]
    lead = jnp.where(full > 0,
                     jnp.min(jnp.where(same, rows[None, :], n_rows), axis=1),
                     rows)
    differs = jnp.any(blocks != jnp.take(blocks, lead, axis=0), axis=-1)
    common = jnp.min(jnp.where(
        differs, jnp.arange(n_blocks, dtype=jnp.int32)[None, :], n_blocks),
        axis=1)
    row_blocks = (jnp.minimum((kv + (page - 1)) // page, max_pages)
                  + (group - 1)) // group
    nshared = jnp.minimum(jnp.minimum(common, row_blocks - 1),
                          jnp.minimum(full, jnp.take(full, lead)))
    nshared = jnp.where(lead == rows, 0, nshared)
    return nshared, jnp.where(nshared > 0, lead, rows)


def _follower_tokens(mine, nshared, lead, n_blocks: int):
    """The tokens a leader's items fold beside the leader's own, as lists
    the kernel reads with scalar loads: the followers' tokens, sorted by
    leader, then by their row's shared blocks (most first), then by packed
    index, so that the tokens that hold a leader's block ``b`` (``nshared``
    of their row > ``b``) are a PREFIX of the leader's stretch. ``mine``
    (R, T): token ``t`` is row ``r``'s. Returns ``ftok`` (T,) packed token
    indices, ``fshare`` (T,) their rows' ``nshared``, ``fstart`` (R,) and
    ``flen`` (R,): a leader's stretch of the two; all int32. O(T^2 + R T)
    integer work, no sort."""
    n_rows, t = mine.shape
    idx = jnp.arange(t, dtype=jnp.int32)
    rows = jnp.arange(n_rows, dtype=jnp.int32)
    share_t = jnp.sum(jnp.where(mine, nshared[:, None], 0), axis=0)
    lead_t = jnp.sum(jnp.where(mine, lead[:, None], 0), axis=0)
    follows = share_t > 0
    past = n_rows * (n_blocks + 1) * t          # every follower's key < past
    assert past + t < 2 ** 31
    key = jnp.where(
        follows, (lead_t * (n_blocks + 1) + (n_blocks - share_t)) * t, past
    ) + idx
    rank = jnp.sum(key[None, :] < key[:, None], axis=1, dtype=jnp.int32)
    lands = rank[None, :] == idx[:, None]       # [slot, token]
    ftok = jnp.sum(jnp.where(lands, idx[None, :], 0), axis=1)
    fshare = jnp.sum(jnp.where(lands, share_t[None, :], 0), axis=1)
    of = follows[None, :] & (lead_t[None, :] == rows[:, None])
    before = follows[None, :] & (lead_t[None, :] < rows[:, None])
    return (ftok, fshare, jnp.sum(before, axis=1, dtype=jnp.int32),
            jnp.sum(of, axis=1, dtype=jnp.int32))


def _work_item_bits(max_pages: int) -> int:
    """Bits of a work item that hold the page index (static; the row sits
    above them, and a list that SMEM can hold is far from 31 bits)."""
    return max(1, (max_pages - 1).bit_length())


def _unpack_work_item(item, page_bits: int):
    """(row, index within the row's table of the block's first page, is the
    row's first listed block, is the row's last block)."""
    return (item >> (page_bits + 2), (item >> 2) & ((1 << page_bits) - 1),
            (item & 2) == 2, (item & 1) == 1)


def _row_first_pages(token_row, positions, n_rows: int, page: int, window):
    """In-program twin of :func:`ragged_first_pages` for one call:
    (n_rows,) int32."""
    big = jnp.iinfo(jnp.int32).max
    mine = token_row[None, :] == jnp.arange(n_rows, dtype=jnp.int32)[:, None]
    first = jnp.min(jnp.where(mine, positions[None, :].astype(jnp.int32),
                              big), axis=1)
    first = jnp.where(first == big, 0, first)
    return jnp.maximum(first - window + 1, 0) // page


def _ragged_work_list(kv_lens, page: int, max_pages: int, first_pages=None):
    """The kernel's grid as data: the live blocks of one call, row-major,
    one packed int32 each (see :func:`_unpack_work_item`). A block is up to
    G = :func:`ragged_block_pages` consecutive pages of one row, counted
    from the row's first listed page; only a row's last block can hold
    fewer. Returns (items (n_rows * ceil(max_pages / G),), n_live); only
    the first ``n_live`` items are work, the rest hold in-range indices.
    Without ``first_pages`` it depends on ``kv_lens`` alone, so under a
    scan over layers it is loop-invariant. ``first_pages`` (n_rows,): a row
    lists only pages ``first_pages[r] .. ceil(kv_lens[r] / page) - 1``,
    what a sliding window can reach (:func:`_row_first_pages`), and its
    blocks count from ``first_pages[r]``, not from a multiple of G."""
    n_rows = kv_lens.shape[0]
    page_bits = _work_item_bits(max_pages)
    group = ragged_block_pages(page, max_pages)
    # the min keeps over-decoded rows (kv_lens past the table span) inside
    # their table
    pages_r = jnp.minimum((kv_lens.astype(jnp.int32) + (page - 1)) // page,
                          max_pages)[:, None]               # (R, 1)
    if first_pages is not None:
        first_r = jnp.minimum(first_pages.astype(jnp.int32)[:, None], pages_r)
        pages_r = pages_r - first_r                         # pages LISTED
    blocks_r = (pages_r + (group - 1)) // group
    ends = jnp.cumsum(blocks_r, axis=0)
    i = jnp.arange(n_rows * (-(-max_pages // group)), dtype=jnp.int32)[None, :]
    # done[r, i]: row r's blocks all come before item i. Reductions of it
    # (no gather): the item's row, the row's first item, and whether the
    # next item belongs to a later row
    done = ends <= i
    row = jnp.sum(done, axis=0, dtype=jnp.int32)
    first = jnp.sum(jnp.where(done, blocks_r, 0), axis=0, dtype=jnp.int32)
    last = jnp.sum(ends <= i + 1, axis=0, dtype=jnp.int32) > row
    # items past the list: keep their indices inside the block table
    row = jnp.minimum(row, n_rows - 1)
    b = i[0] - first                        # the block's number in its row
    is_first = (b == 0).astype(jnp.int32)
    j = b * group
    if first_pages is not None:
        # the item's row's first page, again as a reduction of ``done``:
        # first_r[0] plus the steps first_r takes over the rows done
        step_r = jnp.concatenate([first_r[1:] - first_r[:-1],
                                  jnp.zeros((1, 1), jnp.int32)], axis=0)
        j = j + first_r[0, 0] + jnp.sum(jnp.where(done, step_r, 0), axis=0,
                                        dtype=jnp.int32)
    j = jnp.clip(j, 0, max_pages - 1)
    return ((row << (page_bits + 2)) | (j << 2) | (is_first << 1)
            | last.astype(jnp.int32), ends[-1, 0])


def _block_dma(block_tables_ref, page_bits: int, group: int, pools):
    """The page copies of one work item, for both ragged kernels. ``pools``:
    per pool array its (HBM ref, VMEM double buffer (2, G, page, ...), DMA
    semaphores (2,)). Returns ``fetch(item, side)``, which starts the copies
    of the item's G pages into buffer half ``side`` (a slot past the row's
    last page reads a page clipped into the table, under key positions no
    token of the row has reached; the G copies of a pool's block share one
    semaphore), and ``wait(side)`` (a wait reads only the copy's size and
    semaphore, not its page)."""
    max_pages = block_tables_ref.shape[1]

    def copies(p, side, slot):
        return [pltpu.make_async_copy(hbm.at[p], buf.at[side, slot],
                                      sem.at[side])
                for hbm, buf, sem in pools]

    def fetch(item, side):
        row, first_page, _, _ = _unpack_work_item(item, page_bits)
        for slot in range(group):
            p = block_tables_ref[row, jnp.minimum(first_page + slot,
                                                  max_pages - 1)]
            for copy in copies(p, side, slot):
                copy.start()

    def wait(side):
        for slot in range(group):
            for copy in copies(0, side, slot):
                copy.wait()

    return fetch, wait


def _stream_blocks(i, n_live, work_ref, fetch):
    """The double buffer's schedule, for both ragged kernels: step 0 starts
    its own block's copies, and every step starts the next block's into the
    other half, so that it streams in while this one is folded."""
    @pl.when((i == 0) & (n_live > 0))
    def _fetch_first():
        fetch(work_ref[0], 0)

    @pl.when(i + 1 < n_live)
    def _fetch_next():
        fetch(work_ref[i + 1], 1 - i % 2)


def _ragged_attention_kernel(block_tables_ref, work_ref, n_live_ref,
                             window_ref, token_row_ref, positions_ref, q_ref,
                             k_hbm, v_hbm, o_ref, m_ref, l_ref, acc_ref,
                             k_buf, v_buf, sems, *, page: int, group: int,
                             page_bits: int, scale: float, windowed: bool):
    keys = group * page
    i = pl.program_id(0)
    r, j, first, last = _unpack_work_item(work_ref[i], page_bits)
    # false only in the one step of a call that has no live block
    live = i < n_live_ref[0]
    half = i % 2
    fetch, wait = _block_dma(
        block_tables_ref, page_bits, group,
        [(k_hbm, k_buf, sems.at[0]), (v_hbm, v_buf, sems.at[1])])
    _stream_blocks(i, n_live_ref[0], work_ref, fetch)

    @pl.when(i == 0)
    def _zero_out():
        # pad slots (token_row -1) and tokens of rows with no page belong
        # to no work item and are never merged; zero the whole output
        # once so their lanes hold finite values (uninitialized VMEM
        # garbage scattered into the pool could poison masked softmax
        # lanes of OTHER rows via 0 * NaN)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live & first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)          # (nkv, T*rep, d)
        wait(half)

        def block(buf):
            # the block's pages as one (nkv, G*page, d) operand: batched
            # matmul wants the batch (kv-head) dim leading on both
            # operands (Mosaic "batch dims must be equal" — round-2
            # finding)
            pages = buf[half].reshape((keys,) + buf.shape[3:])
            return pages.astype(jnp.float32).swapaxes(0, 1)

        tr = token_row_ref[...]                     # (T*rep, 1) int32
        pos = positions_ref[...]                    # (T*rep, 1) int32
        key_pos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (tr.shape[0], keys), 1)      # (T*rep, G*page)
        mask = (tr == r) & (key_pos <= pos)
        if windowed:
            # a block wholly below a token's window leaves that token's
            # state at its init (m = _NEG_INF, p = 1): the first block with
            # a key it sees rescales that by exp(_NEG_INF - m) = 0
            mask = mask & (key_pos > pos - window_ref[0])
        s = jax.lax.dot_general(
            q, block(k_buf), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (nkv, T*rep, keys)
        s = jnp.where(mask[None], s, _NEG_INF)
        m_prev = m_ref[:, :, :1]
        l_prev = l_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        pv = jax.lax.dot_general(
            p, block(v_buf), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)     # (nkv, T*rep, d)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(live & last)
    def _finalize():
        l = l_ref[:, :, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = acc_ref[...] / safe_l
        mine = (token_row_ref[...] == r)[None]      # (1, T*rep, 1)
        o_ref[...] = jnp.where(mine, out.astype(o_ref.dtype), o_ref[...])


def ragged_paged_attention_pallas(q, k_pages, v_pages, block_tables,
                                  token_row, positions, kv_lens,
                                  scale: Optional[float] = None,
                                  interpret: bool = False, window=None):
    """Pallas ragged kernel: same contract as
    :func:`ragged_paged_attention_array`.

    The grid is a work list of the call's live blocks
    (:func:`_ragged_work_list`, built here from ``kv_lens``), its extent
    their number — a traced scalar, so a call costs what its rows attend
    to, not rows x table width. A block is up to G consecutive pages of
    one row, G = :func:`ragged_block_pages` (128 keys: one lane width, one
    MXU tile). The pools stay in HBM: each step waits for its block's G
    physical pages, which the step before started copying into the other
    half of a VMEM double buffer through the scalar-prefetched work list
    and block table (one copy a page slot: 2 DMAs a page cost a third of
    what 2G auto-pipelined operands did; a slot past the row's last page is
    clipped into the table and masked by position), and folds them in ONE
    online-softmax merge into every packed token that belongs to the row
    — decode and prefill tokens alike, so a mixed batch is one dispatch
    whose shape is invariant to the request mix (PAPERS.md ragged paged
    attention). Inside, heads lead: queries, softmax state and output are
    (nkv, T x rep, ·), laid out here before and after the call, so a step
    relays out nothing but the block's K and V. A call with no live block
    takes one step, which copies nothing and zeroes the output.

    ``window`` (None, or an int32 scalar that may be traced): the mask also
    asks ``key_pos > position - window``, and each row's list starts at the
    first page its earliest token of the call can still see, so a window
    row costs the blocks its window reaches. None traces neither: the
    program and its outputs are what they were without the argument.
    """
    t, nh, d = q.shape
    page = k_pages.shape[1]
    nkv = k_pages.shape[2]
    n_rows, max_pages = block_tables.shape
    rep = nh // nkv
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    page_bits = _work_item_bits(max_pages)
    group = ragged_block_pages(page, max_pages)
    windowed = window is not None
    window = jnp.asarray(window if windowed else 0, jnp.int32).reshape(1)
    work, n_live = _ragged_work_list(
        kv_lens, page, max_pages,
        _row_first_pages(token_row, positions, n_rows, page, window[0])
        if windowed else None)

    def per_head_row(x):                # (T,) -> (T*rep, 1), as q's rows
        return jnp.repeat(x.astype(jnp.int32), rep).reshape(t * rep, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # block_tables, work list, n_live, window
        grid=(jnp.maximum(n_live, 1),),
        in_specs=[
            pl.BlockSpec((t * rep, 1), lambda i, *_: (0, 0)),
            pl.BlockSpec((t * rep, 1), lambda i, *_: (0, 0)),
            pl.BlockSpec((nkv, t * rep, d), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((nkv, t * rep, d), lambda i, *_: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv, t * rep, 128), jnp.float32),
            pltpu.VMEM((nkv, t * rep, 128), jnp.float32),
            pltpu.VMEM((nkv, t * rep, d), jnp.float32),
            pltpu.VMEM((2, group, page, nkv, d), k_pages.dtype),
            pltpu.VMEM((2, group, page, nkv, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(
        _ragged_attention_kernel, page=page, group=group,
        page_bits=page_bits, scale=s, windowed=windowed)
    out = pl.pallas_call(
        kernel,
        name="ragged_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nkv, t * rep, d), v_pages.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), work, n_live.reshape(1), window,
      per_head_row(token_row), per_head_row(positions),
      q.reshape(t, nkv, rep, d).swapaxes(0, 1).reshape(nkv, t * rep, d),
      k_pages, v_pages)
    return out.reshape(nkv, t, rep, d).swapaxes(0, 1).reshape(t, nh, d)


def ragged_paged_attention(q, k_pages, v_pages, block_tables, token_row,
                           positions, kv_lens, scale: Optional[float] = None,
                           mesh=None, mp_axis: str = "mp", window=None):
    """Dispatcher: Pallas ragged kernel on TPU (FLAGS_use_pallas_kernels),
    XLA gather/mask fallback elsewhere — selected automatically, same
    contract either way (see ragged_paged_attention_array).

    ``mesh`` (a serving TP mesh with ``mp_axis`` degree > 1) only
    matters on the Pallas path: ``pallas_call`` cannot be partitioned by
    GSPMD, so the kernel runs under ``shard_map`` — each chip holds its
    GQA group slice of ``q``/``k_pages``/``v_pages`` (head-sharded
    pool), the row metadata is replicated, and the per-chip kernels are
    byte-identical to the single-chip kernel over their head slice
    (attention has no cross-head reduction, so there is no collective
    here at all). The XLA path ignores ``mesh``: GSPMD partitions the
    gather/einsum graph from the operand shardings alone.

    Pools WITHOUT a head axis, (P, page, d): multi-query attention, every
    query head on the one K and V a token keeps (a head axis of one would be
    padded to a sublane tile on the device). Full attention on one chip; on
    the Pallas path the latent kernel's row-local walk with V as a pool of
    its own (:func:`mla_paged_attention_pallas`), under this kernel's
    name."""
    from ._common import use_pallas
    # not a traced-shape branch: the pools' RANK is their layout (a head
    # axis or none), fixed by the model's cache layout at construction
    # tpu-lint: disable=trace-shape-branch
    if k_pages.ndim == 3:
        if window is not None:
            raise ValueError("pools without a head axis take no window")
        if use_pallas():
            return mla_paged_attention_pallas(
                q, k_pages, block_tables, token_row, positions, kv_lens,
                scale, v_pool=v_pages, name="ragged_paged_attention")
        return ragged_paged_attention_array(
            q, k_pages[:, :, None], v_pages[:, :, None], block_tables,
            token_row, positions, kv_lens, scale)
    if use_pallas():
        # not a traced-shape branch: Mesh.shape is the STATIC axis-degree
        # mapping of a construction-time mesh (engine compile keys carry
        # the chip count, so the specialisation is deliberate + counted)
        # tpu-lint: disable=trace-shape-branch
        if mesh is not None and mp_axis in mesh.shape \
                and mesh.shape[mp_axis] > 1:
            return _ragged_paged_attention_shard_mapped(
                q, k_pages, v_pages, block_tables, token_row, positions,
                kv_lens, scale, mesh, mp_axis, window=window)
        return ragged_paged_attention_pallas(
            q, k_pages, v_pages, block_tables, token_row, positions,
            kv_lens, scale, window=window)
    return ragged_paged_attention_array(
        q, k_pages, v_pages, block_tables, token_row, positions, kv_lens,
        scale, window=window)


def _ragged_paged_attention_shard_mapped(q, k_pages, v_pages, block_tables,
                                         token_row, positions, kv_lens,
                                         scale, mesh, mp_axis: str,
                                         interpret: bool = False,
                                         window=None):
    """The Pallas ragged kernel over a head-sharded pool: shard_map over
    ``mp_axis`` with whole GQA groups per chip. q: (T, nh, d) sharded on
    heads; pools: (LP, page, nkv, d) sharded on kv heads; metadata
    replicated; out (T, nh, d) sharded on heads. ``interpret`` runs the
    kernel in Pallas interpret mode (the CPU parity test for this
    multi-chip wrapper)."""
    from jax.sharding import PartitionSpec as P
    from ..core.compat import shard_map

    def local(q_l, kp_l, vp_l, bt, tr, pos, kvl, *win):
        return ragged_paged_attention_pallas(
            q_l, kp_l, vp_l, bt, tr, pos, kvl, scale, interpret=interpret,
            window=win[0] if win else None)

    # a window is one more replicated scalar operand
    win = () if window is None else (jnp.asarray(window, jnp.int32),)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(None, mp_axis, None),
                  P(None, None, mp_axis, None),
                  P(None, None, mp_axis, None),
                  P(None, None), P(None), P(None), P(None))
        + (P(),) * len(win),
        out_specs=P(None, mp_axis, None),
        check_vma=False,
    )(q, k_pages, v_pages, block_tables, token_row, positions, kv_lens, *win)


# ---------------------------------------------------------------------------
# Latent (MLA) ragged paged attention: one pool, no head axis
# ---------------------------------------------------------------------------

#: tokens of one row the latent kernel folds a block into at a time: 4 tokens
#: x 64 heads fill the MXU's rows on a prefill row (scratch timing on the
#: chip, PERF.md section 6, PR 31: 10.9 us a block against 18.7 one token at
#: a time, 10.2 at 8); a decode row is one token whatever this says
_MLA_TOKEN_TILE = 4

def mla_paged_attention_array(q, pool, block_tables, token_row, positions,
                              kv_lens=None, scale: Optional[float] = None,
                              value_dim: Optional[int] = None):
    """XLA reference of the latent ragged kernel (gather/mask composition).

    Multi-head latent attention in its absorbed form: every head attends to
    the SAME entry of a token, the pool has no head axis, and the values are
    the first ``value_dim`` numbers of the keys (the normed latent; the rest
    is the shared roped key).

    q:            (T, nh, d)    — packed queries ``[q~ | q_rope]``
    pool:         (P, page, d)  — a token's entry ``[c_kv | k_rope]``
    block_tables, token_row, positions, kv_lens: as
                  :func:`ragged_paged_attention_array`; the mask is
                  ``key_pos <= positions[t]``
    Returns (T, nh, value_dim)."""
    t, nh, d = q.shape
    page = pool.shape[1]
    n_rows, max_pages = block_tables.shape
    value_dim = d if value_dim is None else value_dim
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    row_c = jnp.clip(token_row, 0, n_rows - 1)
    bt_tok = jnp.take(block_tables, row_c, axis=0)          # (T, W)
    kv = jnp.take(pool, bt_tok, axis=0).reshape(t, max_pages * page, d)
    key_pos = jnp.arange(max_pages * page)[None, :]
    mask = (key_pos <= positions[:, None]) & (token_row >= 0)[:, None]
    scores = jnp.einsum("thd,tsd->ths", q.astype(jnp.float32),
                        kv.astype(jnp.float32)) * s
    scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("ths,tsv->thv", probs.astype(pool.dtype),
                      kv[..., :value_dim])


def _mla_attention_kernel(block_tables_ref, work_ref, n_live_ref,
                          tok_start_ref, tok_count_ref, fstart_ref,
                          flen_ref, ftok_ref, fshare_ref, positions_ref,
                          q_ref,
                          *refs, page: int, group: int, page_bits: int,
                          scale: float, value_dim: int, value_pool: bool):
    keys = group * page
    tile = _MLA_TOKEN_TILE
    t_slots, heads, d = q_ref.shape
    i = pl.program_id(0)
    r, j, _, last = _unpack_work_item(work_ref[i], page_bits)
    # false only in the one step of a call that has no live block
    live = i < n_live_ref[0]
    half = i % 2
    if value_pool:
        # the values are a pool of their own (K and V without a head axis:
        # multi-query attention), copied beside the keys' block
        (pool_hbm, v_hbm, o_ref, m_ref, l_ref, acc_ref, buf, v_buf,
         sems) = refs
        pools = [(pool_hbm, buf, sems.at[0]), (v_hbm, v_buf, sems.at[1])]
    else:
        pool_hbm, o_ref, m_ref, l_ref, acc_ref, buf, sems = refs
        pools = [(pool_hbm, buf, sems)]
    fetch, wait = _block_dma(block_tables_ref, page_bits, group, pools)
    _stream_blocks(i, n_live_ref[0], work_ref, fetch)

    @pl.when(i == 0)
    def _zero_out():
        # pad slots and tokens of rows with no page belong to no work item
        # (see the GQA kernel)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _compute():
        wait(half)
        key_pos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, keys), 1)
        # a token's state starts at its row's block 0, whichever row's item
        # folds it (a follower's first listed block is not its first block)
        first = j == 0

        def fold(toks):
            """Fold the block into the tokens ``toks`` (packed indices, of
            any rows that hold the block): ``len(toks) x heads`` query rows
            in one operand, and no other token's."""
            tt = len(toks)
            rows = tt * heads
            # the block: keys are its rows as they lie (no head axis,
            # nothing to transpose), values their first lanes. Read where
            # it is folded: one value held across the regions below costs
            # every step ~0.15 us (scratch timing, PERF.md section 6, PR 34)
            block = buf[half].reshape(keys, d)
            values = (v_buf[half].reshape(keys, value_dim) if value_pool
                      else block[:, :value_dim])

            def load(ref, lanes):
                return jnp.stack([ref[t] for t in toks]).reshape(rows, lanes)

            q = load(q_ref, d)
            pos = positions_ref[toks[0]]
            if tt > 1:
                token_of = jax.lax.broadcasted_iota(
                    jnp.int32, (rows, 1), 0) // heads
                for k in range(1, tt):
                    pos = jnp.where(token_of == k, positions_ref[toks[k]],
                                    pos)
            sc = jax.lax.dot_general(
                q, block, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (rows, keys)
            sc = jnp.where(key_pos <= pos, sc, _NEG_INF)
            m_prev = jnp.where(first, _NEG_INF, load(m_ref, 128)[:, :1])
            l_prev = jnp.where(first, 0.0, load(l_ref, 128)[:, :1])
            acc_prev = jnp.where(first, 0.0, load(acc_ref, value_dim))
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc_prev * alpha + jax.lax.dot_general(
                p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # (rows, value_dim)
            m_new = jnp.broadcast_to(m_new, (rows, 128)).reshape(
                tt, heads, 128)
            l_new = jnp.broadcast_to(l_new, (rows, 128)).reshape(
                tt, heads, 128)
            acc = acc.reshape(tt, heads, value_dim)
            for k, t in enumerate(toks):
                m_ref[t], l_ref[t], acc_ref[t] = m_new[k], l_new[k], acc[k]

        # The item's tokens: the row's own, then, where the row leads a
        # group, the followers' tokens that hold this block too (a prefix
        # of the leader's stretch of ``ftok``: :func:`_follower_tokens`).
        # They are folded in operands of ``_MLA_TOKEN_TILE`` tokens and one
        # shorter operand for the rest, whichever rows they belong to, so
        # the block is copied once and loaded into the MXU once for all of
        # them. Per token the blocks still arrive in ascending order.
        own, t0 = tok_count_ref[r], tok_start_ref[r]
        f0 = fstart_ref[r]
        held = jax.lax.fori_loop(
            0, flen_ref[r], lambda k, c: c + (
                fshare_ref[f0 + k] * group > j).astype(jnp.int32),
            jnp.int32(0))
        n = own + held

        def token(k):
            follower = ftok_ref[jnp.clip(f0 + k - own, 0, t_slots - 1)]
            return jnp.where(k < own, t0 + k, follower)

        def tile_body(k, carry):
            fold([token(k * tile + s) for s in range(tile)])
            return carry

        jax.lax.fori_loop(0, n // tile, tile_body, 0)
        rest = n % tile
        for tt in range(1, tile):
            @pl.when(rest == tt)
            def _fold_rest(tt=tt):
                fold([token(n - rest + s) for s in range(tt)])

        @pl.when(last)
        def _finalize():
            # the row's last item (its own: a shared block is never a row's
            # last) normalises the row's tokens
            def write(k, carry):
                t = tok_start_ref[r] + k
                l_t = l_ref[t][:, :1]
                o_ref[t] = (acc_ref[t] / jnp.where(l_t == 0.0, 1.0, l_t)
                            ).astype(o_ref.dtype)
                return carry

            jax.lax.fori_loop(0, tok_count_ref[r], write, 0)


def mla_paged_attention_pallas(q, pool, block_tables, token_row, positions,
                               kv_lens, scale: Optional[float] = None,
                               value_dim: Optional[int] = None,
                               interpret: bool = False, v_pool=None,
                               name: str = "mla_paged_attention"):
    """Pallas latent ragged kernel: same contract as
    :func:`mla_paged_attention_array`, with each row's tokens CONTIGUOUS in
    the packed axis (the engine's plan packs them so).

    The grid, the work list of live blocks, the scalar-prefetched block
    table and the double-buffered page copies are the GQA kernel's
    (:func:`ragged_paged_attention_pallas`; ``_ragged_work_list``,
    ``_block_dma``, ``_stream_blocks``). What differs is what a step folds:
    the pool has no head axis, so a block of G pages is ONE (G x page, d)
    operand that is read once, its rows the keys of every head and its
    first ``value_dim`` lanes the values; and a work item folds its block
    into the query rows of the rows that HOLD it only (their tokens x
    heads, found by each row's first token and token count, both
    scalar-prefetched), in operands of up to ``_MLA_TOKEN_TILE`` tokens,
    where the GQA kernel folds every block into all T x rep query rows
    under a mask. Queries, softmax state and output stay (tokens, heads, ·)
    as they come: nothing is laid out before or after the call.

    A work item is a block and ALL the rows that hold it. Rows whose block
    tables name the same leading pages (prefix-cache hits borrow a
    document's pages) form a group (:func:`_shared_block_groups`: from the
    table's equalities and ``kv_lens``, nothing else): the lowest row lists
    the shared blocks, its followers list only what lies past them, and the
    leader's step folds its own tokens and those of the followers that hold
    the block (:func:`_follower_tokens`: a prefix of a sorted list) in the
    same operands, so a shared block is copied once and loaded into the MXU
    once a call and not once a row. Every token still folds its row's
    blocks in ascending order at the same precisions. With no two rows on
    one page the groups are single rows and the list, the copies and the
    folds are those of a kernel that knows no groups.

    ``v_pool`` (P, page, dv): the values are a pool of their own and not the
    keys' first lanes, i.e. multi-query attention over K and V pools without
    a head axis; :func:`ragged_paged_attention` calls it so, under ITS name
    (``name`` is the kernel's name in a device trace)."""
    t, nh, d = q.shape
    page = pool.shape[1]
    n_rows, max_pages = block_tables.shape
    if v_pool is not None:
        value_dim = v_pool.shape[-1]
    value_dim = d if value_dim is None else value_dim
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    page_bits = _work_item_bits(max_pages)
    group = ragged_block_pages(page, max_pages)
    # rows whose tables name the same leading blocks: a follower lists only
    # the blocks past those its leader's items fold for it
    nshared, lead = _shared_block_groups(block_tables, kv_lens, page)
    work, n_live = _ragged_work_list(kv_lens, page, max_pages,
                                     first_pages=nshared * group)
    mine = token_row[None, :] == jnp.arange(n_rows, dtype=jnp.int32)[:, None]
    ftok, fshare, fstart, flen = _follower_tokens(
        mine, nshared, lead, max_pages // group)
    tok_count = jnp.sum(mine, axis=1, dtype=jnp.int32)
    tok_start = jnp.argmax(mine, axis=1).astype(jnp.int32)

    whole = lambda i, *_: (0, 0, 0)
    pools = (pool,) if v_pool is None else (pool, v_pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block_tables, work list, n_live, each row's first token and token
        # count, its followers' stretch of their token list, that list and
        # each listed token's shared blocks, positions
        num_scalar_prefetch=10,
        grid=(jnp.maximum(n_live, 1),),
        in_specs=[pl.BlockSpec((t, nh, d), whole)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec((t, nh, value_dim), whole),
        scratch_shapes=[
            pltpu.VMEM((t, nh, 128), jnp.float32),
            pltpu.VMEM((t, nh, 128), jnp.float32),
            pltpu.VMEM((t, nh, value_dim), jnp.float32),
        ] + [pltpu.VMEM((2, group, page, p.shape[-1]), p.dtype)
             for p in pools]
        + [pltpu.SemaphoreType.DMA((2,) * len(pools))],
    )
    kernel = functools.partial(
        _mla_attention_kernel, page=page, group=group, page_bits=page_bits,
        scale=s, value_dim=value_dim, value_pool=v_pool is not None)
    # queries and output are whole-array blocks (double-buffered by the
    # pipeline) beside the float32 state: past the default scoped limit at
    # the serving shapes (32 tokens x 64 heads: ~16 MB)
    item = jnp.dtype(pool.dtype).itemsize
    lanes = lambda n: -(-n // 128) * 128
    vmem = (2 * t * nh * (lanes(d) + lanes(value_dim)) * item
            + t * nh * (256 + lanes(value_dim)) * 4
            + 2 * group * page * sum(lanes(p.shape[-1]) for p in pools)
            * item)
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, nh, value_dim), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(vmem) + (16 << 20)),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), work, n_live.reshape(1), tok_start,
      tok_count, fstart, flen, ftok, fshare, positions.astype(jnp.int32), q,
      *pools)


def mla_paged_attention(q, pool, block_tables, token_row, positions, kv_lens,
                        scale: Optional[float] = None,
                        value_dim: Optional[int] = None):
    """Dispatcher: the Pallas latent kernel on TPU
    (FLAGS_use_pallas_kernels), its XLA twin elsewhere; same contract
    (:func:`mla_paged_attention_array`). One chip: a pool without a head
    axis has nothing a tensor-parallel mesh could split."""
    from ._common import use_pallas
    impl = mla_paged_attention_pallas if use_pallas() \
        else mla_paged_attention_array
    return impl(q, pool, block_tables, token_row, positions, kv_lens,
                scale, value_dim)


# ---------------------------------------------------------------------------
# What a token keeps in the cache: the model's choice
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """What ONE token keeps per layer in the paged pool: a pool array per
    entry, ``(layers, pages, page) + entry``. Pages, block tables,
    refcounts and the prefix index never look inside an entry: a page is a
    page. ``head_axis``: the axis of every entry that a tensor-parallel
    mesh splits (whole heads a chip), None where an entry has no head axis
    and the pool lives on one chip."""
    entries: Tuple[Tuple[int, ...], ...]
    head_axis: Optional[int] = None
    #: cache layers, the pool's leading axis (None: one a layer of the model;
    #: a model whose other layers keep a recurrent state names the few that
    #: attend, one whose layers attend twice names twice its depth)
    layers: Optional[int] = None

    @property
    def token_elems(self) -> int:
        """Numbers a token keeps per layer, all entries together."""
        return sum(math.prod(e) for e in self.entries)

    def check_degree(self, chips: int) -> None:
        """Refuse a tensor-parallel degree the entries cannot be split by."""
        if chips <= 1:
            return
        if self.head_axis is None:
            raise ValueError(
                f"a cache entry of {self.entries} has no head axis: it "
                f"cannot be split over a TP degree of {chips}")
        heads = self.entries[0][self.head_axis]
        if heads % chips:
            raise ValueError(
                f"num_kv_heads={heads} must divide by the TP "
                f"degree {chips} (whole GQA groups per chip; "
                "a split group would split single heads across chips)")

    def pool_specs(self, mp_axis: str = "mp") -> List:
        """PartitionSpec of each pool array on a TP mesh."""
        from jax.sharding import PartitionSpec as P
        specs = []
        for entry in self.entries:
            axes = [None] * (3 + len(entry))    # (layers, pages, page) first
            if self.head_axis is not None:
                axes[3 + self.head_axis] = mp_axis
            specs.append(P(*axes))
        return specs


def kv_cache_layout(num_kv_heads: int, head_dim: int) -> CacheLayout:
    """The default: K and V, each (kv heads, head_dim) a token, split by
    kv head over a TP mesh."""
    entry = (num_kv_heads, head_dim)
    return CacheLayout((entry, entry), head_axis=0)


# ---------------------------------------------------------------------------
# Host-side page pool (the allocator metadata; device arrays hold the data)
# ---------------------------------------------------------------------------

class PagedKVCacheManager:
    """Page pool + per-sequence block tables.

    The reference's KV memory comes from the C++ caching allocator
    (SURVEY.md §2.1 allocators row); on TPU the pool is one pre-allocated
    device array per layer and this class manages only host metadata
    (free list, per-sequence page lists) — no device allocation per step.
    Page 0 is reserved as the pad/garbage page so padded block-table slots
    always point at valid memory.
    """

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 num_kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None, dtype=jnp.bfloat16,
                 mesh=None, mp_axis: str = "mp",
                 layout: Optional[CacheLayout] = None):
        """``layout`` says what a token keeps per layer (:class:`CacheLayout`;
        a model's ``cache_layout(config)``); without one it is K and V with
        a head axis, ``kv_cache_layout(num_kv_heads, head_dim)``. One pool
        array per entry of the layout, ``(layers, pages, page) + entry``.

        ``mesh`` shards every pool over its ``mp_axis`` along the layout's
        head axis: whole GQA (kv-head) groups per chip, so every page's
        bytes split evenly across the TP mesh and attention stays
        head-local. Pure LAYOUT — the allocator metadata (free list,
        tables, lens) is host-side and chip-agnostic, which is what makes
        an elastic resize a rebuild-and-replay rather than a data
        migration. The pools are ALLOCATED sharded (never whole on one
        chip first): a full-depth pool sized for the mesh does not fit a
        single chip."""
        self.page_size = page_size
        self.num_pages = num_pages
        self.layout = layout if layout is not None else kv_cache_layout(
            num_kv_heads, head_dim)
        #: TP chips the pool is head-sharded over (1 = single-chip) — the
        #: memory ledger splits per-chip bytes off it and the engine
        #: stamps it into its compile keys
        self.mesh_chips: int = 1
        shardings = [None] * len(self.layout.entries)
        if mesh is not None:
            from jax.sharding import NamedSharding
            self.mesh_chips = int(mesh.shape[mp_axis])
            self.layout.check_degree(self.mesh_chips)
            shardings = [NamedSharding(mesh, spec)
                         for spec in self.layout.pool_specs(mp_axis)]
        #: the device arrays, one per entry of the layout (K and V by
        #: default, also ``k_pages`` / ``v_pages``)
        self.pools: Tuple = tuple(
            jnp.zeros((num_layers, num_pages, page_size) + tuple(entry),
                      dtype, device=sharding)
            for entry, sharding in zip(self.layout.entries, shardings))
        #: what a ROW keeps in a model's recurrent layers, beside the pages
        #: (``kvcache.state.RowStatePool``; the engine sets it for a model
        #: with a state layout): audited and reported with the pages
        self.state = None
        self._free: List[int] = list(range(num_pages - 1, 0, -1))  # 0 reserved
        self._tables: dict = {}   # seq_id -> List[int]
        self._lens: dict = {}     # seq_id -> int
        self._page_nb: int = 0    # page_nbytes memo (geometry is fixed)

    # the default layout's two arrays under their old names; a layout
    # without a V pool has no ``v_pages``
    @property
    def k_pages(self):
        return self.pools[0]

    @k_pages.setter
    def k_pages(self, value):
        self.pools = (value,) + self.pools[1:]

    @property
    def v_pages(self):
        if len(self.pools) != 2:
            raise AttributeError(
                f"a pool of {len(self.pools)} array(s) has no v_pages")
        return self.pools[1]

    @v_pages.setter
    def v_pages(self, value):
        self.pools = (self.pools[0], value)

    @property
    def arrays(self) -> Tuple:
        """Every array the engine's step takes and returns: the pools, then
        the rows' state where the model keeps one."""
        return self.pools + (self.state.arrays if self.state is not None
                             else ())

    @arrays.setter
    def arrays(self, value):
        n = len(self.pools)
        self.pools = tuple(value[:n])
        if self.state is not None:
            self.state.arrays = tuple(value[n:])

    # -- allocation ---------------------------------------------------------

    def can_allocate(self, n_tokens: int) -> bool:
        return len(self._free) >= self.pages_for(n_tokens)

    @staticmethod
    def pages_needed(n_tokens: int, page_size: int) -> int:
        """Pages covering ``n_tokens`` at ``page_size`` granularity — THE
        page-math helper; every layer (scheduler, engines, kvcache)
        delegates here instead of re-deriving the ceil-div."""
        return (n_tokens + page_size - 1) // page_size

    def pages_for(self, n_tokens: int) -> int:
        return self.pages_needed(n_tokens, self.page_size)

    # deprecated alias (pre-kvcache spelling); new code uses pages_for()
    _pages_for = pages_for

    @property
    def usable_pages(self) -> int:
        """Allocatable pool capacity (page 0 is the reserved pad page)."""
        return self.num_pages - 1

    @property
    def page_nbytes(self) -> int:
        """Measured device bytes of ONE page (every pool array's slab
        across every layer) — the memory ledger's byte unit; an int8 pool
        halves it automatically because it is read off the actual arrays.
        Memoized: the pool's geometry and dtype never change after
        construction."""
        pb = self._page_nb
        if not pb:
            pb = self._page_nb = sum(
                int(p.nbytes) for p in self.pools) // self.num_pages
        return pb

    def _oom(self, source: str, need: int) -> None:
        """Allocation-failure forensics hook: every ``MemoryError`` this
        pool raises first lands in the HBM ledger (``oom_pressure``
        event + once-per-reason ``memory.json`` flight bundle). Gated on
        ``memory_armed`` inside; lazy import keeps the hot allocator
        free of the observability package at import time."""
        from ..observability.memory import note_oom
        note_oom(source, self, need_pages=need,
                 free_pages=len(self._free))

    def allocate(self, seq_id, n_tokens: int) -> List[int]:
        """Reserve pages for a new sequence of n_tokens (prefill)."""
        need = self.pages_for(n_tokens)
        if len(self._free) < need:
            self._oom("allocate", need)
            raise MemoryError(
                f"KV pool exhausted: need {need} pages, "
                f"{len(self._free)} free")
        pages = [self._free.pop() for _ in range(need)]
        self._tables[seq_id] = pages
        self._lens[seq_id] = n_tokens
        return pages

    def extend(self, seq_id, n_new: int = 1) -> None:
        """Grow a sequence; acquires a page on boundary crossings."""
        cur = self._lens[seq_id]
        new_len = cur + n_new
        have = len(self._tables[seq_id])
        need = self.pages_for(new_len)
        for _ in range(need - have):
            if not self._free:
                self._oom("extend", 1)
                raise MemoryError("KV pool exhausted on extend")
            self._tables[seq_id].append(self._free.pop())
        self._lens[seq_id] = new_len

    def free(self, seq_id) -> None:
        self._free.extend(reversed(self._tables.pop(seq_id)))
        self._lens.pop(seq_id)

    def sequence_pages(self, seq_id) -> List[int]:
        """The sequence's block table in token order (a copy — callers
        such as cross-host page export must not alias pool metadata)."""
        return list(self._tables.get(seq_id, ()))

    def sequence_len(self, seq_id) -> int:
        """Committed token length of a live sequence (0 if unknown)."""
        return int(self._lens.get(seq_id, 0))

    # -- speculative tail growth / rollback ----------------------------------

    def grow_to(self, seq_id, n_tokens: int) -> List[int]:
        """Ensure the sequence's block table covers ``n_tokens`` without
        committing them: speculative (drafted) tokens write into page
        tail positions past the committed length, so the pages must
        exist before the dispatch but the committed length (``_lens``)
        stays put until the host verifies the draft. Appended pages come
        fresh from the free list; raises ``MemoryError`` (leaving the
        table untouched) when the pool can't cover the span — callers
        shrink the draft instead. Returns the pages added."""
        need = self.pages_for(n_tokens) - len(self._tables[seq_id])
        if need <= 0:
            return []
        if len(self._free) < need:
            self._oom("grow_to", need)
            raise MemoryError(
                f"KV pool exhausted on speculative grow: need {need} "
                f"pages, {len(self._free)} free")
        added = [self._free.pop() for _ in range(need)]
        self._tables[seq_id].extend(added)
        return added

    def truncate_pages(self, seq_id, keep_pages: int) -> List[int]:
        """Roll a sequence's page span back to its first ``keep_pages``
        pages: the speculative-rollback primitive. A rejected draft
        strands any page that exists only to hold rejected tokens —
        those return to the pool here (stale K/V *within* kept pages
        needs no scrub: the next token at a position overwrites its slot
        before anything attends to it, the same scatter-first contract
        over-decoded garbage already relies on). The committed length is
        clamped into the kept span. Returns the pages returned to the
        free list."""
        table = self._tables[seq_id]
        freed: List[int] = []
        while len(table) > keep_pages:
            p = table.pop()
            self._free.append(p)
            freed.append(p)
        if self._lens.get(seq_id, 0) > keep_pages * self.page_size:
            self._lens[seq_id] = keep_pages * self.page_size
        return freed

    def check_conservation(self) -> None:
        """Exclusive-ownership audit (the refcounted subclass replaces
        this with the shared-ownership version): every usable page is
        either free or owned by exactly one sequence exactly once, the
        two sets are disjoint, and reserved page 0 never circulates.
        The serving engine runs this after every speculative step even
        without the prefix cache — draft growth/rollback is the first
        path that returns pages mid-sequence, so the books get audited
        on every round that can move them."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise RuntimeError("duplicate pages on the free list")
        owned: List[int] = []
        for table in self._tables.values():
            owned.extend(table)
        owned_set = set(owned)
        if len(owned) != len(owned_set):
            raise RuntimeError("page owned by two sequences (or twice "
                               "by one) under exclusive ownership")
        if free & owned_set:
            raise RuntimeError(
                f"page state overlap: free∩owned={free & owned_set}")
        if 0 in free | owned_set:
            raise RuntimeError("reserved page 0 entered circulation")
        total = len(free) + len(owned_set)
        if total != self.usable_pages:
            raise RuntimeError(
                f"page conservation violated: {len(free)} free + "
                f"{len(owned_set)} owned = {total} != "
                f"{self.usable_pages} usable")
        if self.state is not None:
            self.state.check_conservation(self._tables)

    # -- multi-chip layout (TP-sharded serving) ------------------------------

    # -- views for the op ---------------------------------------------------

    @property
    def num_free_pages(self) -> int:
        return len(self._free)

    def seq_len(self, seq_id) -> int:
        return self._lens[seq_id]

    def block_tables(self, seq_ids) -> Tuple[np.ndarray, np.ndarray]:
        """(block_tables (B, max_pages), seq_lens (B,)) for a batch;
        padded slots point at reserved page 0."""
        tables = [self._tables[s] for s in seq_ids]
        width = max(len(t) for t in tables)
        bt = np.zeros((len(tables), width), np.int32)
        for i, t in enumerate(tables):
            bt[i, :len(t)] = t
        lens = np.asarray([self._lens[s] for s in seq_ids], np.int32)
        return bt, lens
