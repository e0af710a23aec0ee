"""Paged KV attention vs dense oracle + page-pool manager semantics
(SURVEY.md §2.7 #18)."""

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import paged_attention as pa

pytestmark = pytest.mark.slow  # core tier: -m 'not slow'


def _dense_attention(q, k, v, seq_len):
    # q: (nh, d); k/v: (S, nkv, d) valid to seq_len
    nh, d = q.shape
    nkv = k.shape[1]
    rep = nh // nkv
    k = np.repeat(k, rep, axis=1)
    v = np.repeat(v, rep, axis=1)
    scores = np.einsum("hd,shd->hs", q, k) / np.sqrt(d)
    scores[:, seq_len:] = -np.inf
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hs,shd->hd", p, v)


def test_paged_matches_dense_ragged_batch():
    rng = np.random.RandomState(0)
    PAGE, NPAGES, NKV, NH, D = 4, 32, 2, 4, 8
    lens = [7, 13, 1]
    B = len(lens)

    mgr = pa.PagedKVCacheManager(1, NPAGES, PAGE, NKV, D, dtype=jnp.float32)
    # fill each sequence's pages with random KV at the right slots
    k_pool = np.zeros((NPAGES, PAGE, NKV, D), np.float32)
    v_pool = np.zeros((NPAGES, PAGE, NKV, D), np.float32)
    dense_k, dense_v = [], []
    for sid, L in enumerate(lens):
        pages = mgr.allocate(sid, L)
        kk = rng.randn(L, NKV, D).astype(np.float32)
        vv = rng.randn(L, NKV, D).astype(np.float32)
        dense_k.append(kk)
        dense_v.append(vv)
        for t in range(L):
            k_pool[pages[t // PAGE], t % PAGE] = kk[t]
            v_pool[pages[t // PAGE], t % PAGE] = vv[t]

    bt, seq_lens = mgr.block_tables(list(range(B)))
    q = rng.randn(B, NH, D).astype(np.float32)
    out = np.asarray(pa.paged_attention_array(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(bt), jnp.asarray(seq_lens)))

    for b in range(B):
        S = max(seq_lens)  # oracle uses its own dense copy
        ref = _dense_attention(q[b], dense_k[b], dense_v[b], lens[b])
        np.testing.assert_allclose(out[b], ref, rtol=1e-5, atol=1e-5)


def test_paged_write_then_attend():
    rng = np.random.RandomState(1)
    PAGE, NPAGES, NKV, NH, D = 2, 8, 1, 2, 4
    mgr = pa.PagedKVCacheManager(1, NPAGES, PAGE, NKV, D, dtype=jnp.float32)
    pages = mgr.allocate("s", 3)
    k_pool = jnp.zeros((NPAGES, PAGE, NKV, D), jnp.float32)
    v_pool = jnp.zeros((NPAGES, PAGE, NKV, D), jnp.float32)
    ks = rng.randn(3, NKV, D).astype(np.float32)
    vs = rng.randn(3, NKV, D).astype(np.float32)
    bt, lens = mgr.block_tables(["s"])
    for t in range(3):
        k_pool, v_pool = pa.paged_write_array(
            k_pool, v_pool, jnp.asarray(ks[None, t]), jnp.asarray(vs[None, t]),
            jnp.asarray(bt), jnp.asarray([t], np.int32))
    q = rng.randn(1, NH, D).astype(np.float32)
    out = np.asarray(pa.paged_attention_array(
        jnp.asarray(q), k_pool, v_pool, jnp.asarray(bt), jnp.asarray(lens)))
    ref = _dense_attention(q[0], ks, vs, 3)
    np.testing.assert_allclose(out[0], ref, rtol=1e-5, atol=1e-5)


def test_manager_extend_and_free():
    mgr = pa.PagedKVCacheManager(1, num_pages=6, page_size=4,
                                 num_kv_heads=1, head_dim=2)
    free0 = mgr.num_free_pages          # 5 (page 0 reserved)
    mgr.allocate("a", 4)                # 1 page
    assert mgr.num_free_pages == free0 - 1
    mgr.extend("a", 1)                  # crosses boundary -> +1 page
    assert mgr.num_free_pages == free0 - 2
    assert mgr.seq_len("a") == 5
    mgr.extend("a", 2)                  # within page 2 (5->7)
    assert mgr.num_free_pages == free0 - 2
    mgr.free("a")
    assert mgr.num_free_pages == free0


def test_manager_exhaustion():
    mgr = pa.PagedKVCacheManager(1, num_pages=3, page_size=2,
                                 num_kv_heads=1, head_dim=2)
    mgr.allocate("x", 4)  # 2 pages (all free pages)
    assert not mgr.can_allocate(1)
    with pytest.raises(MemoryError):
        mgr.allocate("y", 1)


def test_ragged_paged_generation_matches_reforward():
    """Paged ragged generation == per-row full re-forward greedy decode."""
    from paddle_tpu.models import llama as L
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from _oracle import assert_greedy

    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=9)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 6, 5)]
    NEW = 4
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=NEW), num_slots=3, page_size=4,
        max_seq_len=16)
    out = eng.serve(params, prompts)
    assert_greedy(params, cfg, prompts, out, n_new=NEW)
