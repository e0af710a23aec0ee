"""AFMoE (Trinity family) on the serving path: the sliding window in the
ragged paged-attention kernel and its work list, the dropless expert layer,
``models.afmoe.ragged_step`` through the page cache against the plain
float32 reference (``perfbench/reference/afmoe.py``), and the engine's model
lookup. Small sizes, seeded, float32, on the CPU."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.models import afmoe as A
from paddle_tpu.models import llama as L
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.serving import ServingScheduler
from perfbench import harness

from _oracle import assert_greedy
from test_unified_step import _BLOCKS, _RAGGED_CASES, _packed_case

adapter = harness.load_module("perfbench/adapters/serve_afmoe.py")
reference = harness.load_module("perfbench/reference/afmoe.py")


# ---------------------------------------------------------------------------
# the window in the ragged kernel
# ---------------------------------------------------------------------------
# page 4, table width 4 (16 positions: one block of G = 4 pages a row), window
# 6 unless a case says otherwise; ``**_BLOCKS``: page 16, width 20, G = 8
_WINDOW_CASES = {
    "row_shorter_than_window": dict(
        kv_lens=[4, 3], spans=[(0, 3, 1), (1, 2, 1)]),
    "row_exactly_at_window": dict(
        kv_lens=[6, 7], spans=[(0, 5, 1), (1, 6, 1)]),
    "row_several_pages_past_window": dict(
        kv_lens=[16, 15], spans=[(0, 15, 1), (1, 14, 1)]),
    # positions 3..9: the first tokens see page 0, the last ones do not
    "prefill_span_straddles_the_bound": dict(
        kv_lens=[10, 2], spans=[(0, 3, 7), (1, 1, 1)]),
    "starved_row_between_live_ones": dict(
        kv_lens=[13, 0, 0, 16], spans=[(0, 12, 1), (3, 12, 4)]),
    "window_of_one_page": dict(
        kv_lens=[16, 9], spans=[(0, 15, 1), (1, 6, 3)], window=4),
    "window_of_one_key": dict(
        kv_lens=[11, 5], spans=[(0, 8, 3), (1, 4, 1)], window=1),
    # row 0's earliest token (280) sees keys from 81: its list starts at page
    # 5, in the middle of what would be block 0, and holds 14 pages = blocks
    # at pages 5 and 13; every token's window begins after page 5's first key
    "blocks_start_at_the_windows_first_page": dict(
        kv_lens=[292, 60], spans=[(0, 280, 11), (1, 59, 1)], window=200,
        **_BLOCKS),
    # a window of exactly one block of keys, not aligned to one: 9 pages
    "blocks_window_of_128_keys_over_9_pages": dict(
        kv_lens=[300, 129], spans=[(0, 299, 1), (1, 128, 1)], window=128,
        **_BLOCKS),
    # a 140-token chunk under a window of 16: the list runs from page 5 (of
    # token 100) in blocks at 5 and 13, and the chunk's last tokens see
    # nothing of the first block: their state stays at its init through it
    "blocks_a_token_whose_window_begins_in_a_later_block": dict(
        kv_lens=[240, 33], spans=[(0, 100, 140), (1, 32, 1)], window=16,
        t=144, **_BLOCKS),
    # a decode row whose window reaches back over three blocks' worth of
    # pages from a start that is no multiple of G, starved rows around it
    "blocks_starved_rows_and_a_long_window": dict(
        kv_lens=[0, 310, 0, 75], spans=[(1, 309, 1), (3, 70, 5)], window=290,
        **_BLOCKS),
}


@pytest.mark.parametrize("case", sorted(_WINDOW_CASES))
def test_windowed_pallas_interpret_matches_array(case):
    """The kernel with a window (interpret mode) against the XLA reference
    with the same bound: rows the window does not reach, rows it cuts,
    a prefill span whose tokens start their windows in different pages."""
    spec = dict(_WINDOW_CASES[case])
    window = spec.pop("window", 6)
    args = _packed_case(**spec)
    token_row = args[4]
    jargs = [jnp.asarray(a) for a in args]
    ref = np.asarray(pa.ragged_paged_attention_array(*jargs, window=window))
    out = np.asarray(pa.ragged_paged_attention_pallas(
        *jargs, interpret=True, window=jnp.int32(window)))
    real = token_row >= 0
    np.testing.assert_allclose(out[real], ref[real], rtol=1e-5, atol=1e-6)
    assert np.all(np.isfinite(out)) and np.all(out[~real] == 0.0)
    # and the bound matters: the unbounded mask gives another answer
    # wherever a context is longer than the window
    if max(spec["kv_lens"]) > window:
        full = np.asarray(pa.ragged_paged_attention_array(*jargs))
        assert np.abs(full[real] - ref[real]).max() > 1e-3


@pytest.mark.parametrize("case", sorted(_RAGGED_CASES))
def test_without_a_window_the_kernel_is_bit_equal(case):
    """No window, and a window no position reaches, give bit for bit the
    outputs of the kernel the bound was added to: the mask's second term
    and the work list's first page change nothing they do not cut. (Held
    against the parent commit's kernel by hand, PR 27: same bytes on all
    eight mixes in interpret mode.)"""
    spec = _RAGGED_CASES[case]
    args = spec() if callable(spec) else _packed_case(**spec)
    jargs = [jnp.asarray(a) for a in args]
    plain = np.asarray(pa.ragged_paged_attention_pallas(*jargs,
                                                        interpret=True))
    wide = np.asarray(pa.ragged_paged_attention_pallas(
        *jargs, interpret=True, window=jnp.int32(1 << 30)))
    assert plain.tobytes() == wide.tobytes()
    ref = np.asarray(pa.ragged_paged_attention_array(*jargs))
    ref_wide = np.asarray(pa.ragged_paged_attention_array(
        *jargs, window=1 << 30))
    assert ref.tobytes() == ref_wide.tobytes()


_WORK_LIST_PLANS = [
    # (kv_lens, [(row, first position, tokens)], window[, geometry])
    ([16, 15], [(0, 15, 1), (1, 14, 1)], 6),
    ([10, 2], [(0, 3, 7), (1, 1, 1)], 6),
    ([13, 0, 0, 16], [(0, 12, 1), (3, 12, 4)], 6),
    ([16, 9, 16], [(0, 15, 1), (1, 6, 3), (2, 13, 3)], 4),
    ([0, 0, 0], [], 5),
    ([21, 6], [(0, 15, 1), (1, 5, 1)], 3),          # past the table span
    ([16, 16], [(0, 15, 1), (1, 15, 1)], 1 << 30),  # nothing skipped
    # rows of several blocks (G = 8): lists that start mid-table
    ([292, 60], [(0, 280, 11), (1, 59, 1)], 200, _BLOCKS),
    ([300, 129, 0, 320], [(0, 299, 1), (1, 128, 1), (3, 316, 4)], 128,
     _BLOCKS),
    ([240, 33], [(0, 100, 12), (1, 32, 1)], 16, _BLOCKS),
    ([333, 310], [(0, 319, 1), (1, 309, 1)], 290, _BLOCKS),  # past the table
    ([300, 200], [(0, 299, 1), (1, 190, 10)], 1 << 30, _BLOCKS),
]


@pytest.mark.parametrize("plan", range(len(_WORK_LIST_PLANS)))
def test_windowed_work_list_matches_python_loop(plan):
    """Under a window a row's list starts at the page of the oldest key its
    earliest token of the call still sees, its blocks of G pages count from
    THERE, and ``_init`` fires on that first listed block; the numpy helpers
    of the engine's work record count the same blocks and pages."""
    kv_lens, spans, window, *geometry = _WORK_LIST_PLANS[plan]
    geometry = {"page": 4, "width": 4, **(geometry[0] if geometry else {})}
    page, width, t = geometry["page"], geometry["width"], 12
    group = pa.ragged_block_pages(page, width)
    token_row = np.full((t,), -1, np.int32)
    positions = np.zeros((t,), np.int32)
    at, want, firsts, listed = 0, [], [], 0
    for row, first, n in spans:
        token_row[at:at + n] = row
        positions[at:at + n] = np.minimum(first + np.arange(n),
                                          page * width - 1)
        at += n
    for r, n in enumerate(kv_lens):
        pages = min(-(-n // page), width)
        mine = positions[token_row == r]
        lo = (max(0, int(mine.min()) - window + 1) // page
              if mine.size else 0)
        lo = min(lo, pages)
        firsts.append(lo)
        listed += pages - lo
        blocks = -(-(pages - lo) // group)
        want += [(r, lo + b * group, int(b == 0), int(b == blocks - 1))
                 for b in range(blocks)]
    first_pages = pa._row_first_pages(
        jnp.asarray(token_row), jnp.asarray(positions), len(kv_lens),
        page, jnp.int32(window))
    items, n_live = pa._ragged_work_list(
        jnp.asarray(kv_lens, jnp.int32), page, width, first_pages)
    bits = pa._work_item_bits(width)
    got = [tuple(int(x) for x in pa._unpack_work_item(it, bits))
           for it in np.asarray(items)[:int(n_live)]]
    assert got == want, (kv_lens, spans, window)
    rows, js, _, _ = pa._unpack_work_item(np.asarray(items), bits)
    assert rows.min() >= 0 and rows.max() < len(kv_lens)
    assert js.min() >= 0 and js.max() < width
    host_first = pa.ragged_first_pages(token_row, positions,
                                       len(kv_lens), page, window)
    assert np.minimum(host_first, [min(-(-n // page), width)
                                   for n in kv_lens]).tolist() == firsts
    assert pa.ragged_live_blocks(kv_lens, page, width,
                                 host_first) == int(n_live) == len(want)
    assert pa.ragged_live_pages(kv_lens, page, width, host_first) == listed


# ---------------------------------------------------------------------------
# the dropless expert layer
# ---------------------------------------------------------------------------
def _expert_loop(x, idx, weight, valid, w_gate, w_up, w_down):
    """Every expert applied to every token, masked: no sort, no groups."""
    out = np.zeros_like(x)
    for e in range(w_gate.shape[0]):
        w_e = np.where((idx == e) & valid[:, None], weight, 0.0).sum(-1)
        g = x @ w_gate[e]
        act = g / (1.0 + np.exp(-g)) * (x @ w_up[e])
        out += w_e[:, None] * (act @ w_down[e])
    return out


def _routing(kind, t, k, n_experts, rng):
    if kind == "uniform":
        idx = np.stack([rng.permutation(n_experts)[:k] for _ in range(t)])
    elif kind == "all_on_one_expert":
        # every token's first choice is expert 5: a fixed capacity would
        # drop most of them
        idx = np.stack([np.concatenate([[5], rng.permutation(
            [e for e in range(n_experts) if e != 5])[:k - 1]])
            for _ in range(t)])
        idx[:, 1:] = idx[:, 1:2]        # ... and one more expert each
        idx[:, 1] = np.where(idx[:, 1] == 5, 6, idx[:, 1])
        idx = idx[:, :2] if k == 2 else idx
    else:                               # two experts take everything
        idx = np.tile(np.array([[2, 7]]), (t, 1))
    return idx.astype(np.int32)


@pytest.mark.parametrize("pallas", [False, True], ids=["array", "pallas"])
@pytest.mark.parametrize("kind", ["uniform", "all_on_one_expert",
                                  "two_experts_take_all"])
def test_grouped_expert_ffn_matches_the_masked_loop(kind, pallas,
                                                    monkeypatch):
    """Dropless: whatever the skew every assignment is computed, a pad
    slot routes nowhere and counts nowhere; the Pallas grouped product
    (interpret mode) and the ``jax.numpy`` one agree with a loop over all
    experts."""
    t, k, h, m, n_experts = 16, 2, 16, 24, 8
    rng = np.random.RandomState(3)
    x = rng.randn(t, h).astype(np.float32)
    idx = _routing(kind, t, k, n_experts, rng)
    weight = rng.rand(t, k).astype(np.float32)
    valid = np.ones((t,), bool)
    valid[[3, 9, 15]] = False                   # pad slots
    w_gate = rng.randn(n_experts, h, m).astype(np.float32) * 0.3
    w_up = rng.randn(n_experts, h, m).astype(np.float32) * 0.3
    w_down = rng.randn(n_experts, m, h).astype(np.float32) * 0.3
    if pallas:
        monkeypatch.setattr(moe_ops, "moe_grouped_matmul_array",
                            lambda lhs, rhs, sizes:
                            moe_ops.moe_grouped_matmul_pallas(
                                lhs, rhs, sizes, interpret=True))
    out, stats = moe_ops.grouped_expert_ffn(
        *(jnp.asarray(a) for a in (x, idx, weight, valid, w_gate, w_up,
                                   w_down)))
    want = _expert_loop(x, idx, weight, valid, w_gate, w_up, w_down)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(out)[~valid] == 0.0)
    loads = np.bincount(idx[valid].reshape(-1), minlength=n_experts)
    assert [int(s) for s in stats] == [int((loads > 0).sum()),
                                       int(loads.max()), int(loads.sum())]
    assert int(stats[2]) == k * int(valid.sum())       # nothing dropped


def test_expert_shares_add_up_to_the_whole_layer():
    """The layer computes the part of the result its own experts give
    (``first_expert``, the weights it is handed): the parts of two halves
    add up to the whole layer's output."""
    t, k, h, m, n_experts = 8, 3, 16, 8, 8
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(t, h), jnp.float32)
    idx = jnp.asarray(_routing("uniform", t, k, n_experts, rng))
    weight = jnp.asarray(rng.rand(t, k), jnp.float32)
    valid = jnp.ones((t,), bool)
    ws = [jnp.asarray(rng.randn(*s) * 0.3, jnp.float32) for s in
          ((n_experts, h, m), (n_experts, h, m), (n_experts, m, h))]
    whole, stats = moe_ops.grouped_expert_ffn(x, idx, weight, valid, *ws)
    parts = [moe_ops.grouped_expert_ffn(
        x, idx, weight, valid, *(w[lo:lo + 4] for w in ws), first_expert=lo)
        for lo in (0, 4)]
    np.testing.assert_allclose(np.asarray(parts[0][0] + parts[1][0]),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert int(parts[0][1][2] + parts[1][1][2]) == int(stats[2]) == t * k


_GROUP_SIZES = [[8] * 8, [64, 0, 0, 0, 0, 0, 0, 0], [0, 0, 3, 0, 40, 0, 1, 5],
                [0] * 8, [0, 0, 0, 0, 0, 0, 0, 64], [1] * 8]


@pytest.mark.parametrize("sizes", _GROUP_SIZES, ids=str)
def test_grouped_matmul_kernel_and_its_work_list(sizes):
    """The kernel's grid is the list of (row tile, expert) pairs in which
    the expert owns a row: an expert nobody chose is in no pair, nor is a
    tile nobody reaches (the output starts as zeros); a call in which
    nobody chose anything keeps one item, so that the grid is not empty."""
    m, k, n, tm = 64, 16, 24, 32
    rng = np.random.RandomState(0)
    lhs = rng.randn(m, k).astype(np.float32)
    rhs = rng.randn(len(sizes), k, n).astype(np.float32)
    want, pairs, at = np.zeros((m, n), np.float32), [], 0
    for e, size in enumerate(sizes):
        want[at:at + size] = lhs[at:at + size] @ rhs[e]
        pairs += [(tile, e) for tile in range(m // tm)
                  if size and at < (tile + 1) * tm and at + size > tile * tm]
        at += size
    gs = jnp.asarray(sizes, jnp.int32)
    for fn in (moe_ops.moe_grouped_matmul_array,
               lambda *a: moe_ops.moe_grouped_matmul_pallas(*a,
                                                            interpret=True)):
        np.testing.assert_allclose(
            np.asarray(fn(jnp.asarray(lhs), jnp.asarray(rhs), gs)), want,
            rtol=1e-5, atol=1e-5)
    items, n_items, _, _ = moe_ops._gmm_work_list(gs, m, tm)
    items = np.asarray(items)[:int(n_items)]
    got = sorted((int(it >> 17), int((it >> 1) & 0xFFFF)) for it in items)
    assert got == (sorted(pairs) or [(0, 0)])
    first = [int(it & 1) for it in items]
    tiles = [int(it >> 17) for it in items]
    assert first == [int(i == 0 or tiles[i] != tiles[i - 1])
                     for i in range(len(tiles))]


# ---------------------------------------------------------------------------
# the model against the plain reference, through the page cache
# ---------------------------------------------------------------------------
def _model_dict(cfg):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "sliding_window",
            "num_dense_layers", "num_experts_per_tok", "route_norm",
            "route_scale", "mup_enabled", "num_hidden_layers")
    return dict({k: getattr(cfg, k) for k in keys},
                layer_types=list(cfg.layer_types))


def _logits_through_the_cache(cfg, params, prompt, n_decode, chunk, page=4):
    """Prefill ``prompt`` in chunks of ``chunk`` tokens, then decode
    ``n_decode`` greedy tokens one at a time, all through ``ragged_step``
    and ONE row's pages; returns (tokens fed, the logits after each
    call's last token). A second, idle row sits beside it."""
    width = -(-(len(prompt) + n_decode) // page)
    pool = 1 + 2 * width
    shape = (cfg.num_hidden_layers, pool, page, cfg.num_key_value_heads,
             cfg.head_dim)
    kp, vp = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    bt = np.zeros((2, width), np.int32)
    bt[1] = 1 + np.arange(width)                   # the live row is row 1
    step = jax.jit(lambda *a: A.ragged_step(*a, cfg))
    fed, logits, at = list(prompt), [], 0
    t = max(chunk, 1)
    while len(logits) < n_decode + 1:
        n = min(chunk, len(prompt) - at) if at < len(prompt) else 1
        ids = np.zeros((t,), np.int32)
        token_row = np.full((t,), -1, np.int32)
        positions = np.zeros((t,), np.int32)
        ids[:n] = fed[at:at + n]
        token_row[:n] = 1
        positions[:n] = at + np.arange(n)
        at += n
        lg, kp, vp, aux = step(
            params, jnp.asarray(ids), jnp.asarray(token_row),
            jnp.asarray(positions), jnp.asarray([0, at], jnp.int32),
            jnp.asarray([0, n - 1], jnp.int32), kp, vp, jnp.asarray(bt))
        assert aux.shape == (cfg.num_expert_layers, 3)
        assert int(aux[0, 2]) == n * cfg.num_experts_per_tok
        if at >= len(prompt):
            logits.append(np.asarray(lg[1]))
            fed.append(int(np.argmax(logits[-1])))
    return np.asarray(fed[:-1], np.int32), np.stack(logits)


@pytest.mark.parametrize("chunk", [5, 16])
def test_prefill_then_decode_through_the_cache_matches_the_reference(chunk):
    """LOGITS of prefill-then-decode through the page cache against the
    reference's full forward pass: contexts of up to 37 positions against a
    window of 8, both layer kinds, a dense and two expert layers. Float32
    on both sides, the reference at ``highest`` matmul precision; what is
    left is the order of float32 sums (the cache's page walk, the experts'
    grouping, the rotary embedding's table against its closed form):
    logits of magnitude ~0.5 agree to 2e-4."""
    cfg = A.afmoe_tiny()
    params = A.init_stacked_params(cfg, seed=7)
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, cfg.vocab_size, (29,)).astype(np.int32)
    fed, got = _logits_through_the_cache(cfg, params, prompt, 8, chunk)
    reference.QUERY_BLOCK, saved = 16, reference.QUERY_BLOCK
    try:
        want = np.asarray(reference.logits_at(
            adapter.ReferenceWeights(params, cfg.num_dense_layers), [fed],
            [(len(prompt) - 1, len(fed))], _model_dict(cfg))[0])
    finally:
        reference.QUERY_BLOCK = saved
    assert got.shape == want.shape == (9, cfg.vocab_size)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_engine_serves_afmoe_through_the_unified_step():
    """submit -> ServingScheduler.step -> _step_unified: same entry points,
    prefix cache and page pool as Llama; one compiled program; every served
    token is the reference's argmax for its context (teacher-forced, float32;
    the reference's logit of the served token within 1e-4 of its maximum)."""
    from paddle_tpu.observability.runtime import recompiles
    cfg = A.afmoe_tiny()
    params = A.init_stacked_params(cfg, seed=2)
    before = recompiles.count("cbe.unified_step")
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(seed=0), num_slots=4, page_size=4,
        max_seq_len=64, chunk=4, prefix_cache=True)
    assert eng._L is A
    sched = ServingScheduler(eng)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 20, 33, 9, 14, 41)]
    handles = [sched.submit(p, max_new_tokens=10) for p in prompts]
    while sched.pending:
        sched.step(params)
    assert recompiles.count("cbe.unified_step") == before + 1
    eng.mgr.check_conservation()
    rows = [np.concatenate([p, np.asarray(h.stream.tokens, np.int32)])
            for p, h in zip(prompts, handles)]
    spans = [(len(p) - 1, len(p) - 1 + 10) for p in prompts]
    reference.QUERY_BLOCK, saved = 16, reference.QUERY_BLOCK
    try:
        logits = reference.logits_at(
            adapter.ReferenceWeights(params, cfg.num_dense_layers), rows,
            spans, _model_dict(cfg))
    finally:
        reference.QUERY_BLOCK = saved
    for lg, h in zip(logits, handles):
        lg, toks = np.asarray(lg), np.asarray(h.stream.tokens)
        assert h.state == "done" and len(toks) == 10
        assert (lg.max(-1) - lg[np.arange(10), toks]).max() < 1e-4


def test_param_count_and_bytes_match_the_weights():
    cfg = A.afmoe_tiny(dtype=jnp.bfloat16)
    params = A.init_stacked_params(cfg, seed=0)
    assert A.param_count(cfg) == sum(v.size for v in params.values())
    assert A.param_nbytes(cfg) == sum(v.nbytes for v in params.values())
    assert params["e_router"].dtype == jnp.float32      # whatever is served
    assert set(A.serving_param_specs(cfg)) == set(params)
    # Trinity-Mini as published: 26B parameters
    full = A.AfmoeConfig()
    assert full.layer_types[:4] == (A.SLIDING,) * 3 + (A.FULL,)
    assert 26.0e9 < A.param_count(full) < 26.3e9
    assert A.kv_geometry(full, 16)["num_kv_heads"] == 4


def _afmoe_over_a_mesh(monkeypatch):
    from paddle_tpu.parallel.mesh import serving_mesh
    return A.afmoe_tiny(), dict(mesh=serving_mesh(2, jax.devices()[:2]))


def _module_without_its_step(monkeypatch):
    """A model module that has every name of the engine's protocol but
    ``ragged_step`` and ``shard_params_tp``, named by its config's class
    as ``models.afmoe`` is by ``AfmoeConfig``."""
    import sys
    import types
    half = types.ModuleType("half_a_model")
    half.init_stacked_params = A.init_stacked_params
    half.serving_param_specs = A.serving_param_specs
    monkeypatch.setitem(sys.modules, half.__name__, half)
    cfg = A.afmoe_tiny()
    cfg.serving_module = half.__name__
    return cfg, {}


@pytest.mark.parametrize("build,message", [
    (_afmoe_over_a_mesh, "replicates every weight"),
    (_module_without_its_step,
     "half_a_model cannot be served.*requires ragged_step, shard_params_tp"),
], ids=["afmoe_over_a_mesh", "module_lacks_protocol_names"])
def test_engine_refuses_at_construction(build, message, monkeypatch):
    """What a model cannot do is refused when the engine is built, by name:
    afmoe over a mesh (it replicates every weight), and a model module that
    lacks a REQUIRED name of the protocol ``_serving_module`` states."""
    cfg, kw = build(monkeypatch)
    with pytest.raises(ValueError, match=message):
        ContinuousBatchingEngine(cfg, num_slots=2, page_size=4,
                                 max_seq_len=32, **kw)


def test_llama_is_served_as_it_was_before_the_model_lookup():
    """The lookup hands a LlamaConfig ``models.llama``, the step returns
    Llama's three values, and the tiny engine's greedy output is the greedy
    full re-forward's, token for token."""
    cfg = L.llama_tiny()
    params = L.init_stacked_params(cfg, seed=4)
    eng = ContinuousBatchingEngine(cfg, GenerationConfig(seed=0),
                                   num_slots=3, page_size=4, max_seq_len=48,
                                   chunk=3, prefix_cache=True)
    assert eng._L is L and eng._layer_windows == (None,) * 4
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (4, 11, 7, 19)]
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    while eng.num_queued or eng._live:
        eng.step(params)
    done = eng.collect()
    assert_greedy(params, cfg, prompts, [done[r] for r in rids], n_new=6)
