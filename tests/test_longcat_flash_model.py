"""LongCat-Flash (``models.longcat_flash``: two latent-attention sub-layers
and two dense feed-forwards a layer, the expert branch taken from the
middle and added at the end, a softmax router whose last outputs are
zero-compute experts) on the serving path, against the plain float32
reference (``perfbench/reference/longcat_flash.py``, the EXPANDED form, no
cache): logits through TWO cache layers a layer, the engine with its prefix
cache, the router, the zero-compute experts and a chip's share of the routed
ones, and the latent kernel at the cell's 128 rows x 256 pages. Small sizes,
seeded, float32, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.models import longcat_flash as F
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.moe_ops import grouped_expert_ffn
from paddle_tpu.parallel.mesh import serving_mesh
from paddle_tpu.serving import ServingScheduler
from perfbench import harness

from test_mla_paged_attention import _case

adapter = harness.load_module("perfbench/adapters/serve_longcat.py")
reference = harness.load_module("perfbench/reference/longcat_flash.py")

_MODEL_KEYS = ("hidden_size", "num_attention_heads", "rms_norm_eps",
               "rope_theta", "num_layers", "q_lora_rank", "kv_lora_rank",
               "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
               "mla_scale_q_lora", "mla_scale_kv_lora", "moe_topk",
               "routed_scaling_factor", "zero_expert_num", "first_expert")


def _model_dict(cfg):
    return {k: getattr(cfg, k) for k in _MODEL_KEYS}


def _weights(cfg, seed, boost=6.0):
    """Seeded weights with every matrix ``boost`` times the program's std of
    0.02 (``tests/test_axk1_model.py``: at a hidden size of 64 a branch is
    otherwise ~1% of the stream it joins and a planted fault moves no
    logit); the selection bias at the scores' size, as the program draws
    it."""
    return {k: v if k in ("ln_f", "expert_bias") or k in F._NORM_KEYS
            else v * boost
            for k, v in F.init_stacked_params(cfg, seed=seed).items()}


def _reference_logits(cfg, params, rows, spans):
    saved = reference.QUERY_BLOCK, reference.HEAD_BLOCK
    reference.QUERY_BLOCK, reference.HEAD_BLOCK = 16, 2
    try:
        return [np.asarray(x) for x in reference.logits_at(
            adapter.ReferenceWeights(params), rows, spans, _model_dict(cfg))]
    finally:
        reference.QUERY_BLOCK, reference.HEAD_BLOCK = saved


# ---------------------------------------------------------------------------
# the model against the plain reference, through the latent page cache
# ---------------------------------------------------------------------------
def _logits_through_the_cache(cfg, params, prompt, n_decode, chunk, page=4,
                              spoil=None):
    """Prefill ``prompt`` in chunks of ``chunk`` tokens, then decode
    ``n_decode`` greedy tokens one at a time, all through ``ragged_step``
    and ONE row's pages of ONE latent array of 2 x layers cache layers;
    returns (tokens fed, the logits after each call's last token, the
    array). A second, idle row sits beside it. ``spoil``: a cache layer
    whose entries are zeroed after the prefill."""
    width = -(-(len(prompt) + n_decode) // page)
    pool = 1 + 2 * width
    lat = jnp.zeros((2 * cfg.num_layers, pool, page, cfg.entry_dim),
                    jnp.float32)
    bt = np.zeros((2, width), np.int32)
    bt[1] = 1 + np.arange(width)                   # the live row is row 1
    step = jax.jit(lambda *a: F.ragged_step(*a, cfg))
    fed, logits, at = list(prompt), [], 0
    while len(logits) < n_decode + 1:
        n = min(chunk, len(prompt) - at) if at < len(prompt) else 1
        ids = np.zeros((chunk,), np.int32)
        token_row = np.full((chunk,), -1, np.int32)
        positions = np.zeros((chunk,), np.int32)
        ids[:n] = fed[at:at + n]
        token_row[:n] = 1
        positions[:n] = at + np.arange(n)
        at += n
        lg, lat, aux = step(
            params, jnp.asarray(ids), jnp.asarray(token_row),
            jnp.asarray(positions), jnp.asarray([0, at], jnp.int32),
            jnp.asarray([0, n - 1], jnp.int32), lat, jnp.asarray(bt))
        aux = np.asarray(aux)
        assert aux.shape == (cfg.num_layers, 5)
        # the router's assignments; held + identities never more than them
        assert (aux[:, 4] == n * cfg.moe_topk).all()
        assert (aux[:, 2] + aux[:, 3] <= aux[:, 4]).all()
        if cfg.experts_held == cfg.n_routed_experts:
            assert (aux[:, 2] + aux[:, 3] == aux[:, 4]).all()
        if at >= len(prompt):
            if not logits and spoil is not None:
                lat = lat.at[spoil].set(0.0)
            logits.append(np.asarray(lg[1]))
            fed.append(int(np.argmax(logits[-1])))
    return np.asarray(fed[:-1], np.int32), np.stack(logits), np.asarray(lat)


@pytest.mark.parametrize("n_prompt", [1, 15, 16, 17, 40])
def test_prefill_then_decode_through_both_cache_layers_matches_the_reference(
        n_prompt):
    """LOGITS of chunked prefill (16 tokens a call: the boundary falls
    before, on and after the last prompt token) then decode through the
    latent pages (the absorbed form) against the reference's full forward
    pass (the expanded form, no cache), two layers = four cache layers.
    Float32 on both sides; what is left is the order of float32 sums."""
    cfg = F.longcat_flash_tiny()
    params = _weights(cfg, 7)
    rng = np.random.RandomState(n_prompt)
    prompt = rng.randint(1, cfg.vocab_size, (n_prompt,)).astype(np.int32)
    fed, got, lat = _logits_through_the_cache(cfg, params, prompt, 6, 16)
    want, = _reference_logits(cfg, params, [fed],
                              [(len(prompt) - 1, len(fed))])
    assert got.shape == want.shape == (7, cfg.vocab_size)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # every cache layer holds the row's entries, each its own
    written = lat[:, 1:1 + -(-len(fed) // 4)].reshape(4, -1, cfg.entry_dim)
    written = written[:, :len(fed)]
    assert (np.abs(written[..., :cfg.latent_dim]).max(-1) > 0).all()
    assert (written[..., cfg.latent_dim:] == 0).all()
    assert all(np.abs(written[a] - written[b]).max() > 1e-3
               for a in range(4) for b in range(a))


@pytest.mark.parametrize("cache_layer", [0, 1, 2, 3])
def test_each_cache_layer_is_read_back(cache_layer):
    """Decoding reads what the prefill wrote on BOTH cache layers of each
    layer: with one layer's entries zeroed after the prefill the decode
    steps' logits move."""
    cfg = F.longcat_flash_tiny()
    params = _weights(cfg, 7)
    prompt = np.random.RandomState(3).randint(
        1, cfg.vocab_size, (17,)).astype(np.int32)
    _, clean, _ = _logits_through_the_cache(cfg, params, prompt, 2, 16)
    _, spoiled, _ = _logits_through_the_cache(cfg, params, prompt, 2, 16,
                                              spoil=cache_layer)
    np.testing.assert_array_equal(spoiled[0], clean[0])   # before the fault
    assert np.abs(spoiled[1] - clean[1]).max() > 1e-2


@pytest.fixture(scope="module")
def served():
    """One engine with the prefix cache on, served in two waves: five cold
    prompts whose last token lies before, on and after a boundary of the
    packed axis; then the longest again (a full-prompt hit) and a prompt
    that shares its first 24 tokens."""
    from paddle_tpu.observability.runtime import recompiles
    cfg = F.longcat_flash_tiny()
    params = _weights(cfg, 2)
    before = recompiles.count("cbe.unified_step")
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(seed=0), num_slots=4, page_size=4,
        max_seq_len=96, chunk=4, prefix_cache=True)
    sched = ServingScheduler(eng)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (1, 15, 16, 17, 40)]
    handles = [sched.submit(p, max_new_tokens=8) for p in prompts]
    while sched.pending:
        sched.step(params)
    cold = dict(eng.cache.snapshot())
    again = [prompts[4], np.concatenate(
        [prompts[4][:24],
         rng.randint(1, cfg.vocab_size, (9,)).astype(np.int32)])]
    handles += [sched.submit(p, max_new_tokens=8) for p in again]
    while sched.pending:
        sched.step(params)
    return dict(cfg=cfg, params=params, eng=eng, prompts=prompts + again,
                handles=handles, cold=cold,
                compiles=recompiles.count("cbe.unified_step") - before)


def test_engine_serves_longcat_and_a_prefix_hit_gives_the_cold_runs_logits(
        served):
    """submit -> ServingScheduler.step -> _step_unified: same entry points,
    planner, prefix cache and page accounting as Llama, ONE compiled
    program. The same prompt served again is a prefix-cache hit on latent
    pages of 2 x layers cache layers and gives the cold run's tokens; every
    served token of every request (cold, hit, shared first pages) is the
    reference's argmax for its context (teacher-forced; within 1e-4 of the
    reference maximum)."""
    eng, handles, prompts = served["eng"], served["handles"], \
        served["prompts"]
    assert eng._L is F and served["compiles"] == 1
    assert served["cold"]["hits"] == 0 and served["cold"]["misses"] == 5
    snap = eng.cache.snapshot()
    assert snap["hits"] == 2
    # the repeated prompt: all but its last token; the sharer: six pages
    assert snap["cached_tokens"] == 39 + 24
    eng.mgr.check_conservation()
    assert handles[5].stream.tokens == handles[4].stream.tokens
    rows = [np.concatenate([p, np.asarray(h.stream.tokens, np.int32)])
            for p, h in zip(prompts, handles)]
    spans = [(len(p) - 1, len(p) - 1 + 8) for p in prompts]
    for h, logits in zip(handles, _reference_logits(
            served["cfg"], served["params"], rows, spans)):
        gen = np.asarray(h.stream.tokens)
        assert len(gen) == 8
        deficit = logits.max(-1) - logits[np.arange(8), gen]
        assert deficit.max() <= 1e-4


def test_the_pool_is_one_latent_array_on_two_cache_layers_a_layer(served):
    eng, cfg = served["eng"], served["cfg"]
    assert cfg.latent_dim == 40 and cfg.entry_dim == 128
    assert eng.mgr.layout == F.cache_layout(cfg)
    assert [p.shape for p in eng.mgr.pools] == [
        (2 * cfg.num_layers, eng.mgr.num_pages, 4, 128)]
    assert not hasattr(eng.mgr, "v_pages")
    # the work record's means are over the CACHE layers
    assert len(eng._layer_windows) == 2 * cfg.num_layers
    # at the published widths: 576 numbers in 640 lanes, 56 cache layers
    full = F.LongcatFlashConfig()
    assert (full.latent_dim, full.entry_dim) == (576, 640)
    assert F.cache_layout(full).entries == ((640,),)
    assert F.cache_layout(full).layers == 56
    assert F.lora_scales(full) == (2.0, pytest.approx(12 ** 0.5))


def test_engine_refuses_a_mesh_of_several_chips_by_name():
    cfg = F.longcat_flash_tiny()
    with pytest.raises(ValueError, match="paddle_tpu.models.longcat_flash "
                                         "replicates every weight"):
        ContinuousBatchingEngine(cfg, num_slots=2, page_size=4,
                                 max_seq_len=32,
                                 mesh=serving_mesh(2, jax.devices()[:2]))
    eng = ContinuousBatchingEngine(cfg, num_slots=2, page_size=4,
                                   max_seq_len=32,
                                   mesh=serving_mesh(1, jax.devices()[:1]))
    assert eng.num_chips == 1 and len(eng.mgr.pools) == 1


def test_param_count_and_bytes_match_the_weights():
    cfg = F.longcat_flash_tiny(experts_held=2, first_expert=4)
    params = F.init_stacked_params(cfg, seed=0)
    assert F.param_count(cfg) == sum(int(np.prod(v.shape))
                                     for v in params.values())
    assert F.param_nbytes(cfg) == sum(v.nbytes for v in params.values())
    assert params["we_gate"].shape[:2] == (2, 2)            # experts HELD
    assert params["router"].shape == (2, 64, 12)            # ALL outputs
    assert params["router"].dtype == jnp.float32
    assert params["w_qa"].shape[:2] == (2, 2)               # two sub-layers
    assert set(F.serving_param_specs(cfg)) == set(params)
    # the selection bias is drawn at the scores' size
    assert 0 < float(jnp.abs(params["expert_bias"]).max()) < 4 / 12
    # the uncut model and the benchmark's cut, in parameters
    assert round(F.param_count(F.LongcatFlashConfig()) / 1e8) == 5607
    cut = F.LongcatFlashConfig(vocab_size=16384, num_layers=4,
                               experts_held=16)
    assert round(F.param_count(cut) / 1e5) == 51727


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------
def _router_case(seed=5, tokens=64):
    cfg = F.longcat_flash_tiny()
    rng = np.random.RandomState(seed)
    m = rng.randn(tokens, cfg.hidden_size).astype(np.float32)
    router = (rng.randn(cfg.hidden_size, cfg.router_width) * 0.3
              ).astype(np.float32)
    return cfg, m, router


def _dense_weights(cfg, sel, w):
    out = np.zeros((sel.shape[0], cfg.router_width), np.float32)
    np.put_along_axis(out, np.asarray(sel), np.asarray(w), axis=1)
    return out


def test_softmax_router_matches_the_reference_and_a_bias_only_selects():
    """Experts and weights against the reference's (S, E) weight matrix:
    softmax scores over routed AND zero-compute outputs, one flat top-k on
    ``s + b``, weights ``6 s`` unnormalised (they sum to less than 6). A
    bias at the scores' size changes the choice of some tokens and the
    weight of no expert chosen both ways."""
    cfg, m, router = _router_case()
    zero = np.zeros((cfg.router_width,), np.float32)
    bias = np.random.RandomState(6).randn(cfg.router_width).astype(
        np.float32) / cfg.router_width
    args = jnp.asarray(m), jnp.asarray(router)
    s = np.asarray(jax.nn.softmax(m @ router, axis=-1))
    picks = {}
    for name, b in (("zero", zero), ("bias", bias)):
        sel, w = F.route(*args, jnp.asarray(b), cfg)
        picks[name] = np.asarray(sel)
        assert sel.shape == (64, cfg.moe_topk) and sel.dtype == jnp.int32
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference.router_weights(
                *args, jnp.asarray(b), top_k=cfg.moe_topk,
                scaling_factor=cfg.routed_scaling_factor))
        np.testing.assert_allclose(_dense_weights(cfg, sel, w), want,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(w), cfg.routed_scaling_factor
            * np.take_along_axis(s, np.asarray(sel), axis=1), rtol=1e-5)
        assert (np.asarray(w).sum(-1) < cfg.routed_scaling_factor).all()
        # both kinds of output are chosen
        assert (np.asarray(sel) >= cfg.n_routed_experts).any()
        assert (np.asarray(sel) < cfg.n_routed_experts).any()
    plain = np.argsort(-s, axis=-1)[:, :cfg.moe_topk]
    assert all(set(a) == set(b) for a, b in zip(plain, picks["zero"]))
    moved = [set(a) != set(b) for a, b in zip(picks["zero"], picks["bias"])]
    assert 0 < sum(moved) < len(moved)


# ---------------------------------------------------------------------------
# the zero-compute experts and a chip's share
# ---------------------------------------------------------------------------
def test_identities_absent_experts_and_a_pad_slot_count_and_add_as_written():
    """One call of the expert layer at 8 routed + 4 zero-compute outputs,
    experts 2-3 held: a token whose choices are all identities gets ``sum w
    x``, one whose choices are all absent experts gets 0, a pad slot gets 0
    and counts nowhere, and a token with one of each gets its held expert's
    term + ``w x``. The stats: experts hit, largest load, assignments among
    the HELD; then the identities' and the router's."""
    rng = np.random.RandomState(1)
    h, mi = 16, 8
    x = rng.randn(4, h).astype(np.float32)
    gate, up = (rng.randn(2, h, mi).astype(np.float32) * 0.3
                for _ in range(2))
    down = rng.randn(2, mi, h).astype(np.float32) * 0.3
    idx = np.array([[8, 9, 11], [0, 5, 7], [9, 10, 3], [3, 10, 6]], np.int32)
    w = rng.rand(4, 3).astype(np.float32)
    valid = np.array([True, True, False, True])
    out, stats = grouped_expert_ffn(
        jnp.asarray(x), jnp.asarray(idx), jnp.asarray(w), jnp.asarray(valid),
        jnp.asarray(gate), jnp.asarray(up), jnp.asarray(down),
        first_expert=2, n_routed=8)
    out = np.asarray(out)
    np.testing.assert_allclose(out[0], w[0].sum() * x[0], rtol=1e-6)
    assert (out[1] == 0).all() and (out[2] == 0).all()
    silu = lambda z: z / (1 + np.exp(-z))
    expert3 = (silu(x[3] @ gate[1]) * (x[3] @ up[1])) @ down[1]
    np.testing.assert_allclose(out[3], w[3, 0] * expert3 + w[3, 1] * x[3],
                               rtol=1e-4, atol=1e-6)
    assert np.asarray(stats).tolist() == [1, 1, 1, 4, 9]
    # without a routed width the same ids name no identity: three stats,
    # and only the held expert's term is left
    out3, stats3 = grouped_expert_ffn(
        jnp.asarray(x), jnp.asarray(idx), jnp.asarray(w), jnp.asarray(valid),
        jnp.asarray(gate), jnp.asarray(up), jnp.asarray(down),
        first_expert=2)
    assert np.asarray(stats3).tolist() == [1, 1, 1]
    assert (np.asarray(out3)[:3] == 0).all()
    np.testing.assert_allclose(np.asarray(out3)[3], w[3, 0] * expert3,
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("sizes,want", [
    # a chip's share at k choices a token: a few held rows in tile 0, then
    # tiles of rows that belong to no held expert, which take no step
    ([3, 0, 2, 1], [(0, 0), (0, 2), (0, 3)]),
    # an expert whose rows straddle two tiles, then empty tiles
    ([20, 30, 0, 0], [(0, 0), (0, 1), (1, 1)]),
    # nothing held was chosen: one item, so that the grid is not empty
    ([0, 0, 0, 0], [(0, 0)]),
    # an empty tile between owned ones does not exist: groups are packed
    ([32, 32, 32, 32], [(0, 0), (1, 1), (2, 2), (3, 3)])], ids=str)
def test_tiles_that_no_held_expert_reaches_take_no_step(sizes, want):
    """The grouped product's work list names only the (tile, expert) pairs
    in which the expert owns a row: at ``T x k`` rows and a chip's share of
    the experts most tiles belong to nobody, and they cost no step; the
    output is the twin's all the same, zeros there."""
    from paddle_tpu.ops import moe_ops
    m, k, n, tm = 128, 16, 24, 32
    gs = jnp.asarray(sizes, jnp.int32)
    items, n_items, _, _ = moe_ops._gmm_work_list(gs, m, tm)
    items = np.asarray(items)[:int(n_items)]
    assert [(int(i >> 17), int((i >> 1) & 0xFFFF)) for i in items] == want
    rng = np.random.RandomState(0)
    lhs = jnp.asarray(rng.randn(m, k).astype(np.float32))
    rhs = jnp.asarray(rng.randn(len(sizes), k, n).astype(np.float32))
    got = np.asarray(moe_ops.moe_grouped_matmul_pallas(lhs, rhs, gs,
                                                       interpret=True))
    np.testing.assert_allclose(
        got, np.asarray(moe_ops.moe_grouped_matmul_array(lhs, rhs, gs)),
        rtol=1e-5, atol=1e-5)
    assert (got[sum(sizes):] == 0).all()


def test_expert_shares_add_up_to_the_whole_layer():
    """Four shares of two experts (an EP4 deployment of the tiny model's 8
    routed + 4 zero-compute): the routed parts of ``MoE(m)`` summed over the
    shares + the identity part counted ONCE equal the uncut reference's;
    every share computes the same identity part (it is computed where the
    token lives)."""
    whole = F.longcat_flash_tiny(num_layers=1)
    lw = adapter.ReferenceWeights(_weights(whole, 4)).layer(0)
    m = jnp.asarray(np.random.RandomState(9).randn(
        24, whole.hidden_size).astype(np.float32))
    kw = dict(top_k=whole.moe_topk,
              scaling_factor=whole.routed_scaling_factor,
              zero_experts=whole.zero_expert_num)
    with jax.default_matmul_precision("highest"):
        routed, identity = (np.asarray(a) for a in reference.expert_branch(
            m, lw["router"], lw["expert_bias"], lw["experts"],
            first_expert=0, **kw))
        total = np.zeros_like(routed)
        for first in range(0, 8, 2):
            experts = {k: v[first:first + 2]
                       for k, v in lw["experts"].items()}
            part, same = reference.expert_branch(
                m, lw["router"], lw["expert_bias"], experts,
                first_expert=first, **kw)
            total += np.asarray(part)
            np.testing.assert_array_equal(np.asarray(same), identity)
    assert np.abs(routed).max() > 1e-3 and np.abs(identity).max() > 1e-3
    np.testing.assert_allclose(total + identity, routed + identity,
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("share", [0, 1, 2, 3])
def test_a_chips_share_through_the_program_matches_the_reference_share(share):
    """The program holding experts ``2 share, 2 share + 1`` of 8 (router 12
    wide, every identity computed here) against the reference given the
    same share: logits of a short forward; the share is not the whole."""
    cfg = F.longcat_flash_tiny(experts_held=2, first_expert=2 * share)
    params = _weights(cfg, 11)
    rng = np.random.RandomState(2)
    prompt = rng.randint(1, cfg.vocab_size, (21,)).astype(np.int32)
    fed, got, _ = _logits_through_the_cache(cfg, params, prompt, 3, 8)
    want, = _reference_logits(cfg, params, [fed],
                              [(len(prompt) - 1, len(fed))])
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    whole = F.longcat_flash_tiny()
    other, = _reference_logits(whole, _weights(whole, 11), [fed],
                               [(len(prompt) - 1, len(fed))])
    assert np.abs(other - want).max() > 1e-3


# ---------------------------------------------------------------------------
# the latent kernel at the cell's rows and table
# ---------------------------------------------------------------------------
def _cell_sized_case():
    """128 rows with block tables of 256 pages of 16 (4,096 positions): 125
    rows decode at scattered contexts of up to 1,500 positions, one at the
    table's last position, one prefills 8 tokens across a block boundary,
    one is idle."""
    rows = [(r, (37 * r * r + 11) % 1500, 1) for r in range(128)
            if r not in (5, 64, 127)]
    rows += [(5, 120, 8), (127, 4095, 1)]
    return _case(rows=sorted(rows), t=136, n_rows=128, heads=2, d=128,
                 page=16, width=256, seed=3)


def test_the_latent_kernel_takes_128_block_tables_of_256_pages():
    """The kernel (interpret mode) against its XLA twin at the new cell's
    row count and table width, which no run had before it."""
    args = [jnp.asarray(a) for a in _cell_sized_case()]
    want = np.asarray(pa.mla_paged_attention_array(*args, value_dim=64))
    got = np.asarray(pa.mla_paged_attention_pallas(
        *args, value_dim=64, interpret=True))
    token_row = np.asarray(args[3])
    assert want.shape == got.shape == (136, 2, 64)
    assert np.isfinite(got).all() and (got[token_row < 0] == 0).all()
    np.testing.assert_allclose(got[token_row >= 0], want[token_row >= 0],
                               rtol=0, atol=2e-5)
