"""A.X-K1 (``models.axk1``: latent attention, group-limited routing, YaRN) on
the serving path, against the plain float32 reference
(``perfbench/reference/axk1.py``, the EXPANDED form, no cache): logits
through the latent page cache, the engine with its prefix cache (the same
prompt again as a hit, a second request sharing its first pages), the router,
YaRN's tables and scale, and a chip's share of the experts. Small sizes,
seeded, float32, on the CPU."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.models import axk1 as X
from paddle_tpu.ops import rope as rope_ops
from paddle_tpu.parallel.mesh import serving_mesh
from paddle_tpu.serving import ServingScheduler
from perfbench import harness

from test_engine_phase_spans import RECORD_KEYS, _host_events

adapter = harness.load_module("perfbench/adapters/serve_axk1.py")
reference = harness.load_module("perfbench/reference/axk1.py")

_YARN = dict(type="yarn", factor=32, beta_fast=32, beta_slow=1, mscale=1,
             mscale_all_dim=1, original_max_position_embeddings=4096)


def _model_dict(cfg):
    keys = ("hidden_size", "num_attention_heads", "rms_norm_eps",
            "rope_theta", "rope_scaling", "num_hidden_layers",
            "first_k_dense_replace", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_group",
            "topk_group", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "first_expert")
    return {k: getattr(cfg, k) for k in keys}


def _weights(cfg, seed, boost=6.0):
    """Seeded weights with every matrix ``boost`` times the program's std
    of 0.02: at a hidden size of 64 that makes each branch as large as the
    stream it joins, as it is at the published widths, so that a fault in a
    branch moves the logits (left out of the softmax scale, YaRN's mscale
    moves them by 2.0 here and by 8e-4 at std 0.02)."""
    return {k: v if k == "ln_f" or k[2:] in X._NORM_KEYS
            or k.endswith("expert_bias") else v * boost
            for k, v in X.init_stacked_params(cfg, seed=seed).items()}


def _reference_logits(cfg, params, rows, spans):
    saved = reference.QUERY_BLOCK, reference.HEAD_BLOCK
    reference.QUERY_BLOCK, reference.HEAD_BLOCK = 16, 2
    try:
        return [np.asarray(x) for x in reference.logits_at(
            adapter.ReferenceWeights(params, cfg.first_k_dense_replace),
            rows, spans, _model_dict(cfg))]
    finally:
        reference.QUERY_BLOCK, reference.HEAD_BLOCK = saved


# ---------------------------------------------------------------------------
# the model against the plain reference, through the latent page cache
# ---------------------------------------------------------------------------
def _logits_through_the_cache(cfg, params, prompt, n_decode, chunk, page=4):
    """Prefill ``prompt`` in chunks of ``chunk`` tokens, then decode
    ``n_decode`` greedy tokens one at a time, all through ``ragged_step``
    and ONE row's pages of ONE latent array; returns (tokens fed, the logits
    after each call's last token). A second, idle row sits beside it."""
    width = -(-(len(prompt) + n_decode) // page)
    pool = 1 + 2 * width
    lat = jnp.zeros((cfg.num_hidden_layers, pool, page, cfg.entry_dim),
                    jnp.float32)
    bt = np.zeros((2, width), np.int32)
    bt[1] = 1 + np.arange(width)                   # the live row is row 1
    step = jax.jit(lambda *a: X.ragged_step(*a, cfg))
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
        lg, lat, aux = step(
            params, jnp.asarray(ids), jnp.asarray(token_row),
            jnp.asarray(positions), jnp.asarray([0, at], jnp.int32),
            jnp.asarray([0, n - 1], jnp.int32), lat, jnp.asarray(bt))
        assert aux.shape == (cfg.num_expert_layers, 3)
        # assignments among the experts HELD: all of them where all are
        made = n * cfg.num_experts_per_tok
        assert int(aux[0, 2]) == made \
            if cfg.experts_held == cfg.n_routed_experts \
            else 0 <= int(aux[0, 2]) < made
        if at >= len(prompt):
            logits.append(np.asarray(lg[1]))
            fed.append(int(np.argmax(logits[-1])))
    return np.asarray(fed[:-1], np.int32), np.stack(logits)


@pytest.mark.parametrize("chunk", [5, 16])
def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(
        chunk):
    """LOGITS of chunked prefill then decode through the latent pages (the
    absorbed form) against the reference's full forward pass (the expanded
    form, no cache): contexts of up to 37 positions past a YaRN original
    context of 32, a dense and two expert layers. Float32 on both sides;
    what is left is the order of float32 sums: logits of magnitude ~4
    agree to 2e-4."""
    cfg = X.axk1_tiny()
    params = _weights(cfg, 7)
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, cfg.vocab_size, (29,)).astype(np.int32)
    fed, got = _logits_through_the_cache(cfg, params, prompt, 8, chunk)
    want, = _reference_logits(cfg, params, [fed],
                              [(len(prompt) - 1, len(fed))])
    assert got.shape == want.shape == (9, cfg.vocab_size)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    assert (got.argmax(-1) == want.argmax(-1)).all()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One engine with the prefix cache on, served in two waves under the
    profiler: four cold prompts; then the longest of them again (a
    full-prompt hit) and a prompt that shares its first 24 tokens."""
    from paddle_tpu.observability.runtime import recompiles
    cfg = X.axk1_tiny()
    params = _weights(cfg, 2)
    before = recompiles.count("cbe.unified_step")
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(seed=0), num_slots=4, page_size=4,
        max_seq_len=96, chunk=4, prefix_cache=True)
    sched = ServingScheduler(eng)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 20, 33, 41)]
    trace_dir = str(tmp_path_factory.mktemp("axk1_trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        handles = [sched.submit(p, max_new_tokens=10) for p in prompts]
        while sched.pending:
            sched.step(params)
        cold = dict(eng.cache.snapshot())
        again = [prompts[3], np.concatenate(
            [prompts[3][:24],
             rng.randint(1, cfg.vocab_size, (9,)).astype(np.int32)])]
        handles += [sched.submit(p, max_new_tokens=10) for p in again]
        while sched.pending:
            sched.step(params)
    finally:
        jax.profiler.stop_trace()
    return dict(cfg=cfg, params=params, eng=eng, prompts=prompts + again,
                handles=handles, cold=cold, events=_host_events(trace_dir),
                compiles=recompiles.count("cbe.unified_step") - before)


def test_engine_serves_axk1_and_a_prefix_hit_gives_the_cold_runs_tokens(
        served):
    """submit -> ServingScheduler.step -> _step_unified: same entry points,
    planner, prefix cache and page accounting as Llama, ONE compiled
    program. The same prompt served again is a prefix-cache hit on latent
    pages and gives the cold run's tokens; every served token of every
    request (cold, hit, shared first pages) is the reference's argmax for
    its context (teacher-forced; within 1e-4 of the reference maximum)."""
    eng, handles, prompts = served["eng"], served["handles"], \
        served["prompts"]
    assert eng._L is X and served["compiles"] == 1
    assert served["cold"]["hits"] == 0 and served["cold"]["misses"] == 4
    snap = eng.cache.snapshot()
    assert snap["hits"] == 2
    # the repeated prompt: all but its last token; the sharer: six pages
    assert snap["cached_tokens"] == 40 + 24
    eng.mgr.check_conservation()
    assert handles[4].stream.tokens == handles[3].stream.tokens
    rows = [np.concatenate([p, np.asarray(h.stream.tokens, np.int32)])
            for p, h in zip(prompts, handles)]
    spans = [(len(p) - 1, len(p) - 1 + 10) for p in prompts]
    for h, logits in zip(handles, _reference_logits(
            served["cfg"], served["params"], rows, spans)):
        gen = np.asarray(h.stream.tokens)
        assert len(gen) == 10
        deficit = logits.max(-1) - logits[np.arange(10), gen]
        assert deficit.max() <= 1e-4


def _serve_side_by_side(prefix_cache):
    """One 140-token document (a whole block of 128 tokens = 32 pages of 4,
    and three more pages), written by one cold request, then asked four
    questions at once: four rows that decode SIDE BY SIDE on its pages, the
    lowest row (the group's leader) retiring first. Returns (the four
    requests' tokens, the work records of the dispatches that wrote the
    document, those of the four requests' dispatches, the engine)."""
    cfg = X.axk1_tiny()
    params = _weights(cfg, 2)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(seed=0), num_slots=4, page_size=4,
        max_seq_len=288, chunk=4, prefix_cache=prefix_cache)
    records, record = [], eng._dispatch_record
    eng._dispatch_record = lambda *a: records.append(record(*a)) or records[-1]
    sched = ServingScheduler(eng)
    rng = np.random.RandomState(4)
    draw = lambda n: rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
    document = draw(140)
    sched.submit(np.concatenate([document, draw(5)]), max_new_tokens=4)
    while sched.pending:
        sched.step(params)
    written = len(records)
    handles = [sched.submit(np.concatenate([document, draw(n)]),
                            max_new_tokens=new)
               for n, new in ((3, 6), (9, 14), (6, 14), (4, 10))]
    while sched.pending:
        sched.step(params)
    eng.mgr.check_conservation()
    return ([h.stream.tokens for h in handles], records[:written],
            records[written:], eng)


def test_rows_side_by_side_on_one_document_give_the_cold_runs_tokens(
        monkeypatch):
    """Four prefix hits whose block tables name the SAME pages decode side
    by side: the latent kernel (interpret mode, inside the engine's one
    step program) folds the document's whole block once, under the lowest
    row's item, for all of them; when that row retires the next one leads.
    Every request's tokens are those of an engine without a prefix cache
    (private pages, nothing shared, the XLA twin), and the work record
    counts the pages folded under another row's item."""
    from paddle_tpu.ops import paged_attention as pa
    cold, _, cold_records, cold_eng = _serve_side_by_side(False)
    assert cold_eng.cache is None
    assert all(r["shared_pages"] == 0 for r in cold_records)
    traced = []

    def interpreted(*args, **kw):
        traced.append(1)
        return pa.mla_paged_attention_pallas(*args, **kw, interpret=True)

    monkeypatch.setattr(pa, "mla_paged_attention", interpreted)
    warm, writing, records, eng = _serve_side_by_side(True)
    assert traced                     # the kernel is in the step program
    assert [len(t) for t in warm] == [6, 14, 14, 10] and warm == cold
    snap = eng.cache.snapshot()
    assert snap["hits"] == 4 and snap["cached_tokens"] == 4 * 140
    # one row alone shares nothing; four rows on one document: each round,
    # every row with a token but the lowest folds the block's 32 pages
    # under the leader's item
    assert all(r["shared_pages"] == 0 for r in writing)
    assert all(r["shared_pages"] % 32 == 0
               and r["shared_pages"] <= r["attended_pages"] for r in records)
    assert records[0]["live_rows"] == 4 and records[0]["shared_pages"] > 0
    # where every live row decodes in each of the 4 micro-rounds, all but
    # the lowest are followers; the leader has retired by then (three rows
    # live: the next lowest leads), and one row alone shares nothing
    steady = [r for r in records if r["decode_tokens"] == 4 * r["live_rows"]]
    assert {r["live_rows"] for r in steady} == {3, 1}
    assert all(r["shared_pages"] == 4 * (r["live_rows"] - 1) * 32
               for r in steady)


def test_the_pool_is_one_latent_array_and_no_v(served):
    eng, cfg = served["eng"], served["cfg"]
    assert cfg.latent_dim == 40 and cfg.entry_dim == 128
    assert eng.mgr.layout == X.cache_layout(cfg)
    assert [p.shape for p in eng.mgr.pools] == [
        (cfg.num_hidden_layers, eng.mgr.num_pages, 4, 128)]
    assert not hasattr(eng.mgr, "v_pages")
    # at the published widths: 576 numbers in 640 lanes
    full = X.Axk1Config()
    assert (full.latent_dim, full.entry_dim) == (576, 640)
    assert X.cache_layout(full).entries == ((640,),)


def test_the_latent_paths_spans_carry_the_work_record_and_routing_stats(
        served):
    """The tracing the repo has, written for the latent path too: the
    ten keys on ``cbe.dispatch`` and ``shared_pages`` (a pool without a head
    axis; 0 here: no prompt shares a whole block of 96 tokens), the four
    routing stats on ``cbe.unpack`` (for the experts held), and
    ``cached_tokens`` on ``cbe.upload``."""
    events = served["events"]
    records = [e[3] for e in events if e[0] == "cbe.dispatch"]
    assert records and all(set(r) == RECORD_KEYS | {"shared_pages"}
                           and r["shared_pages"] == 0 for r in records)
    assert all(r["page_size"] == 4 and r["attended_pages"] > 0
               and r["causal_pairs"] > 0 for r in records)
    unpacks = [e[3] for e in events if e[0] == "cbe.unpack"]
    assert len(unpacks) == len(records)
    assert all(set(u) == {"expert_calls", "experts_hit",
                          "expert_assignments", "max_expert_load",
                          "dropped_tokens"}
               for u in unpacks)
    layers = served["cfg"].num_expert_layers
    assert all(u["expert_calls"] == 4 * layers for u in unpacks)
    uploads = [e[3] for e in events if e[0] == "cbe.upload"]
    assert sum(u["admitted"] for u in uploads) == 6
    assert sum(u["cached_tokens"] for u in uploads) == 40 + 24
    assert all(u["cached_tokens"] == 0 for u in uploads
               if not u["admitted"])


def test_the_shared_page_share_reader(monkeypatch):
    """``kernel.mla_paged_attention.shared_page_share``: 100 x shared /
    attended pages over the complete dispatches' records; silent (None, no
    raise) on a program whose records lack the counter, as the parent's
    do, and without a trace."""
    from perfbench import program_trace
    reader = harness.load_module(
        "perfbench/layer_metrics/"
        "kernel.mla_paged_attention.shared_page_share.py")
    trace = lambda records: {"dispatches": [{"record": r} for r in records]}
    seen = [None]
    monkeypatch.setattr(program_trace, "for_obs", lambda obs: seen[0])
    assert reader.read(object()) is None
    seen[0] = trace([{"attended_pages": 300_000, "shared_pages": 180_000},
                     {"attended_pages": 100_000, "shared_pages": 60_000}])
    assert reader.read(object()) == pytest.approx(60.0)
    seen[0] = trace([{"attended_pages": 300_000}])
    assert reader.read(object()) is None
    seen[0] = trace([{"attended_pages": 0, "shared_pages": 0}])
    assert reader.read(object()) is None
    seen[0] = trace([])
    assert reader.read(object()) is None


def test_engine_refuses_a_mesh_of_several_chips():
    cfg = X.axk1_tiny()
    with pytest.raises(ValueError, match="replicates every weight"):
        ContinuousBatchingEngine(cfg, num_slots=2, page_size=4,
                                 max_seq_len=32,
                                 mesh=serving_mesh(2, jax.devices()[:2]))
    eng = ContinuousBatchingEngine(cfg, num_slots=2, page_size=4,
                                   max_seq_len=32,
                                   mesh=serving_mesh(1, jax.devices()[:1]))
    assert eng.num_chips == 1 and len(eng.mgr.pools) == 1


def test_param_count_and_bytes_match_the_weights():
    cfg = X.axk1_tiny(experts_held=4, first_expert=8)
    params = X.init_stacked_params(cfg, seed=0)
    assert X.param_count(cfg) == sum(int(np.prod(v.shape))
                                     for v in params.values())
    assert X.param_nbytes(cfg) == sum(v.nbytes for v in params.values())
    assert params["e_we_gate"].shape[:2] == (2, 4)          # experts HELD
    assert params["e_router"].shape == (2, 64, 16)          # ALL outputs
    assert params["e_router"].dtype == jnp.float32
    assert set(X.serving_param_specs(cfg)) == set(params)
    # the cut of the benchmark's configuration, at two bytes a parameter
    cut = X.Axk1Config(vocab_size=20480, num_hidden_layers=7,
                       experts_held=12)
    assert round(X.param_count(cut) / 1e6) == 4841


# ---------------------------------------------------------------------------
# the group-limited router
# ---------------------------------------------------------------------------
def _router_case(seed=5, tokens=64):
    cfg = X.axk1_tiny()
    rng = np.random.RandomState(seed)
    m = rng.randn(tokens, cfg.hidden_size).astype(np.float32)
    router = (rng.randn(cfg.hidden_size, cfg.n_routed_experts) * 0.3
              ).astype(np.float32)
    return cfg, m, router


def _reference_route(cfg, m, router, bias):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.router_weights(
            jnp.asarray(m), jnp.asarray(router), jnp.asarray(bias),
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            top_k=cfg.num_experts_per_tok, norm_topk_prob=True,
            scaling_factor=cfg.routed_scaling_factor))


def _dense_weights(cfg, sel, w):
    out = np.zeros((sel.shape[0], cfg.n_routed_experts), np.float32)
    np.put_along_axis(out, np.asarray(sel), np.asarray(w), axis=1)
    return out


def test_group_limited_router_matches_the_reference():
    """Experts and weights against the reference's (S, E) weight matrix;
    the chosen experts lie in ``topk_group`` groups; weights sum to
    ``routed_scaling_factor``."""
    cfg, m, router = _router_case()
    bias = np.random.RandomState(6).randn(cfg.n_routed_experts).astype(
        np.float32) * 0.05
    sel, w = X.route(jnp.asarray(m), jnp.asarray(router), jnp.asarray(bias),
                     cfg)
    np.testing.assert_allclose(_dense_weights(cfg, sel, w),
                               _reference_route(cfg, m, router, bias),
                               rtol=1e-5, atol=1e-6)
    groups = np.asarray(sel) // (cfg.n_routed_experts // cfg.n_group)
    assert all(len(set(g)) <= cfg.topk_group for g in groups)
    np.testing.assert_allclose(np.asarray(w).sum(-1),
                               cfg.routed_scaling_factor, rtol=1e-5)
    # group-limited is not plain top-k: somewhere a larger score lost to its
    # group's rank
    s = jax.nn.sigmoid(m @ router) + bias
    plain = np.argsort(-s, axis=-1)[:, :cfg.num_experts_per_tok]
    assert any(set(a) != set(b) for a, b in zip(plain, np.asarray(sel)))


def test_a_bias_changes_the_selection_but_not_the_weights():
    """``expert_bias`` enters the choice alone: a bias that moves the
    choice leaves the weight of every expert chosen both ways a ratio of
    unbiased scores; the program and the reference agree under it."""
    cfg, m, router = _router_case(seed=8)
    zero = np.zeros((cfg.n_routed_experts,), np.float32)
    bias = zero.copy()
    bias[[1, 6, 11]] = 0.6                      # lifts three experts
    args = jnp.asarray(m), jnp.asarray(router)
    sel0, w0 = X.route(*args, jnp.asarray(zero), cfg)
    sel1, w1 = X.route(*args, jnp.asarray(bias), cfg)
    assert any(set(a) != set(b) for a, b in zip(np.asarray(sel0),
                                                np.asarray(sel1)))
    s = np.asarray(jax.nn.sigmoid(m @ router))
    picked = np.take_along_axis(s, np.asarray(sel1), axis=1)
    np.testing.assert_allclose(
        np.asarray(w1), cfg.routed_scaling_factor * picked
        / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(_dense_weights(cfg, sel1, w1),
                               _reference_route(cfg, m, router, bias),
                               rtol=1e-5, atol=1e-6)


def test_expert_shares_add_up_to_the_whole_layer():
    """Four shares of four experts (an EP4 deployment of the tiny model's
    16): each chip's layer output = shared expert + ITS experts' terms; the
    routed parts of all shares, with the shared expert counted once, add up
    to what the uncut reference gives for the whole layer."""
    whole = X.axk1_tiny(num_hidden_layers=1, first_k_dense_replace=0)
    params = X.init_stacked_params(whole, seed=4)
    rng = np.random.RandomState(9)
    x = rng.randn(24, whole.hidden_size).astype(np.float32)
    lw = adapter.ReferenceWeights(params, 0).layer(0)
    kw = dict(eps=whole.rms_norm_eps, n_group=whole.n_group,
              topk_group=whole.topk_group, top_k=whole.num_experts_per_tok,
              norm_topk_prob=True,
              scaling_factor=whole.routed_scaling_factor)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(reference._expert_mlp(
            jnp.asarray(x), lw, first_expert=0, **kw)) - x
        m = reference._rms_norm(jnp.asarray(x),
                                lw["post_attention_layernorm"],
                                whole.rms_norm_eps)
        shared = np.asarray(reference._swiglu(m, lw["mlp"]["shared"]))
        total = np.zeros_like(uncut)
        for first in range(0, 16, 4):
            mlp = dict(lw["mlp"], experts={
                k: v[first:first + 4]
                for k, v in lw["mlp"]["experts"].items()})
            share = np.asarray(reference._expert_mlp(
                jnp.asarray(x), dict(lw, mlp=mlp), first_expert=first,
                **kw)) - x
            total += share - shared
    assert np.abs(uncut).max() > 1e-3
    np.testing.assert_allclose(total + shared, uncut, rtol=1e-4, atol=1e-6)


def test_a_chips_share_through_the_program_matches_the_reference_share():
    """The program holding experts 8-11 of 16 (router 16 wide) against the
    reference given the same share: logits of a short forward."""
    cfg = X.axk1_tiny(experts_held=4, first_expert=8)
    params = _weights(cfg, 11)
    rng = np.random.RandomState(2)
    prompt = rng.randint(1, cfg.vocab_size, (21,)).astype(np.int32)
    fed, got = _logits_through_the_cache(cfg, params, prompt, 3, 8)
    want, = _reference_logits(cfg, params, [fed],
                              [(len(prompt) - 1, len(fed))])
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    whole = X.axk1_tiny()
    other, = _reference_logits(
        whole, _weights(whole, 11), [fed],
        [(len(prompt) - 1, len(fed))])
    assert np.abs(other - want).max() > 1e-3    # the share is not the whole


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------
def test_yarn_tables_and_scale_match_the_closed_formulas():
    """A.X-K1's ``rope_scaling``: the ramp runs over pairs 10..23 of 32;
    below it the published frequencies, above it those over 32; the scale
    is 192^-0.5 x (0.1 ln 32 + 1)^2."""
    assert rope_ops.yarn_correction_range(32, 1, 64, 10000, 4096) == (10, 23)
    assert rope_ops.yarn_mscale(32, 1) == pytest.approx(
        0.1 * math.log(32) + 1)
    assert rope_ops.yarn_mscale(1, 1) == 1.0
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = plain / 32 * ramp + plain * (1 - ramp)
    got = np.asarray(rope_ops.rope_inv_freq(64, 10000.0, _YARN))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_array_equal(got[:11], np.asarray(
        rope_ops.rope_inv_freq(64, 10000.0))[:11])
    np.testing.assert_allclose(got[23:], plain[23:] / 32, rtol=1e-6)
    full = X.Axk1Config(rope_scaling=_YARN)
    assert X.softmax_scale(full) == pytest.approx(0.07217 * 1.8133, rel=1e-4)
    assert X.softmax_scale(X.Axk1Config()) == pytest.approx(192 ** -0.5)
    assert reference.attention_scale(
        dict(qk_nope_head_dim=128, qk_rope_head_dim=64,
             rope_scaling=_YARN)) == pytest.approx(X.softmax_scale(full))
    # the tables: the program's against the reference's closed form
    pos = jnp.asarray([0, 1, 4095, 4096, 17000], jnp.int32)
    cos, sin = rope_ops.rope_tables(pos, rope_ops.rope_inv_freq(
        64, 10000.0, _YARN))
    rcos, rsin = reference.yarn_tables(17001, 64, 10000.0,
                                       tuple(sorted(_YARN.items())))
    np.testing.assert_allclose(cos, np.asarray(rcos)[np.asarray(pos)],
                               atol=2e-3)
    np.testing.assert_allclose(sin, np.asarray(rsin)[np.asarray(pos)],
                               atol=2e-3)
    # and the plain table is what it was before YaRN came
    c0, s0 = rope_ops.build_rope_cache(8, 16, 10000.0)
    freqs = np.outer(np.arange(8), 10000.0 ** (-np.arange(0, 16, 2) / 16))
    np.testing.assert_allclose(c0, np.cos(np.concatenate([freqs, freqs], -1)),
                               atol=1e-6)
    np.testing.assert_allclose(s0, np.sin(np.concatenate([freqs, freqs], -1)),
                               atol=1e-6)
