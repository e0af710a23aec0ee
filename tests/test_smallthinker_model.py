"""SmallThinker (``models.smallthinker``: a softmax router that reads the
layer's input BEFORE attention, top-k of ReGLU experts, three RoPE window
layers to one NoPE global layer) on the serving path, against the plain
float32 reference (``perfbench/reference/smallthinker.py``, no cache): logits
through the K/V pages with and without a prefix-cache hit, the engine with
its prefix cache, planted faults that the comparison must see, the ReGLU
expert layer and its two stats, and the ragged kernel at 7 query heads a KV
head under a window. Small sizes, seeded, float32, on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.models import smallthinker as S
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.moe_ops import expert_stat_names, grouped_expert_ffn
from paddle_tpu.parallel.mesh import serving_mesh
from paddle_tpu.serving import ServingScheduler
from perfbench import harness

from test_unified_step import _BLOCKS, _packed_case

adapter = harness.load_module("perfbench/adapters/serve_smallthinker.py")
reference = harness.load_module("perfbench/reference/smallthinker.py")

#: float32 on both sides: what is left is the order of float32 sums (the
#: paged walk, the grouped product's sorted rows), ~1e-6 of logits of ~3
TOL = 2e-4

_MODEL_KEYS = ("num_hidden_layers", "num_attention_heads",
               "num_key_value_heads", "head_dim", "rope_theta",
               "rope_layout", "sliding_window_layout", "sliding_window_size",
               "rms_norm_eps", "moe_num_active_primary_experts",
               "norm_topk_prob")


def _model_dict(cfg, **over):
    return dict({k: getattr(cfg, k) for k in _MODEL_KEYS}, **over)


def _weights(cfg, seed, boost=6.0):
    """Seeded weights with every matrix ``boost`` times the program's std of
    0.02 (``tests/test_axk1_model.py``: at a hidden size of 64 a branch is
    otherwise ~1% of the stream it joins and a planted fault moves no
    logit)."""
    return {k: v if k == "ln_f" or k in S._NORM_KEYS else v * boost
            for k, v in S.init_stacked_params(cfg, seed=seed).items()}


def _reference_logits(cfg, params, rows, spans, **over):
    saved = reference.QUERY_BLOCK
    reference.QUERY_BLOCK = 16
    try:
        return [np.asarray(x) for x in reference.logits_at(
            adapter.ReferenceWeights(params), rows, spans,
            _model_dict(cfg, **over))]
    finally:
        reference.QUERY_BLOCK = saved


# ---------------------------------------------------------------------------
# the model against the plain reference, through the page cache
# ---------------------------------------------------------------------------
def _logits_through_the_cache(cfg, params, prompt, n_decode, chunk, page=4,
                              shared=0):
    """Prefill ``prompt`` in chunks of ``chunk`` tokens, then decode
    ``n_decode`` greedy tokens one at a time, all through ``ragged_step``
    and one row's K/V pages; returns (tokens fed, the logits after each
    call's last token, the last call's routing record). ``shared``: a
    prefix-cache hit, as the engine makes one: another row first prefills
    the whole prompt, and the measured row's table names that row's first
    ``shared // page`` pages and feeds from position ``shared`` on."""
    width = -(-(len(prompt) + n_decode) // page)
    kp = jnp.zeros((cfg.num_hidden_layers, 1 + 2 * width, page,
                    cfg.num_key_value_heads, cfg.head_dim), jnp.float32)
    vp = jnp.zeros_like(kp)
    bt = (1 + np.arange(2 * width, dtype=np.int32)).reshape(2, width)
    step = jax.jit(lambda *a: S.ragged_step(*a, cfg))

    def call(row, toks, first, kv_lens):
        nonlocal kp, vp
        n = len(toks)
        ids = np.zeros((chunk,), np.int32)
        token_row = np.full((chunk,), -1, np.int32)
        ids[:n], token_row[:n] = toks, row
        positions = np.zeros((chunk,), np.int32)
        positions[:n] = first + np.arange(n)
        last = np.zeros((2,), np.int32)
        last[row] = n - 1
        lg, kp, vp, aux = step(
            params, jnp.asarray(ids), jnp.asarray(token_row),
            jnp.asarray(positions), jnp.asarray(kv_lens, np.int32),
            jnp.asarray(last), kp, vp, jnp.asarray(bt))
        return np.asarray(lg[row]), np.asarray(aux)

    if shared:
        for at in range(0, len(prompt), chunk):
            call(0, prompt[at:at + chunk], at, [min(at + chunk,
                                                    len(prompt)), 0])
        bt[1, :shared // page] = bt[0, :shared // page]
    fed, logits, at = list(prompt), [], shared
    while len(logits) < n_decode + 1:
        n = min(chunk, len(prompt) - at) if at < len(prompt) else 1
        lg, aux = call(1, fed[at:at + n], at, [0, at + n])
        at += n
        if at >= len(prompt):
            logits.append(lg)
            fed.append(int(np.argmax(lg)))
    return np.asarray(fed[:-1], np.int32), np.stack(logits), aux


@pytest.mark.parametrize("n_prompt", [1, 15, 16, 17, 40])
def test_prefill_then_decode_through_the_cache_matches_the_reference(
        n_prompt):
    """LOGITS of chunked prefill (16 tokens a call: the boundary falls
    before, on and after the last prompt token) then decode through the
    pages against the reference's full forward pass: four layers, one
    global and three under a window of 8, 7 query heads a KV head."""
    cfg = S.smallthinker_tiny()
    params = _weights(cfg, 7)
    prompt = np.random.RandomState(n_prompt).randint(
        1, cfg.vocab_size, (n_prompt,)).astype(np.int32)
    fed, got, aux = _logits_through_the_cache(cfg, params, prompt, 6, 16)
    want, = _reference_logits(cfg, params, [fed],
                              [(len(prompt) - 1, len(fed))])
    assert got.shape == want.shape == (7, cfg.vocab_size)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # the last call decoded one token: k assignments a layer, each of
    # moe_ffn_hidden_size units, some of them left active by ReLU
    assert aux.shape == (cfg.num_hidden_layers, 5)
    assert (aux[:, 2] == cfg.moe_num_active_primary_experts).all()
    assert (aux[:, 4] == aux[:, 2] * cfg.moe_ffn_hidden_size).all()
    assert (0 < aux[:, 3]).all() and (aux[:, 3] < aux[:, 4]).all()


def test_a_prefix_cache_hit_gives_the_references_logits():
    """A row whose first 32 positions are another row's pages (written by
    that row's prefill, the window layers' and the global layer's alike)
    feeds only the rest of the prompt and still decodes the reference's
    logits."""
    cfg = S.smallthinker_tiny()
    params = _weights(cfg, 5)
    prompt = np.random.RandomState(4).randint(
        1, cfg.vocab_size, (43,)).astype(np.int32)
    fed, got, _ = _logits_through_the_cache(cfg, params, prompt, 5, 8,
                                            shared=32)
    want, = _reference_logits(cfg, params, [fed],
                              [(len(prompt) - 1, len(fed))])
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    cold = _logits_through_the_cache(cfg, params, prompt, 5, 8)[1]
    np.testing.assert_allclose(got, cold, rtol=0, atol=TOL)


def _silu_for_relu(monkeypatch):
    monkeypatch.setattr(
        reference, "_reglu", lambda x, gate, up, down: (
            jax.nn.silu(x @ gate) * (x @ up)) @ down)
    jax.clear_caches()


def _router_after_attention(monkeypatch):
    """The router fed the post-attention state (where other families put
    it): the normed stream after the attention's residual."""
    def layer(x, lw, l, model):
        eps = float(model["rms_norm_eps"])
        a = reference._norm(x, lw["input_layernorm"], eps=eps)
        x = x + reference.attention(
            a, lw, n_heads=model["num_attention_heads"],
            n_kv=model["num_key_value_heads"], d=model["head_dim"],
            theta=float(model["rope_theta"]),
            rope=bool(model["rope_layout"][l]),
            window=int(model["sliding_window_size"])
            if model["sliding_window_layout"][l] else None)
        m = reference._norm(x, lw["post_attention_layernorm"], eps=eps)
        weight = reference.router_weights(
            m, lw["router"], top_k=int(model[
                "moe_num_active_primary_experts"]), norm=True)
        return x + reference.experts(x, weight, lw, eps=eps)
    monkeypatch.setattr(reference, "layer", layer)


_FAULTS = {
    "silu_for_relu": (_silu_for_relu, {}),
    "router_after_attention": (_router_after_attention, {}),
    "no_renormalisation": (None, dict(norm_topk_prob=False)),
    "windows_off": (None, dict(sliding_window_layout=(0, 0, 0, 0))),
    "rope_on_the_global_layer": (None, dict(rope_layout=(1, 1, 1, 1))),
}


@pytest.mark.parametrize("fault", sorted(_FAULTS))
def test_each_planted_fault_fails_the_tolerance(fault, monkeypatch):
    """The served logits as written against a reference with one planted
    fault: each lies far outside ``TOL`` (and the honest reference inside
    it, in the tests above), so the comparison sees the mechanism."""
    cfg = S.smallthinker_tiny()
    params = _weights(cfg, 7)
    prompt = np.random.RandomState(40).randint(
        1, cfg.vocab_size, (40,)).astype(np.int32)
    fed, got, _ = _logits_through_the_cache(cfg, params, prompt, 4, 16)
    patch, over = _FAULTS[fault]
    if patch is not None:
        patch(monkeypatch)
    try:
        want, = _reference_logits(cfg, params, [fed],
                                  [(len(prompt) - 1, len(fed))], **over)
    finally:
        if patch is _silu_for_relu:
            monkeypatch.undo()
            jax.clear_caches()
    assert np.abs(got - want).max() > 50 * TOL


@pytest.fixture(scope="module")
def served():
    """One engine with the prefix cache on, served in two waves: five cold
    prompts whose last token lies before, on and after a boundary of the
    packed axis, past the window of 8; then the longest again (a
    full-prompt hit) and a prompt that shares its first 24 tokens."""
    from paddle_tpu.observability.runtime import recompiles
    cfg = S.smallthinker_tiny()
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


def test_engine_serves_smallthinker_and_every_token_is_the_references(
        served):
    """submit -> ServingScheduler.step -> _step_unified: same entry points,
    planner, prefix cache and page accounting as Llama, ONE compiled
    program. The repeated prompt is a prefix-cache hit and gives the cold
    run's tokens; every served token of every request (cold, hit, shared
    first pages) is the reference's argmax for its context (teacher-forced;
    within 1e-4 of the reference maximum)."""
    eng, handles, prompts = served["eng"], served["handles"], \
        served["prompts"]
    assert eng._L is S and served["compiles"] == 1
    assert served["cold"]["hits"] == 0 and served["cold"]["misses"] == 5
    snap = eng.cache.snapshot()
    assert snap["hits"] == 2 and snap["cached_tokens"] == 39 + 24
    eng.mgr.check_conservation()
    assert handles[5].stream.tokens == handles[4].stream.tokens
    assert eng._layer_windows == (None, 8, 8, 8)
    assert eng._routing_columns == expert_stat_names(activation="relu")
    rows = [np.concatenate([p, np.asarray(h.stream.tokens, np.int32)])
            for p, h in zip(prompts, handles)]
    spans = [(len(p) - 1, len(p) - 1 + 8) for p in prompts]
    for h, logits in zip(handles, _reference_logits(
            served["cfg"], served["params"], rows, spans)):
        gen = np.asarray(h.stream.tokens)
        assert len(gen) == 8
        deficit = logits.max(-1) - logits[np.arange(8), gen]
        assert deficit.max() <= 1e-4


def test_engine_refuses_a_mesh_of_several_chips_by_name():
    cfg = S.smallthinker_tiny()
    with pytest.raises(ValueError, match="paddle_tpu.models.smallthinker "
                                         "replicates every weight"):
        ContinuousBatchingEngine(cfg, num_slots=2, page_size=4,
                                 max_seq_len=32,
                                 mesh=serving_mesh(2, jax.devices()[:2]))


def test_config_layouts_and_parameter_count():
    """The published period from the layouts' absence; a layout of the
    wrong length or values refused; the whole model and the benchmark's
    eight-layer stage in parameters."""
    full = S.SmallThinkerConfig()
    assert full.rope_layout[:8] == full.sliding_window_layout[:8] == \
        (0, 1, 1, 1, 0, 1, 1, 1)
    assert S.attention_windows(S.smallthinker_tiny()) == (None, 8, 8, 8)
    with pytest.raises(ValueError, match="rope_layout"):
        S.smallthinker_tiny(rope_layout=(0, 1))
    with pytest.raises(ValueError, match="sliding_window_layout"):
        S.smallthinker_tiny(sliding_window_layout=(0, 2, 1, 1))
    with pytest.raises(ValueError, match="softmax router"):
        S.smallthinker_tiny(moe_primary_router_apply_softmax=False)
    cfg = S.smallthinker_tiny()
    params = S.init_stacked_params(cfg, seed=0)
    assert S.param_count(cfg) == sum(int(np.prod(v.shape))
                                     for v in params.values())
    assert S.param_nbytes(cfg) == sum(v.nbytes for v in params.values())
    assert params["router"].dtype == jnp.float32
    assert params["wq"].shape == (4, 64, 14 * 16)       # wider than hidden
    assert set(S.serving_param_specs(cfg)) == set(params)
    assert round(S.param_count(full) / 1e8) == 215
    stage = S.SmallThinkerConfig(num_hidden_layers=8)
    assert round(S.param_count(stage) / 1e6) == 3967


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("norm", [True, False])
def test_router_matches_the_reference(norm):
    """Softmax over every expert, top-k, renormalised over the chosen where
    ``norm_topk_prob``: the program's (experts, weights) against the
    reference's (S, E) weight matrix."""
    cfg = S.smallthinker_tiny(norm_topk_prob=norm)
    rng = np.random.RandomState(5)
    a = rng.randn(64, cfg.hidden_size).astype(np.float32)
    router = (rng.randn(cfg.hidden_size, cfg.moe_num_primary_experts)
              * 0.3).astype(np.float32)
    sel, w = S.route(jnp.asarray(a), jnp.asarray(router), cfg)
    dense = np.zeros((64, cfg.moe_num_primary_experts), np.float32)
    np.put_along_axis(dense, np.asarray(sel), np.asarray(w), axis=1)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.router_weights(
            jnp.asarray(a), jnp.asarray(router),
            top_k=cfg.moe_num_active_primary_experts, norm=norm))
    np.testing.assert_allclose(dense, want, rtol=1e-5, atol=1e-6)
    sums = np.asarray(w).sum(-1)
    if norm:
        np.testing.assert_allclose(sums, 1.0, rtol=1e-5)
    else:
        assert (sums < 1.0).all()


# ---------------------------------------------------------------------------
# the ReGLU expert layer
# ---------------------------------------------------------------------------
def _expert_case(seed=1, t=12, h=16, mi=8, n_exp=6, k=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(t, h).astype(np.float32)
    gate, up = (rng.randn(n_exp, h, mi).astype(np.float32) * 0.3
                for _ in range(2))
    down = rng.randn(n_exp, mi, h).astype(np.float32) * 0.3
    idx = np.stack([rng.permutation(n_exp)[:k] for _ in range(t)]
                   ).astype(np.int32)
    w = rng.rand(t, k).astype(np.float32)
    valid = np.ones((t,), bool)
    valid[[3, 7]] = False
    return x, idx, w, valid, gate, up, down


@pytest.mark.parametrize("first,held", [(0, 6), (2, 3)])
def test_reglu_expert_layer_matches_a_per_expert_loop_and_counts_by_hand(
        first, held):
    """``activation="relu"``: each valid token's sum over its choices of
    ``w down(relu(x gate) * (x up))`` among the experts held, a pad slot 0;
    the two new stats against a numpy count over the assignments made:
    units ReLU left non-zero, and assignments x the expert width."""
    x, idx, w, valid, gate, up, down = _expert_case()
    gate, up, down = (a[first:first + held] for a in (gate, up, down))
    out, stats = grouped_expert_ffn(
        *(jnp.asarray(a) for a in (x, idx, w, valid, gate, up, down)),
        first_expert=first, activation="relu")
    want = np.zeros_like(x)
    active = made = 0
    for t in range(x.shape[0]):
        for j in range(idx.shape[1]):
            e = idx[t, j] - first
            if not valid[t] or not 0 <= e < held:
                continue
            g = x[t] @ gate[e]
            want[t] += w[t, j] * ((np.maximum(g, 0) * (x[t] @ up[e]))
                                  @ down[e])
            active += int((g > 0).sum())
            made += 1
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    stats = np.asarray(stats)
    assert stats.shape == (5,)
    assert stats[2] == made and stats[4] == made * gate.shape[-1]
    assert stats[3] == active and 0 < active < stats[4]


def test_silu_keeps_its_three_or_five_stats_and_relu_adds_two_after():
    x, idx, w, valid, gate, up, down = _expert_case()
    args = [jnp.asarray(a) for a in (x, idx, w, valid, gate, up, down)]
    silu3 = grouped_expert_ffn(*args)[1]
    silu5 = grouped_expert_ffn(*args, n_routed=5)[1]
    relu7 = grouped_expert_ffn(*args, n_routed=5, activation="relu")[1]
    assert silu3.shape == (3,) and silu5.shape == (5,)
    assert relu7.shape == (7,)
    np.testing.assert_array_equal(relu7[:5], silu5)
    np.testing.assert_array_equal(silu5[:3], silu3)
    assert expert_stat_names() == ("experts_hit", "max_expert_load",
                                   "expert_assignments")
    assert expert_stat_names(n_routed=5, activation="relu")[3:] == (
        "zero_expert_assignments", "router_assignments",
        "expert_active_units", "expert_units")


# ---------------------------------------------------------------------------
# the ragged kernel at 7 query heads a KV head, under a window
# ---------------------------------------------------------------------------
_REP7_CASES = {
    "rows_past_the_window": dict(
        kv_lens=[16, 13], spans=[(0, 15, 1), (1, 9, 4)], window=6),
    "prefill_span_straddles_the_bound": dict(
        kv_lens=[10, 2], spans=[(0, 3, 7), (1, 1, 1)], window=6),
    "blocks_window_of_128_keys": dict(
        kv_lens=[300, 129], spans=[(0, 299, 1), (1, 128, 1)], window=128,
        **_BLOCKS),
    # the cell's window at the cell's page: decode rows a block past 4,096
    # keys and one prefill chunk, over 320 pages of 16 a row
    "cell_window_4096": dict(
        kv_lens=[5000, 4110, 0], spans=[(0, 4999, 1), (1, 4100, 10)],
        window=4096, page=16, width=320),
}


@pytest.mark.parametrize("case", sorted(_REP7_CASES))
def test_rep7_windowed_pallas_interpret_matches_array(case):
    """The kernel with 14 query heads on 2 KV heads (interpret mode, T x 7
    query rows a block) under a window, against the XLA reference with
    the same bound; pad slots zeros."""
    spec = dict(_REP7_CASES[case])
    window = spec.pop("window")
    q, *rest = _packed_case(**spec)
    q = np.random.RandomState(7).randn(q.shape[0], 14, q.shape[2]).astype(
        np.float32)
    jargs = [jnp.asarray(a) for a in [q] + rest]
    token_row = rest[3]
    ref = np.asarray(pa.ragged_paged_attention_array(*jargs, window=window))
    out = np.asarray(pa.ragged_paged_attention_pallas(
        *jargs, interpret=True, window=jnp.int32(window)))
    real = token_row >= 0
    assert out.shape == (q.shape[0], 14, q.shape[2])
    np.testing.assert_allclose(out[real], ref[real], rtol=1e-5, atol=1e-6)
    assert np.all(out[~real] == 0.0)
    full = np.asarray(pa.ragged_paged_attention_array(*jargs))
    assert np.abs(full[real] - ref[real]).max() > 1e-3


def test_config_is_a_dataclass_of_the_published_keys():
    """The keys the configuration's file names, under the published
    names."""
    names = {f.name for f in dataclasses.fields(S.SmallThinkerConfig)}
    assert {"moe_ffn_hidden_size", "moe_num_primary_experts",
            "moe_num_active_primary_experts",
            "moe_primary_router_apply_softmax", "norm_topk_prob",
            "rope_layout", "sliding_window_layout", "sliding_window_size",
            "tie_word_embeddings", "max_position_embeddings"} <= names
    assert S.SmallThinkerConfig.serving_module == "paddle_tpu.models.smallthinker"


# ---------------------------------------------------------------------------
# rows that share a document: the GQA kernel folds a shared page once
# ---------------------------------------------------------------------------
# page 16, table width 20 (G = 8); ``documents``: (owner, pages, borrowers):
# each borrower's first ``pages`` table slots name the owner's pages, as
# prefix-cache hits borrow a document's; (kv_lens, spans, documents, window)
_SHARED_CASES = {
    # two documents; doc B's leader is a prefill row above both of its
    # followers; row 6 holds doc A in its table but has no token (kv_lens 0);
    # in the window layer row 4's window starts past doc A's 9 pages
    "two_documents_decode_and_prefill": (
        [150, 100, 160, 120, 300, 90, 0],
        [(0, 149, 1), (1, 99, 1), (2, 150, 10), (3, 119, 1), (4, 299, 1),
         (5, 84, 6)], [(0, 9, [2, 4, 6]), (1, 5, [3, 5])], 100),
    # the leader (row 2, earliest token 160) is not the lowest row; under a
    # window of 128 row 1's window starts at page 7, below row 0's (10): had
    # row 0 led, row 1 would lose pages 7-9
    "leader_not_the_lowest_row": (
        [301, 251, 176, 0], [(0, 300, 1), (1, 250, 1), (2, 160, 16)],
        [(2, 10, [0, 1, 3])], 128),
    # two rows at one position on one table: the follower's every page is
    # shared, and it lists nothing of its own
    "a_follower_that_lists_nothing": (
        [128, 128, 12], [(0, 127, 1), (1, 127, 1), (2, 0, 12)],
        [(0, 8, [1])], 50),
    # private tables only
    "share_nothing": (
        [150, 0, 96, 33, 260],
        [(0, 149, 1), (2, 90, 6), (3, 32, 1), (4, 259, 1)], [], 64),
}


def _shared_case(name, windowed):
    """The case's kernel arguments (14 query heads on 2 KV heads: rep 7, as
    the cell) and its window (None for a global layer)."""
    kv_lens, spans, documents, window = _SHARED_CASES[name]
    shared = [(b, slot, owner) for owner, pages, borrowers in documents
              for b in borrowers for slot in range(pages)]
    q, *rest = _packed_case(kv_lens, spans, shared, page=16, width=20, t=24)
    q = np.random.RandomState(11).randn(24, 14, q.shape[2]).astype(
        np.float32)
    return [q] + rest, window if windowed else None


def _gqa_kernel(args, window):
    kw = {} if window is None else dict(window=jnp.int32(window))
    return np.asarray(pa.ragged_paged_attention_pallas(
        *[jnp.asarray(a) for a in args], interpret=True, **kw))


@pytest.mark.parametrize("windowed", [False, True], ids=["global", "window"])
@pytest.mark.parametrize("case", sorted(_SHARED_CASES))
def test_rows_on_one_document_fold_its_pages_once_and_match_the_array(
        case, windowed):
    """The kernel (interpret mode) where rows' tables name the same leading
    pages, against the XLA reference, in a global and a window layer: every
    live token to float32 rounding, pad slots exactly 0; where rows share,
    the call lists fewer blocks than its rows attend; where nothing is
    shared, each row's outputs are bit for bit those of a call that holds
    that row alone (no other row's items touch its softmax state)."""
    args, window = _shared_case(case, windowed)
    q, kp, vp, bt, token_row, positions, kv_lens = args
    jargs = [jnp.asarray(a) for a in args]
    ref = np.asarray(pa.ragged_paged_attention_array(*jargs, window=window))
    out = _gqa_kernel(args, window)
    real = token_row >= 0
    np.testing.assert_allclose(out[real], ref[real], rtol=1e-5, atol=1e-6)
    assert np.all(np.isfinite(out)) and np.all(out[~real] == 0.0)
    n_rows, page = len(kv_lens), 16
    first = (None if window is None else pa.ragged_first_pages(
        token_row, positions, n_rows, page, window))
    shared = pa.ragged_shared_pages(bt, token_row, positions, kv_lens, page,
                                    first)
    if _SHARED_CASES[case][2]:
        assert shared.sum() > 0
        return
    assert shared.sum() == 0
    for r in np.unique(token_row[real]):
        alone = token_row == r
        solo = [q, kp, vp, bt, np.where(alone, r, -1).astype(np.int32),
                positions, np.where(np.arange(n_rows) == r, kv_lens, 0)]
        assert _gqa_kernel(solo, window)[alone].tobytes() == \
            out[alone].tobytes()


@pytest.mark.parametrize("windowed", [False, True], ids=["global", "window"])
@pytest.mark.parametrize("case", sorted(_SHARED_CASES))
def test_the_hosts_shared_pages_are_the_programs_groups(case, windowed):
    """The in-program groups (each row's leader and shared run, the list's
    row order) against the host's numpy twin that the engine's
    ``shared_pages`` counts from: a leader's earliest token comes first in
    its group and its window starts no later than a member's, its items
    come before its followers', a follower lists what lies past its run and
    inside its window, and the list's length is the host's count of
    blocks. A layer's page offset on every entry changes nothing."""
    args, window = _shared_case(case, windowed)
    _, _, bt, token_row, positions, kv_lens = args[1:]
    n_rows, page, width = len(kv_lens), 16, bt.shape[1]
    tr, pos, kv = (jnp.asarray(a) for a in (token_row, positions, kv_lens))
    first_pos = pa._row_first_positions(tr, pos, n_rows)
    run, lead, order = [np.asarray(x) for x in pa._shared_page_groups(
        jnp.asarray(bt) + 1000, kv, first_pos, page)]
    start = np.zeros(n_rows, np.int64) if window is None else np.asarray(
        pa._window_first_pages(first_pos, page, jnp.int32(window)))
    host_first = None if window is None else pa.ragged_first_pages(
        token_row, positions, n_rows, page, window)
    host = pa.ragged_shared_pages(bt, token_row[None], positions[None],
                                  kv_lens[None], page,
                                  None if host_first is None
                                  else host_first[None])
    assert host.tolist() == [np.maximum(run - start, 0).tolist()]
    first_pos = np.asarray(first_pos)
    for r in np.nonzero(run)[0]:
        leader = lead[r]
        assert run[leader] == 0 and bt[r, 0] == bt[leader, 0]
        assert (first_pos[leader], leader) < (first_pos[r], r)
        assert start[leader] <= start[r]
        assert 0 < run[r] <= min(kv_lens[r], kv_lens[leader]) // page
        assert list(order).index(leader) < list(order).index(r)
    assert sorted(order.tolist()) == list(range(n_rows))
    listed = np.maximum(start, run)
    work, n_live = pa._ragged_work_list(kv, page, width, jnp.asarray(listed),
                                        jnp.asarray(order))
    assert int(n_live) == int(pa.ragged_live_blocks(kv_lens, page, width,
                                                    listed))
    rows, js, _, _ = pa._unpack_work_item(np.asarray(work)[:int(n_live)],
                                          pa._work_item_bits(width))
    # row by row in the list's order, each row from its first listed page
    seen = [order[k] for k in range(n_rows)
            if -(-min(kv_lens[order[k]], width * page) // page)
            > listed[order[k]]]
    assert [r for i, r in enumerate(rows) if i == 0 or rows[i - 1] != r] \
        == seen
    for r in seen:
        assert js[rows == r].min() == listed[r]
    if not _SHARED_CASES[case][2]:
        assert order.tolist() == list(range(n_rows))
        assert not run.any()


def test_the_shared_page_groups_of_two_documents_by_hand():
    """Case ``two_documents_decode_and_prefill``, counted by hand: doc A
    (9 pages) is led by row 0 (earliest token 149), doc B (5 pages) by the
    prefill row 5 (84); row 6 holds doc A but has no token. Under a window
    of 100 a row's list starts at the page of its earliest token less 99,
    and row 4's (page 12) lies past doc A."""
    args, _ = _shared_case("two_documents_decode_and_prefill", True)
    _, _, bt, token_row, positions, kv_lens = args[1:]
    host = lambda first: pa.ragged_shared_pages(
        bt, token_row, positions, kv_lens, 16, first).tolist()
    assert host(None) == [0, 5, 9, 5, 9, 0, 0]
    first = pa.ragged_first_pages(token_row, positions, 7, 16, 100)
    assert first.tolist() == [3, 0, 3, 1, 12, 0, 0]
    assert host(first) == [0, 5, 6, 4, 0, 0, 0]


def _serve_side_by_side(prefix_cache):
    """A 40-token document written by one request, then four requests on it
    that decode side by side (their block tables name the document's 10
    pages where the prefix cache is on). Returns (the four requests' tokens,
    the work records of their dispatches, the engine)."""
    cfg = S.smallthinker_tiny()
    params = _weights(cfg, 2)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(seed=0), num_slots=4, page_size=4,
        max_seq_len=96, chunk=4, prefix_cache=prefix_cache)
    records, record = [], eng._dispatch_record
    eng._dispatch_record = lambda *a: records.append(record(*a)) or records[-1]
    sched = ServingScheduler(eng)
    rng = np.random.RandomState(4)
    draw = lambda n: rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
    document = draw(40)
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
    return [h.stream.tokens for h in handles], records[written:], eng


def test_rows_side_by_side_on_one_document_give_the_cold_runs_tokens(
        monkeypatch):
    """Four prefix hits whose block tables name the SAME document pages
    decode side by side through the GQA kernel (interpret mode, inside the
    engine's one step program, global and window layers): every request's
    tokens are those of an engine without a prefix cache (private pages,
    nothing shared, the XLA twin), and the work record counts the pages
    folded under another row's item."""
    cold, cold_records, cold_eng = _serve_side_by_side(False)
    assert cold_eng.cache is None
    assert all(r["shared_pages"] == 0 for r in cold_records)
    traced = []

    def interpreted(*args, mesh=None, mp_axis=None, **kw):
        traced.append(1)
        return pa.ragged_paged_attention_pallas(*args, **kw, interpret=True)

    monkeypatch.setattr(pa, "ragged_paged_attention", interpreted)
    warm, records, eng = _serve_side_by_side(True)
    assert traced                     # the kernel is in the step program
    assert [len(t) for t in warm] == [6, 14, 14, 10] and warm == cold
    snap = eng.cache.snapshot()
    assert snap["hits"] == 4 and snap["cached_tokens"] == 4 * 40
    assert all(0 <= r["shared_pages"] <= r["attended_pages"]
               for r in records)
    assert records[0]["live_rows"] == 4 and records[0]["shared_pages"] > 0
