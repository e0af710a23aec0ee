"""The SmallThinker configuration's benchmark files: the plain reference
against the layer equations written out by hand in numpy for three tokens,
the new reader over a hand-written trace (and its silence on a program that
writes none of its stats, as an older one), the accepted readers fed
through the configuration's aliases, the adapter's weight names under the
reference's, the configuration's file against the catalog's row and its
cut, the mix's schedule, and the comparison that decides ``correct``
catching planted faults of the program on the CPU. (The reference against
the program, logit by logit: ``tests/test_smallthinker_model.py``.)
"""

import json
import os
import time

import numpy as np
import pytest

from perfbench import (checks, expert_work, harness, loadgen, program_trace)

from test_perfbench_program_trace import (dispatch_spans, observe,
                                          plant_xplane, record, summary)

CELL = "st21-1chip.docqa12k-batch"
#: a JSON-lines catalog of published model configs, one row a model
CATALOG = os.environ.get("MODEL_CONFIG_CATALOG", "")
NEW_READER = "moe.expert_active_share"
#: the catalog's copy of the published config.json, stated a second time
PERIOD = [0, 1, 1, 1] * 13
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": PERIOD,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": PERIOD, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}

reference = harness.load_module("perfbench/reference/smallthinker.py")


def reader(name):
    return harness.load_module(f"perfbench/layer_metrics/{name}.py")


# ---------------------------------------------------------------------------
# the reference against the equations, by hand
# ---------------------------------------------------------------------------
_TINY = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=4,
             rope_theta=10000.0, rms_norm_eps=1e-6, sliding_window_size=2,
             moe_num_active_primary_experts=2, norm_topk_prob=True)


def _hand_weights(rng):
    h, q, kv, e, m = 8, 16, 8, 4, 3
    draw = lambda *shape: rng.randn(*shape).astype(np.float32) * 0.5
    return {"input_layernorm": 1 + 0.1 * draw(h),
            "post_attention_layernorm": 1 + 0.1 * draw(h),
            "q_proj": draw(h, q), "k_proj": draw(h, kv), "v_proj": draw(h, kv),
            "o_proj": draw(q, h), "router": draw(h, e),
            "experts": {"gate_proj": draw(e, h, m), "up_proj": draw(e, h, m),
                        "down_proj": draw(e, m, h)}}


def _np_layer(x, lw, rope, window):
    """The module doc's layer in numpy float64, three positions."""
    eps, nh, nkv, d, n = 1e-6, 4, 2, 4, x.shape[0]
    f64 = lambda a: np.asarray(a, np.float64)
    rms = lambda a, w: a / np.sqrt((a * a).mean(-1, keepdims=True) + eps) \
        * f64(w)
    x = f64(x)
    a = rms(x, lw["input_layernorm"])
    logit = a @ f64(lw["router"])
    p = np.exp(logit - logit.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    q = (a @ f64(lw["q_proj"])).reshape(n, nh, d)
    k = (a @ f64(lw["k_proj"])).reshape(n, nkv, d)
    v = (a @ f64(lw["v_proj"])).reshape(n, nkv, d)
    if rope:                # half rotation, inverse frequencies 1 and 0.01
        freq = np.array([1.0, 0.01])

        def rot(z, pos):
            c, s = np.cos(pos * freq), np.sin(pos * freq)
            return np.concatenate([z[:2] * c - z[2:] * s,
                                   z[2:] * c + z[:2] * s])
        q = np.stack([[rot(q[t, h], t) for h in range(nh)] for t in range(n)])
        k = np.stack([[rot(k[t, h], t) for h in range(nkv)]
                      for t in range(n)])
    out = np.zeros((n, nh, d))
    for t in range(n):
        keys = [s for s in range(t + 1) if window is None or s > t - window]
        for h in range(nh):
            g = h // 2                          # two query heads a KV head
            sc = np.array([q[t, h] @ k[s, g] for s in keys]) / 2.0
            w = np.exp(sc - sc.max())
            w /= w.sum()
            out[t, h] = sum(wi * v[s, g] for wi, s in zip(w, keys))
    x = x + out.reshape(n, nh * d) @ f64(lw["o_proj"])
    mm = rms(x, lw["post_attention_layernorm"])
    ex = lw["experts"]
    for t in range(n):
        top = np.argsort(-p[t])[:2]
        for e in top:
            hidden = np.maximum(mm[t] @ f64(ex["gate_proj"][e]), 0) \
                * (mm[t] @ f64(ex["up_proj"][e]))
            x[t] += p[t, e] / p[t, top].sum() * (
                hidden @ f64(ex["down_proj"][e]))
    return x


@pytest.mark.parametrize("rope,window", [(False, None), (True, 2)],
                         ids=["global_nope", "window_rope"])
def test_the_reference_is_the_layer_equations_on_three_tokens(rope, window):
    """The router on the layer's normed input, renormalised top-2; rope or
    none; the window's bound; ReGLU experts: one layer of the reference on
    three positions against the equations in numpy."""
    import jax
    import jax.numpy as jnp
    lw = _hand_weights(np.random.RandomState(0))
    x = np.random.RandomState(1).randn(3, 8).astype(np.float32)
    model = dict(_TINY, rope_layout=[int(rope)],
                 sliding_window_layout=[int(window is not None)])
    saved = reference.QUERY_BLOCK
    reference.QUERY_BLOCK = 3
    try:
        with jax.default_matmul_precision("highest"):
            got = np.asarray(reference.layer(
                jnp.asarray(x), jax.tree_util.tree_map(jnp.asarray, lw), 0,
                model))
    finally:
        reference.QUERY_BLOCK = saved
    want = _np_layer(x, lw, rope, window)
    assert np.abs(want - x).max() > 0.3
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def _routed_trace():
    """Two complete dispatches at 32 rows x 16 micro-rounds over 8 expert
    layers, and a cut one whose ``cbe.unpack`` still carries stats (left
    out: it lies in no complete step)."""
    rec_a = record(7, 96, 416, 3_100, 200_000)
    rec_b = record(8, 32, 480, 3_200, 210_000)
    rec_a["window_skipped_pages"], rec_b["window_skipped_pages"] = \
        3_300, 3_200
    spans = dispatch_spans(100, rec_a) + dispatch_spans(1200, rec_b)
    spans += [["cbe.fence", 2300, 100, {}], ["cbe.unpack", 2400, 20, {}]]
    stats = [{"expert_calls": 128, "experts_hit": 7_800,
              "expert_assignments": 24_576, "max_expert_load": 1_100,
              "expert_active_units": 9_000_000,
              "expert_units": 24_576 * 768},
             {"expert_calls": 128, "experts_hit": 7_700,
              "expert_assignments": 24_576, "max_expert_load": 1_050,
              "expert_active_units": 9_874_368,
              "expert_units": 24_576 * 768},
             {"expert_calls": 128, "experts_hit": 7_000,
              "expert_assignments": 24_576, "max_expert_load": 900,
              "expert_active_units": 0, "expert_units": 24_576 * 768}]
    for span, st in zip([s for s in spans if s[0] == "cbe.unpack"], stats):
        span[3] = st
    ops = [["ragged_paged_attention.3", 210, 390],
           ["moe_grouped_matmul.5", 600, 390],
           ["ragged_paged_attention.3", 1310, 390],
           ["moe_grouped_matmul.5", 1700, 390], ["fusion.1", 2300, 90]]
    return {"ops": ops, "spans": spans, "window": [0, 2500]}


def _routed_summary():
    s = summary()
    s.op_seconds = {"ragged_paged_attention.3": 0.9,
                    "moe_grouped_matmul.5": 0.9, "fusion.1": 0.3}
    s.busy_s, s.dispatches = 3.0, 3.0
    return s


def _fresh_caches(monkeypatch, tmp_path):
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    for module in (program_trace, expert_work, reader(NEW_READER)):
        monkeypatch.setattr(module, "_CACHE", {})


def test_readers_over_a_hand_trace(tmp_path, monkeypatch, capsys):
    cell = harness.load_cell(CELL)
    plant_xplane(tmp_path, cell.name)
    _fresh_caches(monkeypatch, tmp_path)
    monkeypatch.setattr(program_trace, "load", lambda p: _routed_trace())
    obs = observe(cell, _routed_summary())
    # the two complete dispatches: (9,000,000 + 9,874,368) / (2 x 18,874,368)
    assert reader(NEW_READER).read(obs) == pytest.approx(50.0)
    assert reader(NEW_READER).share(_routed_trace()) == pytest.approx(50.0)
    # the accepted readers, fed through the aliases: all 64 experts held
    assert cell.config["n_routed_experts"] == 64
    assert reader("moe.held_experts_hit_share").read(obs) == \
        pytest.approx(100 * 22_500 / (384 * 64))
    assert reader("kernel.ragged_paged_attention.window_skipped_share").read(
        obs) == pytest.approx(100 * 6_500 / (6_500 + 6_300))
    # the ragged kernel's work over ALL 8 layers (every layer attends), at
    # 28 query and 4 KV heads of 128
    work = program_trace.required_work(
        {"attended_pages": 3_150, "page_size": 16, "token_slots": 512,
         "causal_pairs": 205_000}, cell.config, 1)
    assert work["bytes"] == 8 * (3_150 * 16 * 4 * 128 * 2 * 2
                                 + 512 * 28 * 128 * 2 * 2)
    assert reader("ragged_paged_attention_roofline").read(obs) is not None
    # the grouped products' work at the expert width the alias gives
    experts = expert_work.required_work(
        {"experts_hit": 22_500, "expert_assignments": 73_728}, cell.config)
    assert experts["flops"] == 2.0 * 73_728 * 3 * 2560 * 768
    assert reader("tpot.moe_grouped_matmul_roofline").read(obs) is not None
    capsys.readouterr()


@pytest.mark.parametrize("cell_name", ["lcf-1chip.reason-batch", CELL])
def test_the_new_reader_is_silent_without_its_stats(
        cell_name, tmp_path, monkeypatch, capsys):
    """A program older than this model, or a SwiGLU family (three or
    five routing stats): no ``expert_units``. The reader returns None and
    does not raise; so it does without a trace at all."""
    cell = harness.load_cell(cell_name)
    _fresh_caches(monkeypatch, tmp_path)
    assert reader(NEW_READER).read(observe(cell, None)) is None
    assert reader(NEW_READER).read(observe(cell, summary())) is None
    plant_xplane(tmp_path, cell.name)
    spans = dispatch_spans(100, record(7, 496, 16, 2047, 598432))
    for span in spans:
        if span[0] == "cbe.unpack":
            span[3] = {"expert_calls": 96, "experts_hit": 850,
                       "expert_assignments": 1500, "max_expert_load": 420}
    trace = {"ops": [["fusion.1", 210, 390]], "spans": spans,
             "window": [0, 1200]}
    monkeypatch.setattr(program_trace, "load", lambda p: trace)
    assert reader(NEW_READER).read(observe(cell, summary())) is None
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the configuration's file, the mix, the cell
# ---------------------------------------------------------------------------
def test_the_configuration_is_the_catalogs_row_but_its_depth():
    """Every key of the catalog's row at the file's top level with its
    value, the two layouts whole, but the depth; the aliases the accepted
    readers need under ``assumed.aliases``; the cell listed under the
    readers that apply to it."""
    config = harness.load_cell(CELL).config
    if CATALOG and os.path.exists(CATALOG):     # where a catalog is given
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "SmallThinker-21BA3B-Instruct"]
        assert config["source"] == row["source_url"]
        assert row["config"] == PUBLISHED
    assert len(PUBLISHED) == 20
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert (config["reduced"][key]["from"], config[key]) == (52, 8)
        else:
            assert config[key] == value and type(config[key]) is type(value)
    assert sorted(config["reduced"]) == ["num_hidden_layers"]
    assert config["rope_layout"][:8] == [0, 1, 1, 1, 0, 1, 1, 1]
    aliases = config["assumed"]["aliases"]
    assert sorted(aliases) == ["moe_intermediate_size", "n_routed_experts"]
    assert config["moe_intermediate_size"] == config["moe_ffn_hidden_size"]
    assert config["n_routed_experts"] == config["moe_num_primary_experts"]
    for key in ("layers", "router_input", "router", "rotary_pairing",
                "attention", "secondary_experts", "weights",
                "router_precision"):
        assert key in config["assumed"]
    assert "one pipeline stage of a bf16 replica" in config["deployment"]
    assert config["serving"] == {
        "dtype": "bfloat16", "num_slots": 32, "max_seq_len": 16384,
        "kv_pool_tokens": 262144, "prefix_cache": True,
        "max_queue_depth": 32}
    assert config["correct"]["kernels"] == [
        "ragged_paged_attention", "rms_norm_fwd", "moe_grouped_matmul"]
    assert config["correct"]["max_positions"] == 16384
    bench = harness.read_json("BENCHMARK.json")
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in bench[g] if CELL in m.get("workloads", ())}
    assert "tpot_p50_ms" in listed and "serve_tok_s" not in listed
    assert {NEW_READER,
            "kernel.ragged_paged_attention.window_skipped_share-tpot",
            "kernel.ragged_paged_attention.time_share",
            "kernel.ragged_paged_attention.grid_live_share",
            "ragged_paged_attention_roofline", "moe.held_experts_hit_share",
            "moe.max_expert_load-tpot",
            "kernel.moe_grouped_matmul.time_share-tpot",
            "tpot.moe_grouped_matmul_roofline",
            "sched.prefix_hit_token_share",
            "prefix.blocks_walked_per_dispatch"} <= listed
    assert not any("mla_paged_attention" in name for name in listed)
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1
    assert bench["per_layer"][-2]["name"] == NEW_READER


def test_the_schedule_seed_is_the_first_from_40_that_covers_the_documents():
    """Four 12,288-token documents, every request on one of them; the 8
    warm-up requests of schedule 40 draw all four (the cell 7 precedent),
    and the callers are the slots."""
    cell = harness.load_cell(CELL)
    mix = cell.traffic
    assert mix["schedule_seed"] == 40
    assert mix["shared_prefix"] == {"tokens": 12288, "groups": 4,
                                    "share": 1.0}
    assert cell.params["clients"] == 32 == cell.config["serving"][
        "num_slots"]
    traffic = loadgen.Traffic(mix, cell.params, 151936, 2040000001, 51)
    docs = {tuple(p[:8]) for p in traffic._prefixes}
    warm = traffic.warmup()
    assert len(warm) == 8
    assert {tuple(r.prompt[:8]) for r in warm} == docs
    batch = traffic.batch()
    longest = max(len(r.prompt) + r.max_new_tokens for r in warm + batch)
    assert longest <= cell.config["serving"]["max_seq_len"]
    assert all(len(r.prompt) > 12288 for r in warm + batch)


def test_the_adapters_weight_names_are_the_references():
    """``ReferenceWeights.layer`` hands the reference every name its module
    doc lists; the adapter takes the layouts' first n entries."""
    from paddle_tpu.models import smallthinker as S
    adapter = harness.load_module("perfbench/adapters/serve_smallthinker.py")
    cell = harness.load_cell(CELL, rehearse=True)
    cfg = adapter.smallthinker_config(cell.config, "float32")
    assert cfg.rope_layout == (0, 1, 1, 1)
    assert cfg.num_attention_heads // cfg.num_key_value_heads == 7
    params = S.init_stacked_params(cfg, seed=1)
    lw = adapter.ReferenceWeights(params).layer(2)
    assert sorted(lw) == sorted([
        "q_proj", "k_proj", "v_proj", "o_proj", "input_layernorm",
        "post_attention_layernorm", "router", "experts"])
    assert sorted(lw["experts"]) == ["down_proj", "gate_proj", "up_proj"]
    np.testing.assert_array_equal(lw["q_proj"], params["wq"][2])
    np.testing.assert_array_equal(lw["experts"]["up_proj"],
                                  params["we_up"][2])
    assert lw["router"].shape == (64, 8)


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``, against planted faults
# ---------------------------------------------------------------------------
def _serve(seed=11):
    """The cell's own path at its rehearsal size: the adapter's server, the
    mix's warm-up and batch (six requests, all of them in the sample)."""
    cell = harness.load_cell(CELL, rehearse=True)
    adapter = harness.load_module("perfbench/adapters/serve_smallthinker.py")
    server = adapter.Server(cell.config, 1, seed)
    # every matrix six times the program's std of 0.02 (a branch is then as
    # large as the stream it joins, as at the published widths)
    server.params = {k: v if k in ("ln_f", "ln_in", "ln_post") else v * 6.0
                     for k, v in server.params.items()}
    traffic = loadgen.Traffic(cell.traffic, dict(cell.params, batch_rps=2.0),
                              server.vocab_size, seed, 1.2)
    clock = time.perf_counter
    records, _ = harness.serve_batch(
        server, traffic, harness.Spans(clock), harness.Tracer(None, 1.0),
        clock)
    return cell.config, server, records


def _serve_and_check(seed=11):
    """``checks.check_serving`` of the served batch with a float32 limit."""
    config, server, records = _serve(seed)
    config = dict(config, correct=dict(
        config["correct"], max_deficit=1e-3, mean_deficit=1e-4))
    return checks.check_serving(server, records, config, on_chip=False)


def _silu_experts(monkeypatch):
    from paddle_tpu.models import smallthinker as S
    real = S.grouped_expert_ffn
    # the experts' output with silu; the record as the step names it
    monkeypatch.setattr(
        S, "grouped_expert_ffn", lambda *a, activation, **kw: (
            real(*a, activation="silu", **kw)[0],
            real(*a, activation=activation, **kw)[1]))


def _no_window(monkeypatch):
    from paddle_tpu.models import smallthinker as S
    monkeypatch.setattr(S, "attention_windows",
                        lambda c: (None,) * c.num_hidden_layers)


def _not_renormalised(monkeypatch):
    import dataclasses
    from paddle_tpu.models import smallthinker as S
    real = S.route
    monkeypatch.setattr(S, "route", lambda a, r, c: real(
        a, r, dataclasses.replace(c, norm_topk_prob=False)))


def _rope_everywhere(monkeypatch):
    import dataclasses
    adapter = harness.load_module("perfbench/adapters/serve_smallthinker.py")
    real = adapter.smallthinker_config
    monkeypatch.setattr(adapter, "smallthinker_config", lambda m, dt: (
        lambda c: dataclasses.replace(
            c, rope_layout=(1,) * c.num_hidden_layers))(real(m, dt)))


@pytest.mark.parametrize(
    "fault", [None, _silu_experts, _no_window, _not_renormalised,
              _rope_everywhere],
    ids=["as_written", "silu_experts", "no_window", "not_renormalised",
         "rope_on_the_global_layer"])
def test_correct_catches_planted_faults(fault, monkeypatch):
    """As written the served tokens are the reference's argmax (float32,
    deficits ~0); with SwiGLU experts, the windows off, the router's weights
    not renormalised or rope on the global layer the sample lies beyond the
    limit and ``correct`` is false."""
    if fault is not None:
        fault(monkeypatch)
    ok, facts = _serve_and_check()
    seen = facts["reference"]
    assert seen["tokens"] > 30 and len(seen["requests"]) == 6
    if fault is None:
        assert ok and facts["problems"] == []
        assert seen["max_deficit"] < 1e-3
    else:
        assert not ok
        assert seen["max_deficit"] > 1e-3
        assert any("below the reference maximum" in p
                   for p in facts["problems"])


# ---------------------------------------------------------------------------
# the configuration's own limits against the reference computed in float8
# ---------------------------------------------------------------------------
FLOAT8 = "perfbench/reference/smallthinker_float8.py"


class _Float8Program:
    """The served server with the sampled requests' tokens replaced by the
    float8 reference's greedy answers to the same prompts: a program that
    computed in float8."""

    def __init__(self, server, records, config):
        float8 = harness.load_module(FLOAT8)
        weights = server.reference_weights()
        self._server, self._tokens = server, {}
        done = [r for r in records if r.complete]
        for r in checks._sample(done, config["correct"]):
            row = list(r.prompt)
            for _ in range(r.n_out):
                logits, = float8.logits_at(
                    weights, [np.asarray(row, np.int32)],
                    [(len(row) - 1, len(row))], config)
                row.append(int(np.argmax(logits[-1])))
            self._tokens[id(r.handle)] = row[len(r.prompt):]

    def tokens(self, handle):
        return self._tokens.get(id(handle)) or self._server.tokens(handle)

    def __getattr__(self, name):
        return getattr(self._server, name)


@pytest.mark.parametrize("place", ["program", "reference"])
def test_the_configurations_limits_refuse_the_float8_control(place):
    """With the configuration's own ``correct`` limits: the program's tokens
    pass; the float8 reference's greedy tokens in the program's place fail,
    and so do the program's tokens held against the float8 reference in
    the reference's place (what a chip run can afford at 13k positions).
    Each fails by the mean, the limit that separates the two."""
    config, server, records = _serve(seed=12)
    if place == "program":
        server = _Float8Program(server, records, config)
        ok, facts = checks.check_serving(server, records, config,
                                         on_chip=False)
    else:
        honest, seen = checks.check_serving(
            server, records, config, on_chip=False)
        assert honest and seen["reference"]["mean_deficit"] < 1e-4
        ok, facts = checks.check_serving(
            _Unreleased(server), records, dict(config, reference=FLOAT8),
            on_chip=False)
    assert not ok
    assert facts["reference"]["mean_deficit"] > \
        config["correct"]["mean_deficit"]
    assert any("on average" in p for p in facts["problems"])


class _Unreleased:
    """A server whose engine was released already: its health as it was."""

    def __init__(self, server):
        self._server = server

    def problems(self):
        return []

    def release_engine(self):
        pass

    def __getattr__(self, name):
        return getattr(self._server, name)
