"""The LongCat-Flash configuration's benchmark files: the plain reference
against the layer equations written out by hand in numpy for two tokens, the
two new readers over a hand-written trace (and their silence on a program
that writes none of their stats, as the parent commit), both accepted
roofline readers fed through the configuration's aliases, the adapter's
weight names under the reference's, the configuration's file against the
catalog's row and its cut, and the comparison that decides ``correct``
catching planted faults of the program on the CPU. (The reference against the
program, logit by logit: ``tests/test_longcat_flash_model.py``.)
"""

import json
import os
import time

import numpy as np
import pytest

from perfbench import (checks, expert_work, harness, latent_work, loadgen,
                       program_trace, zero_expert_work)

from test_perfbench_program_trace import (dispatch_spans, observe,
                                          plant_xplane, record, summary)

CELL = "lcf-1chip.reason-batch"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = ["moe.zero_expert_share", "moe.held_assignments_per_token"]
#: the catalog's copy of the published config.json, stated a second time
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12}

reference = harness.load_module("perfbench/reference/longcat_flash.py")


def reader(name):
    return harness.load_module(f"perfbench/layer_metrics/{name}.py")


# ---------------------------------------------------------------------------
# the reference against the equations, by hand
# ---------------------------------------------------------------------------
_TINY = dict(hidden_size=8, num_attention_heads=2, q_lora_rank=4,
             kv_lora_rank=6, qk_nope_head_dim=4, qk_rope_head_dim=2,
             v_head_dim=3, mla_scale_q_lora=True, mla_scale_kv_lora=True,
             moe_topk=2, routed_scaling_factor=6, zero_expert_num=2,
             num_layers=1, rms_norm_eps=1e-5, rope_theta=10000.0,
             first_expert=0)


def _hand_weights(rng):
    h, cq, ckv, nh, nope, rope, v, f, m = 8, 4, 6, 2, 4, 2, 3, 5, 3
    draw = lambda *shape: rng.randn(*shape).astype(np.float32) * 0.5
    sub = lambda: {
        "input_layernorm": 1 + 0.1 * draw(h),
        "post_attention_layernorm": 1 + 0.1 * draw(h),
        "q_a_proj": draw(h, cq), "q_a_layernorm": 1 + 0.1 * draw(cq),
        "q_b_proj": draw(cq, nh * (nope + rope)),
        "kv_a_proj": draw(h, ckv + rope),
        "kv_a_layernorm": 1 + 0.1 * draw(ckv),
        "kv_b_proj": draw(ckv, nh * (nope + v)), "o_proj": draw(nh * v, h),
        "mlp": {"gate_proj": draw(h, f), "up_proj": draw(h, f),
                "down_proj": draw(f, h)}}
    return {"sub": [sub(), sub()], "router": draw(h, 5),
            "expert_bias": np.zeros((5,), np.float32),
            "experts": {"gate_proj": draw(3, h, m), "up_proj": draw(3, h, m),
                        "down_proj": draw(3, m, h)}}


def _np_layer(x, lw, chosen):
    """The module doc's layer in numpy float64, two positions, with the
    router's choice handed in: ``chosen[t]`` = the outputs token t picked
    (0-2 routed experts, 3-4 identities)."""
    eps, nh, nope, rope, v, ckv = 1e-5, 2, 4, 2, 3, 6
    f64 = lambda a: np.asarray(a, np.float64)
    rms = lambda a, w: a / np.sqrt((a * a).mean(-1, keepdims=True) + eps) \
        * f64(w)
    silu = lambda z: z / (1 + np.exp(-z))
    ffn = lambda a, w: (silu(a @ f64(w["gate_proj"]))
                        * (a @ f64(w["up_proj"]))) @ f64(w["down_proj"])

    def rot(z, pos):                            # one pair: dims 0 and 1
        c, s = np.cos(pos), np.sin(pos)         # inverse frequency 1
        return np.stack([z[..., 0] * c - z[..., 1] * s,
                         z[..., 1] * c + z[..., 0] * s], -1)

    def mla(x, w):
        a = rms(x, w["input_layernorm"])
        c_q = rms(a @ f64(w["q_a_proj"]), w["q_a_layernorm"])
        q = (2 ** 0.5 * c_q @ f64(w["q_b_proj"])).reshape(2, nh, nope + rope)
        kv_a = a @ f64(w["kv_a_proj"])
        c_kv = (8 / 6) ** 0.5 * rms(kv_a[:, :ckv], w["kv_a_layernorm"])
        kv = (c_kv @ f64(w["kv_b_proj"])).reshape(2, nh, nope + v)
        k_r = np.stack([rot(kv_a[t, ckv:], t) for t in range(2)])
        out = np.zeros((2, nh, v))
        for t in range(2):
            for head in range(nh):
                q_r = rot(q[t, head, nope:], t)
                scores = np.array([
                    q[t, head, :nope] @ kv[s, head, :nope] + q_r @ k_r[s]
                    for s in range(t + 1)]) * 6 ** -0.5
                p = np.exp(scores - scores.max())
                p /= p.sum()
                out[t, head] = sum(p[s] * kv[s, head, nope:]
                                   for s in range(t + 1))
        return out.reshape(2, nh * v) @ f64(w["o_proj"])

    sub0, sub1 = lw["sub"]
    x = f64(x)
    x = x + mla(x, sub0)
    m = rms(x, sub0["post_attention_layernorm"])
    logit = m @ f64(lw["router"])
    s = np.exp(logit - logit.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    e = np.zeros_like(m)
    for t in range(2):
        for j in chosen[t]:
            term = m[t] if j >= 3 else ffn(m[t], {
                k: w[j] for k, w in lw["experts"].items()})
            e[t] += 6 * s[t, j] * term
    x = x + ffn(m, sub0["mlp"])
    x = x + mla(x, sub1)
    return x + ffn(rms(x, sub1["post_attention_layernorm"]), sub1["mlp"]) + e


def test_the_reference_is_the_layer_equations_on_two_tokens():
    """Sub-layer order, where the shortcut is taken and added, both lora
    scales, the rope on one shared key, softmax scores times 6 unnormalised
    and the identity branch: one layer of the reference on two positions
    against the equations in numpy, with the router's choices read off the
    reference's own weight matrix (a routed expert and an identity are both
    among them)."""
    import jax
    import jax.numpy as jnp
    lw = _hand_weights(np.random.RandomState(0))
    x = np.random.RandomState(1).randn(2, 8).astype(np.float32)
    saved = reference.QUERY_BLOCK, reference.HEAD_BLOCK
    reference.QUERY_BLOCK, reference.HEAD_BLOCK = 2, 1
    try:
        with jax.default_matmul_precision("highest"):
            got = np.asarray(reference.layer(
                jnp.asarray(x), jax.tree_util.tree_map(jnp.asarray, lw),
                _TINY))
            sub0 = lw["sub"][0]
            mid = jnp.asarray(x) + reference.attention(
                jnp.asarray(x), sub0, n_heads=2, nope=4, rope=2, v_dim=3,
                kv_rank=6, eps=1e-5, theta=10000.0, s_q=2 ** 0.5,
                s_kv=(8 / 6) ** 0.5)
            m = reference._rms_norm(mid, sub0["post_attention_layernorm"],
                                    1e-5)
            weight = np.asarray(reference.router_weights(
                m, lw["router"], lw["expert_bias"], top_k=2,
                scaling_factor=6.0))
    finally:
        reference.QUERY_BLOCK, reference.HEAD_BLOCK = saved
    chosen = [np.flatnonzero(row) for row in weight]
    assert all(len(c) == 2 for c in chosen)
    picked = {int(j) for c in chosen for j in c}
    assert min(picked) < 3 <= max(picked)
    want = _np_layer(x, lw, chosen)
    assert np.abs(want - x).max() > 0.5
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def routed_trace():
    """Two complete dispatches of this model at 128 rows x 16 micro-rounds
    on 8 cache layers and 4 expert layers, and a cut one whose
    ``cbe.unpack`` still carries its stats."""
    rec_a = record(7, 96, 1952, 5_200, 82_000)
    rec_b = record(8, 32, 2016, 4_800, 78_000)
    spans = dispatch_spans(100, rec_a) + dispatch_spans(1200, rec_b)
    spans += [["cbe.fence", 2300, 100, {}], ["cbe.unpack", 2400, 20, {}]]
    stats = [{"expert_calls": 64, "experts_hit": 900,
              "expert_assignments": 2100, "max_expert_load": 400,
              "zero_expert_assignments": 33_000,
              "router_assignments": 98_304},
             {"expert_calls": 64, "experts_hit": 880,
              "expert_assignments": 2000, "max_expert_load": 380,
              "zero_expert_assignments": 32_000,
              "router_assignments": 98_304},
             {"expert_calls": 64, "experts_hit": 860,
              "expert_assignments": 2044, "max_expert_load": 390,
              "zero_expert_assignments": 33_304,
              "router_assignments": 98_304}]
    for span, st in zip([s for s in spans if s[0] == "cbe.unpack"], stats):
        span[3] = st
    ops = [["mla_paged_attention.3", 210, 390],
           ["moe_grouped_matmul.5", 600, 390],
           ["mla_paged_attention.3", 1310, 390],
           ["moe_grouped_matmul.5", 1700, 390], ["fusion.1", 2300, 90]]
    return {"ops": ops, "spans": spans, "window": [0, 2500]}


def routed_summary():
    s = summary()
    s.op_seconds = {"mla_paged_attention.3": 0.9,
                    "moe_grouped_matmul.5": 0.9, "fusion.1": 0.3}
    s.busy_s, s.dispatches = 3.0, 3.0
    return s


def _fresh_caches(monkeypatch, tmp_path):
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    for module in (program_trace, expert_work, zero_expert_work):
        monkeypatch.setattr(module, "_CACHE", {})


def test_readers_over_a_hand_trace(tmp_path, monkeypatch, capsys):
    cell = harness.load_cell(CELL)
    plant_xplane(tmp_path, cell.name)
    _fresh_caches(monkeypatch, tmp_path)
    monkeypatch.setattr(program_trace, "load", lambda p: routed_trace())
    obs = observe(cell, routed_summary())
    assert zero_expert_work.reduce(routed_trace()) == {
        "expert_assignments": 6144, "zero_expert_assignments": 98_304,
        "router_assignments": 3 * 98_304, "dispatches": 3}
    # a third of the router's assignments chose an identity; of a token's
    # 12, a quarter of one went to an expert held here
    assert reader("moe.zero_expert_share").read(obs) == \
        pytest.approx(100 / 3)
    assert reader("moe.held_assignments_per_token").read(obs) == \
        pytest.approx(0.25)
    # the accepted readers, fed through the aliases: the HELD experts,
    # 16 a call; the latent kernel's work over the 8 CACHE layers
    assert cell.config["n_routed_experts"] == 16
    assert reader("moe.held_experts_hit_share").read(obs) == \
        pytest.approx(100 * 2640 / (192 * 16))
    assert reader("moe.max_expert_load").read(obs) == \
        pytest.approx(1170 / 192)
    work = latent_work.required_work(
        {"attended_pages": 5_000, "page_size": 16, "token_slots": 512,
         "causal_pairs": 80_000}, cell.config)
    assert work["bytes"] == 8 * (5_000 * 16 * 576 * 2 + 512 * 64 * 320 * 2)
    least = max(work["bytes"] / 819e9, work["flops"] / 197e12)
    assert reader("mla_paged_attention_roofline").read(obs) == \
        pytest.approx(100 * least / (0.9 / 3.0))
    # the grouped products' work is the held experts': the identities are
    # in no count it reads
    experts = expert_work.required_work(
        {"experts_hit": 2640, "expert_assignments": 6144}, cell.config)
    assert experts["flops"] == 2.0 * 6144 * 3 * 6144 * 2048
    assert reader("tpot.moe_grouped_matmul_roofline").read(obs) is not None
    capsys.readouterr()


def test_new_readers_are_silent_on_a_program_without_their_stats(
        tmp_path, monkeypatch, capsys):
    """The parent commit under these files, in a cell it can run, or a
    model whose router has no zero-compute experts (four routing stats): no
    ``router_assignments``. Both new readers return None and do not raise;
    so they do without a trace at all."""
    cell = harness.load_cell("axk1-1chip.docqa-batch")
    _fresh_caches(monkeypatch, tmp_path)
    for name in NEW_READERS:
        assert reader(name).read(observe(cell, None)) is None
        assert reader(name).read(observe(cell, summary())) is None
    plant_xplane(tmp_path, cell.name)
    spans = dispatch_spans(100, record(7, 496, 16, 2047, 598432))
    for span in spans:
        if span[0] == "cbe.unpack":
            span[3] = {"expert_calls": 96, "experts_hit": 850,
                       "expert_assignments": 1500, "max_expert_load": 420}
    trace = {"ops": [["mla_paged_attention.3", 210, 390]], "spans": spans,
             "window": [0, 1200]}
    monkeypatch.setattr(program_trace, "load", lambda p: trace)
    assert zero_expert_work.reduce(trace) is None
    for name in NEW_READERS:
        assert reader(name).read(observe(cell, summary())) is None
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the configuration's file
# ---------------------------------------------------------------------------
def test_the_configuration_is_the_catalogs_row_but_its_cut():
    """Every key of the catalog's row at the file's top level with its
    value, but the three cuts; the aliases the accepted readers need stand
    under ``assumed.aliases`` with the published key each stands for; the
    cell is listed where ISSUE 37 lists it."""
    config = harness.load_cell(CELL).config
    if os.path.exists(CATALOG):     # where this machine has the catalog
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "LongCat-Flash-Chat"]
        assert config["source"] == row["source_url"]
        assert row["config"] == PUBLISHED
    cut = {"num_layers": (28, 4), "n_routed_experts": (512, 16),
           "vocab_size": (131072, 16384)}
    assert len(PUBLISHED) == 23
    for key, value in PUBLISHED.items():
        if key in cut:
            assert (config["reduced"][key]["from"], config[key]) == cut[key]
            assert value == cut[key][0]
        else:
            assert config[key] == value and type(config[key]) is type(value)
    assert sorted(config["reduced"]) == sorted(list(cut)
                                               + ["num_hidden_layers"])
    assert (config["published_n_routed_experts"], config["first_expert"],
            config["zero_expert_num"]) == (512, 0, 256)
    aliases = config["assumed"]["aliases"]
    assert sorted(aliases) == ["moe_intermediate_size", "num_hidden_layers",
                               "num_key_value_heads"]
    assert config["num_hidden_layers"] == 2 * config["num_layers"]
    assert config["moe_intermediate_size"] == config["expert_ffn_hidden_size"]
    assert config["num_key_value_heads"] == config["num_attention_heads"]
    for key in ("layers", "router", "lora_scales", "head_dim",
                "rotary_pairing", "weights", "router_precision"):
        assert key in config["assumed"]
    assert "32 chips share each layer" in config["deployment"]
    assert "EP32" in config["deployment"]
    assert config["serving"] == {
        "dtype": "bfloat16", "num_slots": 128, "max_seq_len": 4096,
        "kv_pool_tokens": 196608, "prefix_cache": True,
        "max_queue_depth": 128}
    assert config["correct"]["kernels"] == [
        "mla_paged_attention", "rms_norm_fwd", "moe_grouped_matmul"]
    assert (config["correct"]["requests"], config["correct"]["longest"]) \
        == (4, 1)
    bench = harness.read_json("BENCHMARK.json")
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in bench[g] if CELL in m.get("workloads", ())}
    assert "tpot_p50_ms" in listed and "serve_tok_s" not in listed
    assert set(NEW_READERS) <= listed
    assert {"kernel.mla_paged_attention.time_share",
            "mla_paged_attention_roofline", "moe.held_experts_hit_share",
            "moe.max_expert_load-tpot",
            "kernel.moe_grouped_matmul.time_share-tpot",
            "tpot.moe_grouped_matmul_roofline"} <= listed
    assert not any("ragged_paged_attention" in name for name in listed)


def test_the_cell_is_cell_eights_mix_and_rows():
    """The accepted mix as it is, as many callers as slots, and the same
    traffic as ``jamba2-1chip.reason-batch`` at equal ``batch_rps``."""
    cell, other = harness.load_cell(CELL), harness.load_cell(
        "jamba2-1chip.reason-batch")
    assert cell.traffic == other.traffic
    assert cell.traffic["schedule_seed"] == 33
    assert cell.params["clients"] == 128 == cell.config["serving"][
        "num_slots"]
    draw = lambda c: loadgen.Traffic(
        c.traffic, dict(c.params, batch_rps=2.0), 16384, 5, 51).batch()
    mine, theirs = draw(cell), draw(other)
    assert len(mine) == len(theirs) == 102
    assert [(len(r.prompt), r.max_new_tokens) for r in mine] == \
        [(len(r.prompt), r.max_new_tokens) for r in theirs]


def test_the_adapters_weight_names_are_the_references():
    """``ReferenceWeights.layer`` hands the reference every name its module
    doc lists, two sub-layers a layer, ``kv_b_proj`` joined head-major from
    the program's two halves, the experts HELD and the router whole."""
    from paddle_tpu.models import longcat_flash as F
    adapter = harness.load_module("perfbench/adapters/serve_longcat.py")
    cell = harness.load_cell(CELL, rehearse=True)
    cfg = adapter.longcat_config(cell.config, "float32")
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.first_expert,
            cfg.zero_expert_num) == (8, 2, 2, 4)
    params = F.init_stacked_params(cfg, seed=1)
    weights = adapter.ReferenceWeights(params)
    lw = weights.layer(1)
    assert sorted(lw) == ["expert_bias", "experts", "router", "sub"]
    assert len(lw["sub"]) == 2
    for i, sub in enumerate(lw["sub"]):
        assert sorted(sub) == sorted([
            "input_layernorm", "post_attention_layernorm", "q_a_layernorm",
            "kv_a_layernorm", "q_a_proj", "q_b_proj", "kv_a_proj",
            "kv_b_proj", "o_proj", "mlp"])
        assert sorted(sub["mlp"]) == ["down_proj", "gate_proj", "up_proj"]
        assert sub["mlp"]["gate_proj"].shape == (64, 96)
        np.testing.assert_array_equal(sub["q_a_proj"], params["w_qa"][1, i])
        kv_b = np.asarray(sub["kv_b_proj"]).reshape(32, 4, 16 + 16)
        np.testing.assert_array_equal(kv_b[..., :16], params["w_uk"][1, i])
        np.testing.assert_array_equal(kv_b[..., 16:], params["w_uv"][1, i])
    assert lw["router"].shape == (64, 12)
    assert lw["experts"]["down_proj"].shape == (2, 32, 64)
    np.testing.assert_array_equal(lw["experts"]["up_proj"],
                                  params["we_up"][1])
    assert weights.lm_head.shape == (64, 256)


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``, against planted faults
# ---------------------------------------------------------------------------
def _serve_and_check(seed=11):
    """The cell's own path at its rehearsal size: the adapter's server, the
    mix's warm-up and batch, ``checks.check_serving`` with a float32
    limit."""
    cell = harness.load_cell(CELL, rehearse=True)
    adapter = harness.load_module("perfbench/adapters/serve_longcat.py")
    server = adapter.Server(cell.config, 1, seed)
    # every matrix six times the program's std of 0.02 (a branch is then as
    # large as the stream it joins, as at the published widths)
    server.params = {
        k: v if k in ("ln_f", "expert_bias") or "norm" in k
        or k in ("ln_in", "ln_post") else v * 6.0
        for k, v in server.params.items()}
    traffic = loadgen.Traffic(cell.traffic, dict(cell.params, batch_rps=2.0),
                              server.vocab_size, seed, 1.2)
    clock = time.perf_counter
    records, _ = harness.serve_batch(
        server, traffic, harness.Spans(clock), harness.Tracer(None, 1.0),
        clock)
    config = dict(cell.config, correct=dict(
        cell.config["correct"], max_deficit=1e-3, mean_deficit=1e-4))
    return checks.check_serving(server, records, config, on_chip=False)


def _identities_left_out(monkeypatch):
    from paddle_tpu.models import longcat_flash as F
    real = F.grouped_expert_ffn
    # ids beyond the routed width fall outside the held slice, as they did
    # before the layer knew the routed width
    monkeypatch.setattr(
        F, "grouped_expert_ffn",
        lambda *a, n_routed, **kw: (real(*a, **kw)[0], real(
            *a, n_routed=n_routed, **kw)[1]))


def _no_kv_scale(monkeypatch):
    from paddle_tpu.models import longcat_flash as F
    real = F.lora_scales
    monkeypatch.setattr(F, "lora_scales", lambda c: (real(c)[0], 1.0))


def _dropped_assignment(monkeypatch):
    from paddle_tpu.models import longcat_flash as F
    real = F.grouped_expert_ffn
    # the router's last choice is computed by nobody
    monkeypatch.setattr(
        F, "grouped_expert_ffn",
        lambda x, idx, weight, *a, **kw: real(
            x, idx, weight.at[:, -1].set(0.0), *a, **kw))


@pytest.mark.parametrize(
    "fault", [None, _identities_left_out, _no_kv_scale, _dropped_assignment],
    ids=["as_written", "identities_left_out", "latent_not_scaled",
         "dropped_assignment"])
def test_correct_catches_planted_faults(fault, monkeypatch):
    """As written the served tokens are the reference's argmax (float32,
    deficits ~0); with the zero-compute experts adding nothing (what
    ``grouped_expert_ffn`` did with their ids before this model), the latent
    left unscaled or the router's last assignment dropped, the sample lies
    beyond the limit and ``correct`` is false."""
    if fault is not None:
        fault(monkeypatch)
    ok, facts = _serve_and_check()
    seen = facts["reference"]
    assert seen["tokens"] > 30 and len(seen["requests"]) == 4
    if fault is None:
        assert ok and facts["problems"] == []
        assert seen["max_deficit"] < 1e-3
    else:
        assert not ok
        assert seen["max_deficit"] > 1e-3
        assert any("below the reference maximum" in p
                   for p in facts["problems"])
