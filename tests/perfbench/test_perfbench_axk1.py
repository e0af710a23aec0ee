"""The A.X-K1 configuration's benchmark files: the latent kernel's required
work by hand, the new readers over a hand-written trace and counters (and
their silence on a program that writes none, as the parent commit), the
configuration's file against its cut, and the comparison that decides
``correct`` catching planted faults of the program on the CPU. (The plain
reference against the program, logit by logit: ``tests/test_axk1_model.py``.)
"""

import time

import numpy as np
import pytest

from perfbench import (checks, expert_work, harness, latent_work, loadgen,
                       program_trace)

from test_perfbench_program_trace import (dispatch_spans, observe,
                                          plant_xplane, record, summary)

CELL = "axk1-1chip.docqa-batch"
TRACE_READERS = ["kernel.mla_paged_attention.time_share",
                 "mla_paged_attention_roofline",
                 "moe.held_experts_hit_share", "moe.max_expert_load",
                 "kernel.moe_grouped_matmul.time_share",
                 "moe_grouped_matmul_roofline"]


def reader(name):
    return harness.load_module(f"perfbench/layer_metrics/{name}.py")


def test_latent_required_work_by_hand():
    config = harness.load_cell(CELL).config
    # a dispatch of 16 micro-rounds, 32 rows at ~16.5k positions: 1,031
    # pages a row a round
    rec = {"attended_pages": 528_000, "page_size": 16, "token_slots": 512,
           "causal_pairs": 8_450_000}
    w = latent_work.required_work(rec, config)
    # 7 layers x (528,000 pages x 16 tokens x 576 numbers x bf16, read once,
    # + 512 token-slots x 64 heads x (192 in + 128 out) x bf16)
    assert latent_work.entry_numbers(config) == 576
    assert w["bytes"] == 7 * (528_000 * 16 * 576 * 2 + 512 * 64 * 320 * 2) \
        == 68_271_472_640
    # the expanded form's count: 2 x pairs x 64 heads x (192 + 128), x 7
    assert w["flops"] == 7 * 2.0 * 8_450_000 * 64 * 320 == 2_422_784_000_000
    # ... which is the lesser of the two forms (absorbed: 2 x 512 + 64)
    assert 192 + 128 < 2 * config["kv_lora_rank"] + 64
    twice = latent_work.required_work(
        {k: v if k == "page_size" else 2 * v for k, v in rec.items()},
        config)
    assert twice["bytes"] == 2 * w["bytes"]             # linear
    assert twice["flops"] == 2 * w["flops"]


def latent_trace():
    """Two complete dispatches of a model with a latent kernel and experts,
    and a cut one whose ``cbe.unpack`` still carries its stats."""
    rec_a = record(7, 96, 416, 528_000, 8_450_000)
    rec_b = record(8, 32, 480, 496_000, 7_950_000)
    spans = dispatch_spans(100, rec_a) + dispatch_spans(1200, rec_b)
    spans += [["cbe.fence", 2300, 100, {}], ["cbe.unpack", 2400, 20, {}]]
    stats = [{"expert_calls": 96, "experts_hit": 850,
              "expert_assignments": 1500, "max_expert_load": 420},
             {"expert_calls": 96, "experts_hit": 800,
              "expert_assignments": 1400, "max_expert_load": 400},
             {"expert_calls": 96, "experts_hit": 750,
              "expert_assignments": 1300, "max_expert_load": 380}]
    for span, st in zip([s for s in spans if s[0] == "cbe.unpack"], stats):
        span[3] = st
    ops = [["mla_paged_attention.3", 210, 390],
           ["moe_grouped_matmul.5", 600, 390],
           ["mla_paged_attention.3", 1310, 390],
           ["moe_grouped_matmul.5", 1700, 390], ["fusion.1", 2300, 90]]
    return {"ops": ops, "spans": spans, "window": [0, 2500]}


def latent_summary(kernel_s=0.9):
    s = summary()
    s.op_seconds = {"mla_paged_attention.3": kernel_s,
                    "moe_grouped_matmul.5": 0.9, "fusion.1": 0.3}
    s.busy_s, s.dispatches = 3.0, 3.0
    return s


def test_readers_over_a_hand_trace(tmp_path, monkeypatch, capsys):
    cell = harness.load_cell(CELL)
    plant_xplane(tmp_path, cell.name)
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(program_trace, "load", lambda p: latent_trace())
    monkeypatch.setattr(program_trace, "_CACHE", {})
    monkeypatch.setattr(expert_work, "_CACHE", {})
    obs = observe(cell, latent_summary())
    got = {name: reader(name).read(obs) for name in TRACE_READERS}
    capsys.readouterr()
    assert got["kernel.mla_paged_attention.time_share"] == \
        pytest.approx(100 * 0.9 / 3.0)
    # the mean record of the two complete dispatches, memory-bound
    work = latent_work.required_work(
        {"attended_pages": 512_000, "page_size": 16, "token_slots": 512,
         "causal_pairs": 8_200_000}, cell.config)
    least = work["bytes"] / 819e9
    assert work["flops"] / 197e12 < least
    assert got["mla_paged_attention_roofline"] == \
        pytest.approx(100 * least / (0.9 / 3.0))
    # the HELD experts: 12 a call, whatever the router's width
    assert cell.config["n_routed_experts"] == 12
    assert got["moe.held_experts_hit_share"] == \
        pytest.approx(100 * 2400 / (288 * 12))
    assert got["moe.max_expert_load"] == pytest.approx(1200 / 288)
    assert got["moe_grouped_matmul_roofline"] is not None
    # the ragged kernel's readers find nothing of theirs in this trace
    for name in ("kernel.ragged_paged_attention.time_share",
                 "ragged_paged_attention_roofline"):
        assert reader(name).read(obs) is None
    bare = observe(cell, latent_summary(kernel_s=0.0))
    assert reader("mla_paged_attention_roofline").read(bare) is None
    assert reader("kernel.mla_paged_attention.time_share").read(bare) is None


def test_prefix_hit_token_share_reads_the_windows_counter():
    cell = harness.load_cell(CELL)
    obs = observe(cell, None)
    read = reader("sched.prefix_hit_token_share").read
    assert read(obs) is None                    # no counter, no requests
    obs.requests = [
        harness.RequestRecord(i, np.zeros((n,), np.int32), 8, None)
        for i, n in ((-2, 16500), (-1, 16480), (0, 16500), (1, 16420))]
    assert read(obs) is None                    # an adapter without it
    obs.counters["prefix_cache.window_cached_tokens"] = 2 * 16384
    # the window's requests alone (index >= 0): 32,768 of 32,920 tokens
    assert read(obs) == pytest.approx(100 * 32768 / 32920)


def test_new_readers_are_silent_on_a_program_without_the_kernel(
        tmp_path, monkeypatch, capsys):
    """The parent commit under these files, in a cell it can run: no
    ``mla_paged_attention`` event, no routing stats, no window counter.
    Every new reader returns None and does not raise; so it does without a
    trace at all."""
    cell = harness.load_cell("m7b-1chip.chat-batch")
    monkeypatch.setattr(program_trace, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(program_trace, "_CACHE", {})
    monkeypatch.setattr(expert_work, "_CACHE", {})
    new = ["kernel.mla_paged_attention.time_share",
           "mla_paged_attention_roofline", "moe.held_experts_hit_share",
           "sched.prefix_hit_token_share"]
    for name in new:
        assert reader(name).read(observe(cell, None)) is None
        assert reader(name).read(observe(cell, summary())) is None
    plant_xplane(tmp_path, cell.name)
    spans = dispatch_spans(100, record(7, 496, 16, 2047, 598432))
    monkeypatch.setattr(program_trace, "load", lambda p: {
        "ops": [["ragged_paged_attention.3", 210, 390]], "spans": spans,
        "window": [0, 1200]})
    for name in new:
        assert reader(name).read(observe(cell, summary())) is None
    capsys.readouterr()


def test_the_configuration_states_its_cut():
    """Every published width as published; ``reduced`` is depth, the experts
    held and the vocabulary; the router's width and the share stand beside
    the held count; the cell is under no reader that counts K and V heads
    or the router's experts."""
    config = harness.load_cell(CELL).config
    assert sorted(config["reduced"]) == ["n_routed_experts",
                                         "num_hidden_layers", "vocab_size"]
    assert [config["reduced"][k]["from"] for k in sorted(config["reduced"])] \
        == [192, 61, 163840]
    assert (config["n_routed_experts"], config["published_n_routed_experts"],
            config["first_expert"]) == (12, 192, 0)
    assert config["vocab_size"] * 8 == 163840
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    for key in ("topk_method", "rotary_pairing", "head_dim", "weights"):
        assert key in config["assumed"]
    assert "16 chips share each layer" in config["deployment"]
    assert config["correct"]["kernels"] == [
        "mla_paged_attention", "rms_norm_fwd", "moe_grouped_matmul"]
    bench = harness.read_json("BENCHMARK.json")
    for m in bench["per_layer"]:
        if "ragged_paged_attention" in m["name"] \
                or m["name"] == "moe.experts_hit_share":
            assert CELL not in m["workloads"], m["name"]
    mix = harness.load_cell(CELL).traffic
    assert mix["shared_prefix"] == {"tokens": 16384, "groups": 8,
                                    "share": 1.0}
    # the warm-up's draws cover every document, so the window only hits
    traffic = loadgen.Traffic(mix, {"clients": 32, "batch_rps": 1.0},
                              config["vocab_size"], 5, 51)
    groups = {r.prompt[:16384].tobytes() for r in traffic.warmup()}
    assert len(groups) == 8


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``, against planted faults
# ---------------------------------------------------------------------------
def _serve_and_check(seed=11):
    """The cell's own path at its rehearsal size: the adapter's server, the
    mix's warm-up and batch (shared prefixes: the batch's requests are
    prefix-cache hits), ``checks.check_serving`` with a float32 limit."""
    cell = harness.load_cell(CELL, rehearse=True)
    adapter = harness.load_module("perfbench/adapters/serve_axk1.py")
    server = adapter.Server(cell.config, 1, seed)
    # every matrix six times the program's std of 0.02: at a hidden size of
    # 64 a branch is then as large as the stream it joins, as it is at the
    # published widths, and a fault in one moves the argmax
    server.params = {
        k: v if k == "ln_f" or "norm" in k or k[2:] in ("ln_in", "ln_post")
        or k.endswith("expert_bias") else v * 6.0
        for k, v in server.params.items()}
    traffic = loadgen.Traffic(cell.traffic, cell.params, server.vocab_size,
                              seed, 1.2)
    clock = time.perf_counter
    records, _ = harness.serve_batch(
        server, traffic, harness.Spans(clock), harness.Tracer(None, 1.0),
        clock)
    counters = server.counters()
    config = dict(cell.config, correct=dict(
        cell.config["correct"], max_deficit=1e-3, mean_deficit=1e-4))
    ok, facts = checks.check_serving(server, records, config, on_chip=False)
    return ok, facts, counters, records


def _no_rope_in_the_key(monkeypatch):
    from paddle_tpu.models import axk1
    real = axk1.rope_ops.apply_rope_array
    # the shared key goes into the cache unrotated
    monkeypatch.setattr(axk1.rope_ops, "apply_rope_array",
                        lambda q, k, cos, sin: (real(q, k, cos, sin)[0], k))


def _dropped_assignment(monkeypatch):
    from paddle_tpu.models import axk1
    real = axk1.grouped_expert_ffn
    # the router's last choice is computed by nobody
    monkeypatch.setattr(
        axk1, "grouped_expert_ffn",
        lambda x, idx, weight, *a, **kw: real(
            x, idx, weight.at[:, -1].set(0.0), *a, **kw))


def _no_mscale(monkeypatch):
    from paddle_tpu.models import axk1
    monkeypatch.setattr(axk1.rope_ops, "yarn_mscale", lambda f, m=1.0: 1.0)


@pytest.mark.parametrize(
    "fault", [None, _no_rope_in_the_key, _dropped_assignment, _no_mscale],
    ids=["as_written", "key_not_roped", "dropped_assignment",
         "scale_without_mscale"])
def test_correct_catches_planted_faults(fault, monkeypatch):
    """As written the served tokens are the reference's argmax (float32,
    deficits ~0) and the window's requests are prefix-cache hits; with the
    shared key left unrotated, the router's last assignment dropped or YaRN's
    mscale left out of the softmax scale, the sample lies beyond the limit
    and ``correct`` is false."""
    if fault is not None:
        fault(monkeypatch)
    ok, facts, counters, records = _serve_and_check()
    seen = facts["reference"]
    assert seen["tokens"] > 30 and len(seen["requests"]) == 4
    window_prompts = sum(r.n_prompt for r in records if r.index >= 0)
    assert counters["prefix_cache.window_cached_tokens"] \
        >= 0.6 * window_prompts
    if fault is None:
        assert ok and facts["problems"] == []
        assert seen["max_deficit"] < 1e-3
    else:
        assert not ok
        assert seen["max_deficit"] > 1e-3
        assert any("below the reference maximum" in p
                   for p in facts["problems"])
