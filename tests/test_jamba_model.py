"""Jamba (``models.jamba``: Mamba layers with a recurrent state a row beside
multi-query attention layers' pages) on the serving path, against the plain
float32 reference (``perfbench/reference/jamba.py``: one scan over a row's
positions from a zero state, attention over the whole row, no cache): logits
through the pages and the state, the engine's slots re-used, the scan's XLA
twin and its Pallas kernel, what the engine refuses, what its memory books
and its work record hold. Small sizes, seeded, float32, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.kvcache.state import RowStatePool
from paddle_tpu.models import jamba as J
from paddle_tpu.observability.memory import memory_ledger, plan_capacity
from paddle_tpu.ops import mamba_scan as ms
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.parallel.mesh import serving_mesh
from paddle_tpu.serving import ServingScheduler
from perfbench import harness

from test_engine_phase_spans import RECORD_KEYS, _host_events

adapter = harness.load_module("perfbench/adapters/serve_jamba.py")
reference = harness.load_module("perfbench/reference/jamba.py")

STATE_KEYS = {"state_row_rounds", "state_resets", "state_bytes_per_row"}
_MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
               "rms_norm_eps", "num_hidden_layers", "attn_layer_period",
               "attn_layer_offset", "mamba_d_state", "mamba_dt_rank",
               "mamba_d_conv", "mamba_expand", "tie_word_embeddings")


def _weights(cfg, seed, boost=6.0):
    """Seeded weights with every matrix ``boost`` times the program's std of
    0.02 (``tests/test_axk1_model.py``: at a hidden size of 64 a branch is
    otherwise ~1% of the stream it joins and a fault in it moves no logit);
    the Mamba family's own initialisation (``a_log``, ``d_skip``,
    ``dt_bias``), the conv and the norms stay as drawn."""
    keep = J._NORM_KEYS + J._F32_KEYS + ("conv_w", "conv_b")
    return {k: v if k == "ln_f" or k[2:] in keep else v * boost
            for k, v in J.init_stacked_params(cfg, seed=seed).items()}


def _reference_logits(cfg, params, rows, spans):
    saved = reference.QUERY_BLOCK
    reference.QUERY_BLOCK = 16
    try:
        return [np.asarray(x) for x in reference.logits_at(
            adapter.ReferenceWeights(params, cfg.layer_kinds), rows, spans,
            {k: getattr(cfg, k) for k in _MODEL_KEYS})]
    finally:
        reference.QUERY_BLOCK = saved


# ---------------------------------------------------------------------------
# (a) the model against the plain reference, through pages and state
# ---------------------------------------------------------------------------
def _logits_through_the_cache(cfg, params, prompt, n_decode, chunk, page=4):
    """Prefill ``prompt`` in chunks of ``chunk`` tokens, then decode
    ``n_decode`` greedy tokens one at a time, all through ``ragged_step``,
    ONE row's pages and ONE row's state; returns (tokens fed, the logits
    after each call's last token from the prompt's end on). The live row is
    row 1; row 0 sits idle beside it with a state that must stay as it is."""
    width = -(-(len(prompt) + n_decode) // page)
    layout = J.cache_layout(cfg)
    pools = tuple(jnp.zeros((layout.layers, 1 + 2 * width, page) + e,
                            jnp.float32) for e in layout.entries)
    # the idle row's state is noise, and stays noise
    ssm, conv = RowStatePool(J.state_layout(cfg), 2).arrays
    rng = np.random.RandomState(5)
    state = (ssm.at[:, 0].set(rng.normal(size=ssm[:, 0].shape)),
             conv.at[:, :, 0].set(rng.normal(size=conv[:, :, 0].shape)))
    idle = [np.asarray(state[0][:, 0]), np.asarray(state[1][:, :, 0])]
    bt = np.zeros((2, width), np.int32)
    bt[1] = 1 + np.arange(width)
    step = jax.jit(lambda *a: J.ragged_step(*a, cfg))
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
        lg, *cache = step(
            params, jnp.asarray(ids), jnp.asarray(token_row),
            jnp.asarray(positions), jnp.asarray([0, at], jnp.int32),
            jnp.asarray([0, n - 1], jnp.int32), *pools, *state,
            jnp.asarray(bt))
        pools, state = tuple(cache[:2]), tuple(cache[2:])
        if at >= len(prompt):
            logits.append(np.asarray(lg[1]))
            fed.append(int(np.argmax(logits[-1])))
    np.testing.assert_array_equal(np.asarray(state[0][:, 0]), idle[0])
    np.testing.assert_array_equal(np.asarray(state[1][:, :, 0]), idle[1])
    return np.asarray(fed[:-1], np.int32), np.stack(logits)


@pytest.mark.parametrize("n_prompt", [1, 15, 16, 17, 40])
def test_prefill_then_decode_through_pages_and_state_matches_the_reference(
        n_prompt):
    """LOGITS of chunked prefill (chunk 16) then decode, through the pages
    of two attention layers and the state of five Mamba layers, against the
    reference's full forward pass: a chunk boundary falls before, on and
    after the last prompt token. Float32 on both sides; what is left is the
    order of float32 sums."""
    cfg = J.jamba_tiny()
    params = _weights(cfg, 7)
    rng = np.random.RandomState(n_prompt)
    prompt = rng.randint(1, cfg.vocab_size, (n_prompt,)).astype(np.int32)
    fed, got = _logits_through_the_cache(cfg, params, prompt, 6, 16)
    want, = _reference_logits(cfg, params, [fed],
                              [(len(prompt) - 1, len(fed))])
    assert got.shape == want.shape == (7, cfg.vocab_size)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_a_state_that_is_lost_or_rounded_shows_in_the_logits():
    """The seeded Mamba initialisation makes the state carry: decoding from
    a zeroed state moves the logits by far more than the agreement above,
    and one rounded to bfloat16 once, measurably."""
    cfg = J.jamba_tiny()
    params = _weights(cfg, 7)
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, cfg.vocab_size, (40,)).astype(np.int32)
    layout = J.cache_layout(cfg)
    page, width = 4, 11
    bt = jnp.asarray(1 + np.arange(width)[None], jnp.int32)

    def run(spoil):
        pools = tuple(jnp.zeros((layout.layers, 1 + width, page) + e,
                                jnp.float32) for e in layout.entries)
        state = RowStatePool(J.state_layout(cfg), 1).arrays
        n = len(prompt)
        _, *cache = J.ragged_step(
            params, jnp.asarray(prompt), jnp.zeros((n,), jnp.int32),
            jnp.arange(n, dtype=jnp.int32), jnp.asarray([n], jnp.int32),
            jnp.asarray([n - 1], jnp.int32), *pools, *state, bt, cfg)
        cache[2] = spoil(cache[2])
        lg, *_ = J.ragged_step(
            params, jnp.asarray([7], jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.asarray([n], jnp.int32), jnp.asarray([n + 1], jnp.int32),
            jnp.asarray([0], jnp.int32), *cache, bt, cfg)
        return np.asarray(lg[0])

    kept = run(lambda s: s)
    assert np.abs(kept - run(jnp.zeros_like)).max() > 0.05
    # ONE rounding of the state to bfloat16 is already visible (a state
    # HELD in bfloat16 rounds at every token)
    assert np.abs(kept - run(
        lambda s: s.astype(jnp.bfloat16).astype(s.dtype))).max() > 5e-5


# ---------------------------------------------------------------------------
# (b) the engine: slots re-used, rows of different ages side by side
# ---------------------------------------------------------------------------
def _engine(cfg, slots, **kw):
    return ContinuousBatchingEngine(
        cfg, GenerationConfig(seed=0, max_new_tokens=10), num_slots=slots,
        page_size=4, max_seq_len=96, chunk=4, **kw)


def _deficits(cfg, params, prompt, tokens):
    """How far each served token's reference logit lies under the
    reference's maximum, teacher-forced (0: the reference's own argmax)."""
    row = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    lg, = _reference_logits(cfg, params, [row],
                            [(len(prompt) - 1, len(row) - 1)])
    return lg.max(-1) - lg[np.arange(len(tokens)), tokens]


def test_six_requests_through_two_slots_each_start_from_a_zero_state():
    """A request admitted into a RE-USED slot gives what a fresh engine
    gives it (the state's reset, inside the step), rows of different ages
    share every micro-round without reading each other's state, and every
    served token is the float32 reference's argmax."""
    cfg = J.jamba_tiny()
    params = _weights(cfg, 2)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (1, 15, 16, 17, 40, 9)]
    eng = _engine(cfg, 2)
    served = eng.serve(params, prompts)
    assert all(len(out) == 10 for out in served)
    for prompt, out in zip(prompts, served):
        alone = _engine(cfg, 1).serve(params, [prompt])[0]
        assert out == alone
        assert _deficits(cfg, params, prompt, out).max() == 0.0
    eng.mgr.check_conservation()
    assert eng.mgr.state.snapshot()["rows_held"] == 0


# ---------------------------------------------------------------------------
# (c) the scan: its XLA twin against a per-row scan, the kernel against it
# ---------------------------------------------------------------------------
def _packed_mix(spans, t, n_rows, seed, d_inner=256, n=8, layers=3):
    """``spans``: (row, tokens, first position) in packed order."""
    rng = np.random.RandomState(seed)
    token_row = np.full(t, -1, np.int32)
    positions = np.zeros(t, np.int32)
    at = 0
    for row, count, first in spans:
        token_row[at:at + count] = row
        positions[at:at + count] = first + np.arange(count)
        at += count
    f32 = np.float32
    return dict(
        u=rng.normal(size=(t, d_inner)).astype(f32),
        delta=(np.log1p(np.exp(rng.normal(size=(t, d_inner)))) * 0.1
               ).astype(f32),
        b=rng.normal(size=(t, n)).astype(f32),
        c=rng.normal(size=(t, n)).astype(f32),
        a=-np.exp(rng.normal(size=(n, d_inner))).astype(f32),
        d=rng.normal(size=(d_inner,)).astype(f32),
        state=rng.normal(size=(layers, n_rows, n, d_inner)).astype(f32),
        token_row=token_row, positions=positions)


def _per_row_scan(m, spans, layer):
    """Each row alone: a ``lax.scan`` over ITS tokens from ITS state."""
    y = np.zeros_like(m["u"])
    state = m["state"].copy()
    at = 0
    for row, count, first in spans:
        sl = slice(at, at + count)

        def step(s, xs):
            u, dt, b, c = xs
            s = jnp.exp(dt[None, :] * m["a"]) * s + b[:, None] * (dt * u)[None]
            return s, jnp.sum(s * c[:, None], axis=0) + m["d"] * u

        s0 = (jnp.zeros_like(state[layer, row]) if first == 0
              else state[layer, row])
        s, ys = jax.lax.scan(step, s0, (m["u"][sl], m["delta"][sl],
                                        m["b"][sl], m["c"][sl]))
        y[sl], state[layer, row] = np.asarray(ys), np.asarray(s)
        at += count
    return y, state


_MIXES = {
    # rows of 0 tokens (0, 3), 1 token (decoding: 1, 4), a chunk from
    # position 0 (2: starts from zeros) and a chunk that continues (5)
    "mixed": ([(1, 1, 9), (2, 5, 0), (4, 1, 30), (5, 7, 3)], 16, 6),
    "decode_only": ([(r, 1, 5 + r) for r in range(6)], 8, 6),
    "one_full_row": ([(3, 16, 0)], 16, 4),
    "empty": ([], 8, 4),
    "last_rows_only": ([(6, 2, 0), (7, 1, 11)], 8, 8),
}


@pytest.mark.parametrize("name", sorted(_MIXES))
def test_scan_twin_matches_a_per_row_scan_and_the_kernel_matches_the_twin(
        name):
    spans, t, n_rows = _MIXES[name]
    m = _packed_mix(spans, t, n_rows, seed=len(name))
    layer = 1
    plan = ms.scan_plan(jnp.asarray(m["token_row"]),
                        jnp.asarray(m["positions"]), n_rows)
    args = (m["u"], m["delta"], m["b"], m["c"], m["a"], m["d"],
            jnp.asarray(m["state"]), layer, jnp.asarray(m["token_row"]), plan)
    want_y, want_state = _per_row_scan(m, spans, layer)
    y, state = ms.mamba_ragged_scan_array(*args)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), want_state, rtol=0,
                               atol=2e-5)
    # rows without work and the other layers: bit for bit what they were
    idle = sorted(set(range(n_rows)) - {r for r, _, _ in spans})
    np.testing.assert_array_equal(np.asarray(state)[layer, idle],
                                  m["state"][layer, idle])
    np.testing.assert_array_equal(np.asarray(state)[[0, 2]],
                                  m["state"][[0, 2]])
    for block_d in (0, 128):
        ky, kstate = ms.mamba_ragged_scan_pallas(
            *args, block_d=block_d, interpret=True)
        np.testing.assert_allclose(np.asarray(ky), np.asarray(y), rtol=0,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(kstate), np.asarray(state),
                                   rtol=0, atol=1e-6)
        assert not np.asarray(ky)[m["token_row"] < 0].any()   # pad slots: 0


def test_multi_query_pools_without_a_head_axis_match_a_head_axis_of_one():
    """The ragged kernel over K and V pools WITHOUT a head axis (the latent
    kernel's row-local walk, V a pool of its own), in interpret mode, against
    the XLA reference over the same pools with a head axis of one: rows of
    several blocks, a decode row, a starved row, pad slots."""
    rng = np.random.RandomState(4)
    page, width, n_rows, t, heads, d = 16, 20, 4, 24, 5, 128
    pool = 1 + n_rows * width
    k = rng.normal(size=(pool, page, d)).astype(np.float32)
    v = rng.normal(size=(pool, page, d)).astype(np.float32)
    bt = (1 + np.arange(n_rows * width, dtype=np.int32)).reshape(n_rows, width)
    token_row = np.full(t, -1, np.int32)
    positions = np.zeros(t, np.int32)
    kv_lens = np.zeros(n_rows, np.int32)
    at = 0
    for row, count, first in ((0, 1, 300), (2, 9, 140), (3, 6, 0)):
        token_row[at:at + count] = row
        positions[at:at + count] = first + np.arange(count)
        kv_lens[row] = first + count
        at += count
    q = rng.normal(size=(t, heads, d)).astype(np.float32)
    args = (jnp.asarray(bt), jnp.asarray(token_row), jnp.asarray(positions),
            jnp.asarray(kv_lens))
    want = pa.ragged_paged_attention_array(
        q, k[:, :, None], v[:, :, None], *args, scale=d ** -0.5)
    got = pa.mla_paged_attention_pallas(
        q, k, *args, scale=d ** -0.5, v_pool=v,
        name="ragged_paged_attention", interpret=True)
    live = token_row >= 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=0, atol=2e-5)
    assert not np.asarray(got)[~live].any()
    # and the dispatcher takes such pools (the XLA twin on the CPU)
    np.testing.assert_allclose(
        np.asarray(pa.ragged_paged_attention(q, k, v, *args,
                                             scale=d ** -0.5))[live],
        np.asarray(want)[live], rtol=0, atol=2e-5)


# ---------------------------------------------------------------------------
# (d) what the engine refuses for a model with a state a row
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("option", ["prefix_cache", "speculative"])
def test_engine_refuses_what_needs_state_snapshots(option):
    with pytest.raises(ValueError, match=f"{option}=True needs state "
                                         "snapshots"):
        _engine(J.jamba_tiny(), 2, **{option: True})


def test_engine_refuses_a_mesh_of_degree_two():
    with pytest.raises(ValueError, match="replicates every weight"):
        _engine(J.jamba_tiny(), 2, mesh=serving_mesh(2, jax.devices()[:2]))
    # a degree-1 mesh pins the device and serves
    eng = _engine(J.jamba_tiny(), 2, mesh=serving_mesh(1, jax.devices()[:1]))
    assert eng.num_chips == 1


def test_config_refuses_what_the_module_cannot_serve():
    with pytest.raises(ValueError, match="num_key_value_heads=2"):
        J.jamba_tiny(num_key_value_heads=2)
    with pytest.raises(ValueError, match="num_experts=16"):
        J.jamba_tiny(num_experts=16)


# ---------------------------------------------------------------------------
# (e) the books: pool built for the attention layers, the state counted
# ---------------------------------------------------------------------------
def test_published_sizes_count_what_the_configuration_says():
    """3,028M parameters, 9.32 MB of state a row, 1 KiB of pages a token at
    the published widths (shapes only: nothing is allocated)."""
    cfg = J.JambaConfig(dtype=jnp.bfloat16)
    assert cfg.layer_kinds.count("attention") == 2
    assert [i for i, k in enumerate(cfg.layer_kinds)
            if k == "attention"] == [7, 21]
    # 26 x 104,161,472 + 2 x 76,682,240 + the tied embedding 167,772,160
    # + the final norm
    assert J.param_count(cfg) == 3_029_337_472
    assert J.param_nbytes(cfg) == 2 * 3_029_337_472 + 2 * 26 * 18 * 5120
    layout = J.state_layout(cfg)
    assert layout.row_layer_nbytes == 327_680 + 30_720
    assert layout.row_nbytes == 26 * 358_400
    assert 128 * layout.row_nbytes == 1_192_755_200
    pages = J.cache_layout(cfg)
    assert pages.layers * pages.token_elems * 2 == 1024


def test_pool_state_conservation_and_the_memory_planner_count_the_state():
    cfg = J.jamba_tiny()
    params = _weights(cfg, 2)
    eng = _engine(cfg, 3)
    mgr, state = eng.mgr, eng.mgr.state
    # pages for the 2 attention layers of 7, K and V without a head axis
    assert [p.shape for p in mgr.pools] == [
        (2, mgr.num_pages, 4, cfg.head_dim)] * 2
    assert [a.shape for a in state.arrays] == [
        (5, 3, cfg.mamba_d_state, cfg.d_inner), (5, 3, 3, cfg.d_inner)]
    row = 5 * (cfg.mamba_d_state * cfg.d_inner * 4 + 3 * cfg.d_inner * 4)
    assert state.layout.row_nbytes == row and state.nbytes == 3 * row
    assert sum(int(a.nbytes) for a in state.arrays) == state.nbytes
    assert mgr.arrays == mgr.pools + state.arrays
    memory_ledger.reset()
    memory_ledger.arm()
    try:
        rng = np.random.RandomState(1)
        for n in (5, 9):
            eng.submit(rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32))
        eng.step(params)
        mgr.check_conservation()
        assert state.snapshot()["rows_held"] == 2
        # (an engine without per-step invariant checks feeds the ledger
        # every 16th step: one accounting round by hand)
        memory_ledger.observe(mgr)
        snap = memory_ledger.snapshot()
        assert snap["classes"]["row_state"] == state.nbytes
        pool, = snap["pools"]
        assert pool["state_bytes"] == state.nbytes
        assert pool["planner"]["exact"]
        # a planner given the chip and the state leaves the pages less room
        kw = dict(num_layers=2, page_size=4, token_elems=2 * cfg.head_dim,
                  dtype_bytes=4, hbm_bytes=1 << 20)
        assert plan_capacity(**kw).kv_budget_bytes \
            - plan_capacity(**kw, state_bytes=state.nbytes).kv_budget_bytes \
            == state.nbytes
        # an owner the page manager does not know, or one that holds two rows
        eng._slot_rid[2] = 99
        with pytest.raises(RuntimeError, match="without pages"):
            mgr.check_conservation()
        eng._slot_rid[2] = eng._slot_rid[0]
        with pytest.raises(RuntimeError, match="owns two rows"):
            mgr.check_conservation()
        eng._slot_rid[2] = None
        # arrays that are not the layout's size
        state.arrays = (state.arrays[0][:, :2], state.arrays[1])
        with pytest.raises(RuntimeError, match="state conservation"):
            mgr.check_conservation()
    finally:
        memory_ledger.disarm()
        memory_ledger.reset()


# ---------------------------------------------------------------------------
# (f) the work record of a Jamba dispatch
# ---------------------------------------------------------------------------
def test_dispatch_record_gains_the_states_three_keys(tmp_path):
    cfg = J.jamba_tiny()
    params = _weights(cfg, 2)
    eng = _engine(cfg, 3)
    sched = ServingScheduler(eng)
    rng = np.random.RandomState(2)

    def serve(lengths):
        for n in lengths:
            sched.submit(rng.randint(1, cfg.vocab_size, (n,)
                                     ).astype(np.int32), max_new_tokens=6)
        while sched.pending:
            sched.step(params)

    serve([3])                                      # compile outside the trace
    plans = []
    plain = eng._plan_step

    def spy():
        out = plain()
        plans.append((out[0][2].copy(), out[0][3].copy()))
        return out
    eng._plan_step = spy
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        serve([5, 9, 13, 4])
    finally:
        jax.profiler.stop_trace()
    records = [e[3] for e in _host_events(str(tmp_path))
               if e[0] == "cbe.dispatch"]
    assert len(records) == len(plans) > 0
    # pools without a head axis: the latent kernel's walk, which counts the
    # pages folded under another row's item (none: no prefix cache here)
    assert all(set(r) == RECORD_KEYS | STATE_KEYS | {"shared_pages"}
               and r["shared_pages"] == 0 for r in records)
    assert all(isinstance(v, int) for r in records for v in r.values())
    layout = J.state_layout(cfg)
    for rec, (token_row, positions) in zip(records, plans):
        assert rec["state_bytes_per_row"] == layout.row_layer_nbytes
        assert rec["state_row_rounds"] == sum(
            len(set(token_row[k][token_row[k] >= 0]))
            for k in range(eng.chunk))
        assert rec["state_resets"] == int(
            ((positions == 0) & (token_row >= 0)).sum())
        assert rec["state_row_rounds"] <= rec["rounds"] * rec["live_rows"]
    # every request started from zeros exactly once
    assert sum(r["state_resets"] for r in records) == 4
