"""Continuous batching serving loop (VERDICT r4 item 4): fixed decode
slots, page free on per-sequence EOS, admission of queued prompts into
freed slots mid-service. Reference surface: the AnalysisPredictor serving
engine (paddle/fluid/inference/api/analysis_predictor.cc:§0)."""

import numpy as np
import pytest

from paddle_tpu.models import llama as L
from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)

from _oracle import greedy_reforward as _greedy_ref


def _setup(max_new=6, num_slots=2, eos=None, seed=3):
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=seed)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=max_new, eos_token_id=eos),
        num_slots=num_slots, page_size=4, max_seq_len=32, chunk=3)
    return cfg, params, eng


def test_streams_3x_slots_with_correct_outputs():
    """3x num_slots ragged requests stream through 2 fixed slots; every
    output equals the full-reforward greedy oracle for that prompt."""
    cfg, params, eng = _setup(max_new=6, num_slots=2)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 3, 7, 4, 6, 2)]          # 3x the slot count
    free0 = eng.mgr.num_free_pages

    outs = eng.serve(params, prompts)

    assert len(outs) == len(prompts)
    for p, got in zip(prompts, outs):
        ref = _greedy_ref(params, cfg, p, 6)
        assert got == ref, (p.tolist(), got, ref)
    # every page returned to the pool after the last completion
    assert eng.mgr.num_free_pages == free0
    assert all(r is None for r in eng._slot_rid)


def test_eos_frees_slot_early_and_admits_next():
    """A request that hits EOS mid-chunk retires early (pages freed) and a
    queued request takes its slot."""
    cfg, params, _ = _setup()
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, cfg.vocab_size, (5,)).astype(np.int32)
    ref = _greedy_ref(params, cfg, prompt, 6)
    eos = ref[2]  # third generated token acts as EOS

    cfg2, params2, eng = _setup(max_new=6, num_slots=1, eos=eos, seed=3)
    prompts = [prompt,
               rng.randint(1, cfg.vocab_size, (4,)).astype(np.int32)]
    outs = eng.serve(params, prompts)
    # first request stopped AT the EOS token
    assert outs[0] == ref[:3]
    # second request ran to its full budget in the freed slot
    assert len(outs[1]) == 6
    assert outs[1] == _greedy_ref(params, cfg, prompts[1], 6)
    assert eng.mgr.num_free_pages == eng.num_slots * eng._table_width


def test_service_api_submit_step_collect():
    """Predictor-style service surface: submit returns rids, step makes
    progress, collect drains in any order."""
    cfg, params, eng = _setup(max_new=4, num_slots=2)
    rng = np.random.RandomState(2)
    r1 = eng.submit(rng.randint(1, cfg.vocab_size, (3,)))
    r2 = eng.submit(rng.randint(1, cfg.vocab_size, (5,)))
    assert (r1, r2) == (0, 1)
    seen = {}
    for _ in range(10):
        live = eng.step(params)
        seen.update(eng.collect())
        if not live and not eng._queue:
            break
    assert set(seen) == {r1, r2}
    assert all(len(v) == 4 for v in seen.values())


def test_pool_exhaustion_defers_admission():
    """When the pool can't hold another sequence, admission waits instead
    of failing; the request completes after a slot frees."""
    cfg = L.llama_tiny(num_hidden_layers=2)
    params = L.init_stacked_params(cfg, seed=3)
    # pool of exactly one sequence's worth of pages (+ reserved page 0)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=4),
        num_slots=2, page_size=4, max_seq_len=16,
        num_pages=1 + (16 // 4), chunk=2)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, (6,)).astype(np.int32)
               for _ in range(2)]
    outs = eng.serve(params, prompts)
    for p, got in zip(prompts, outs):
        assert got == _greedy_ref(params, cfg, p, 4)


def test_a8w8_flag_flip_retraces_unified_step():
    """ISSUE 8 regression (tpu-lint trace-host-state): llama._mm_prefill
    reads FLAGS_serving_a8w8_prefill at TRACE time, so the engine's
    unified-step cache keys on it — a set_flags flip must produce a
    fresh program and a counted recompile, not silently keep serving
    the stale one (which the runtime RecompileDetector cannot see)."""
    import paddle_tpu as paddle
    from paddle_tpu.observability.runtime import recompiles

    cfg, params, eng = _setup(max_new=3, num_slots=2)
    rng = np.random.RandomState(5)
    p = rng.randint(1, cfg.vocab_size, (4,)).astype(np.int32)
    out1 = eng.serve(params, [p])
    prog1 = eng._unified_step
    before = recompiles.count("cbe.unified_step")
    paddle.set_flags({"FLAGS_serving_a8w8_prefill": 0})
    try:
        out2 = eng.serve(params, [p])
        assert eng._unified_step is not prog1, (
            "flag flip must rebuild the unified program")
        assert recompiles.count("cbe.unified_step") == before + 1, (
            "the rebuild must be a COUNTED recompile")
    finally:
        paddle.set_flags({"FLAGS_serving_a8w8_prefill": 1})
    # dense (unquantized) params: the flag selects the same math path,
    # so outputs stay byte-identical across the retrace
    assert out1 == out2
