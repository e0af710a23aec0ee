"""Multi-chip TP-sharded serving (ISSUE 14 tentpole): the unified
continuous-batching engine over an ``mp`` mesh.

The acceptance bar: sharding is a LAYOUT problem — Megatron-placed
weights (``models.llama.shard_params_tp``) + a head-sharded paged KV
pool (``PagedKVCacheManager(mesh=...)``, whole GQA groups per chip) —
so the sharded engine's greedy output is byte-identical to the
single-chip engine at mp=2 and mp=4 (prefix cache on/off, COW wave,
speculation on/off) and the O(1)-recompile contract survives a sharded
length-diverse storm unchanged. All on the 8-virtual-device CPU mesh
(conftest), the same substrate MULTICHIP_r05 validated training on."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                           GenerationConfig)
from paddle_tpu.models import llama as L
from paddle_tpu.observability.runtime import recompiles
from paddle_tpu.parallel.mesh import (serving_mesh, shrink_serving_mesh,
                                      surviving_mp_degree)

CFG = L.llama_tiny(num_hidden_layers=2)
PARAMS = L.init_stacked_params(CFG, seed=3)


def _engine(mp, max_new=6, num_slots=2, prefix_cache=False,
            speculative=False, **kw):
    mesh = serving_mesh(mp) if mp > 1 else None
    return ContinuousBatchingEngine(
        CFG, GenerationConfig(max_new_tokens=max_new, seed=3),
        num_slots=num_slots, page_size=4, max_seq_len=64, chunk=2,
        prefix_cache=prefix_cache, speculative=speculative, mesh=mesh,
        **kw)


def _prompts(n, lens, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, CFG.vocab_size,
                        (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


# ---------------------------------------------------------------------------
# byte-identical greedy output across TP degrees
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefix_cache", [False, True])
@pytest.mark.parametrize("speculative", [False, True])
def test_byte_identity_across_mp_degrees(prefix_cache, speculative):
    """Single-chip vs mp=2 vs mp=4 sharded engines emit byte-identical
    greedy tokens over a ragged mix — with the prefix cache the SECOND
    serve is the warm pass (full-prompt hits go copy-on-write), so the
    COW wave is byte-checked across degrees too."""
    prompts = _prompts(5, (5, 9, 3, 12, 7))
    outs, warm = [], []
    for mp in (1, 2, 4):
        eng = _engine(mp, prefix_cache=prefix_cache,
                      speculative=speculative)
        outs.append(eng.serve(PARAMS, prompts))
        if prefix_cache:
            warm.append(eng.serve(PARAMS, prompts))   # warm + COW wave
        assert eng.num_chips == mp
    assert outs[0] == outs[1] == outs[2]
    if prefix_cache:
        assert warm[0] == warm[1] == warm[2]
        # the warm pass reuses cached prefixes yet answers identically
        assert warm[0] == outs[0]


def test_sharded_storm_o1_recompiles_and_program_identity():
    """The sharded engine keeps the unified step's compile contract: a
    length-diverse storm with mid-decode admissions misses the compile
    cache at most twice (one compile + one optional remat), and every
    round reuses ONE program object — sharding changed array layouts,
    never the program count."""
    eng = _engine(2, max_new=4, num_slots=4)
    prompts = _prompts(12, (2, 3, 5, 7, 9, 12, 17, 23, 31, 44))
    u0 = recompiles.count("cbe.unified_step")
    rids = [eng.submit(p) for p in prompts[:6]]
    results = {}
    step = 0
    prog = None
    while len(results) < len(prompts):
        eng.step(PARAMS)
        if prog is None:
            prog = eng._unified_step
        assert eng._unified_step is prog        # one program object ever
        results.update(eng.collect())
        step += 1
        if step == 2:                           # mid-decode trickle
            rids += [eng.submit(p) for p in prompts[6:]]
        assert step < 500
    assert recompiles.count("cbe.unified_step") - u0 <= 2
    # ...and the storm's output matches the single-chip engine's
    single = _engine(1, max_new=4, num_slots=4)
    assert single.serve(PARAMS, prompts) == [results[r] for r in rids]


# ---------------------------------------------------------------------------
# placement + mesh helpers
# ---------------------------------------------------------------------------

def test_shard_params_tp_placements():
    """Weights land with the serving TP specs: column-parallel QKV/gate/
    up (heads over mp), row-parallel wo/down, replicated embed/lm_head/
    norms; weight-only-quantized leaves shard q like the dense weight
    and the (L, out) scale along out for column-parallel weights."""
    from paddle_tpu.quantization import quantize_stacked_params
    mesh = serving_mesh(4)
    placed = L.shard_params_tp(PARAMS, mesh, CFG)

    def n_shards(x):
        return len({str(s.index) for s in x.addressable_shards})

    assert n_shards(placed["wq"]) == 4
    assert n_shards(placed["wo"]) == 4
    assert n_shards(placed["embed"]) == 1       # replicated
    assert n_shards(placed["lm_head"]) == 1
    # sharded axis: wq splits its OUT dim, wo its IN dim
    assert placed["wq"].addressable_shards[0].data.shape[2] \
        == PARAMS["wq"].shape[2] // 4
    assert placed["wo"].addressable_shards[0].data.shape[1] \
        == PARAMS["wo"].shape[1] // 4
    qp = quantize_stacked_params(PARAMS, keys=("wq", "wo"))
    placed_q = L.shard_params_tp(qp, mesh, CFG)
    assert n_shards(placed_q["wq"]["q"]) == 4
    assert placed_q["wq"]["scale"].addressable_shards[0].data.shape[1] \
        == qp["wq"]["scale"].shape[1] // 4      # col-parallel scale
    assert n_shards(placed_q["wo"]["scale"]) == 1   # row-parallel scale


def test_pool_head_sharding_and_validation():
    """The paged pool head-shards over mp (whole GQA groups per chip);
    invalid degrees fail loudly at construction, never silently serve a
    torn layout."""
    eng = _engine(2)
    assert eng.mgr.mesh_chips == 2
    kv_shard = eng.mgr.k_pages.addressable_shards[0].data
    assert kv_shard.shape[3] == CFG.num_key_value_heads // 2
    # degree must divide the head counts (nkv=4: 3 chips is invalid)
    with pytest.raises(ValueError, match="divide"):
        ContinuousBatchingEngine(
            CFG, GenerationConfig(max_new_tokens=4), num_slots=2,
            page_size=4, max_seq_len=32,
            mesh=serving_mesh(3))


def test_mesh_resize_helpers():
    """Surviving-degree math: the resize picks the largest TP degree
    that divides the kv-head count AND fits the surviving chips."""
    assert surviving_mp_degree(4, 4) == 4
    assert surviving_mp_degree(3, 4) == 2       # 3 doesn't divide 4 heads
    assert surviving_mp_degree(2, 4) == 2
    assert surviving_mp_degree(1, 4) == 1
    assert surviving_mp_degree(5, 6) == 3       # gqa: 6 kv heads, 5 chips
    m4 = serving_mesh(4)
    m2 = shrink_serving_mesh(m4, 1, 4)
    assert m2.shape["mp"] == 2
    dead = m4.devices.reshape(-1).tolist()[1]
    assert dead not in m2.devices.reshape(-1).tolist()
    with pytest.raises(ValueError):
        serving_mesh(0)
    # an out-of-range dead-chip index must raise, never silently keep
    # the dead chip and report a "completed" resize
    with pytest.raises(ValueError, match="outside"):
        shrink_serving_mesh(m4, 4, 4)


@pytest.mark.parametrize("page,width,window", [
    (4, 4, None),           # the table is one block
    (16, 20, None),         # G = 8: rows of 1, 9 and 13 pages
    (16, 20, 100),          # and a window whose first page is mid-block
], ids=["one_block_table", "several_blocks", "several_blocks_windowed"])
def test_sharded_pallas_wrapper_interpret_parity(page, width, window):
    """The TPU path's shard_map wrapper around the Pallas ragged kernel
    (per-chip GQA slices, replicated metadata, the window one more
    replicated scalar, the step's row groups computed outside it as the
    models do) matches the XLA reference elementwise — run in Pallas
    interpret mode on the CPU mesh, rows of one block and of several, two
    of them on the same leading pages (a prefix hit)."""
    from paddle_tpu.ops import paged_attention as pa
    rng = np.random.RandomState(0)
    n_rows, nkv, nh, d, T = 3, 4, 4, 8, 10
    pool = n_rows * width + 1
    kp = jnp.asarray(rng.randn(pool, page, nkv, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(pool, page, nkv, d).astype(np.float32))
    q = jnp.asarray(rng.randn(T, nh, d).astype(np.float32))
    bt = np.zeros((n_rows, width), np.int32)
    for r in range(n_rows):
        bt[r] = 1 + r * width + np.arange(width)
    # row 2's table borrows row 1's first pages
    bt[2, :min(width, 8)] = bt[1, :min(width, 8)]
    token_row = np.array([0, 0, 0, 1, 1, 2, -1, -1, -1, -1], np.int32)
    if page == 4:
        positions = np.array([0, 1, 2, 5, 6, 3, 0, 0, 0, 0], np.int32)
        kv_lens = np.array([3, 7, 4], np.int32)
    else:
        positions = np.array([0, 1, 2, 138, 139, 199, 0, 0, 0, 0], np.int32)
        kv_lens = np.array([3, 140, 200], np.int32)
    ref = pa.ragged_paged_attention_array(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(token_row),
        jnp.asarray(positions), jnp.asarray(kv_lens), window=window)
    groups = pa.ragged_row_groups(
        jnp.asarray(bt), jnp.asarray(token_row), jnp.asarray(positions),
        jnp.asarray(kv_lens), page)
    assert int(np.asarray(groups.run).sum()) > 0
    got = pa._ragged_paged_attention_shard_mapped(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(token_row),
        jnp.asarray(positions), jnp.asarray(kv_lens), None,
        serving_mesh(2), "mp", interpret=True, window=window, groups=groups)
    real = np.asarray(token_row) >= 0
    np.testing.assert_allclose(np.asarray(got)[real],
                               np.asarray(ref)[real], rtol=2e-5,
                               atol=2e-5)


def test_memory_ledger_per_chip_split():
    """The HBM ledger's pool books carry the TP degree: a head-sharded
    pool reports per-chip bytes = class bytes / chips (the capacity
    answer an elastic resize changes)."""
    from paddle_tpu.observability.memory import memory_ledger
    memory_ledger.reset()
    memory_ledger.arm()
    try:
        eng = _engine(2, prefix_cache=True)
        eng.serve(PARAMS, _prompts(3, (5, 9, 3)))
        snap = memory_ledger.snapshot()
        pool = next(p for p in snap["pools"]
                    if p["num_pages"] == eng.mgr.num_pages)
        assert pool["chips"] == 2
        for cls, b in pool["bytes"].items():
            assert pool["bytes_per_chip"][cls] == b // 2
        assert sum(pool["bytes"].values()) == \
            pool["usable_pages"] * pool["page_bytes"]
    finally:
        memory_ledger.disarm()
        memory_ledger.reset()
