#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths ONCE through the entry points a user calls, at the
full width of Llama-2-7B (h=4096, 32x128 heads, ff=11008, bf16), on the TPU
this process finds, and checks what comes out by the repo's own means:

  serve   ContinuousBatchingEngine (16 slots x 2048 tokens, page 16, prefix
          cache) behind ServingScheduler, default flags, 10 seeded requests
          (32..1000-token prompts, 32 new tokens each; one sampled, two
          sharing a 256-token prefix). Vocab 32000 untouched; DEPTH CUT
          32 -> 8 so weights (3.76 GB) + pool (4.30 GB) fit one 16 GB chip.
  parity  the Pallas kernels the two phases run — ragged paged attention,
          rms_norm fwd+bwd, flash attention fwd+bwd — against their XLA
          references, on the chip, at the phases' shapes.
  train   bench.py's llama7b_layer geometry (L=4, vocab 8192, B=8, S=2048,
          default remat) through build_hybrid_train_step: loss finite +
          falling, one call site of each flash kernel (no replayed forward).
  fence   one steady train step timed to jax.block_until_ready and to a
          float(loss) read — the two must agree.
  4chips  only when the process holds >= 4 chips: the serve phase again on
          serving_mesh(4) (the same 8-layer model, greedy streams compared
          with the one-chip run; then FULL-DEPTH Llama-2-7B), and one hybrid
          train step on mp2 x sharding2.

One process holds the chip from start to end; each phase releases its arrays
before the next (serve and train do not fit together). Weights are random
from a seed; nothing is read from the network. Every number printed is a
set-up fact of this run (did it compile, did it run, how long did start-up
take) — not a benchmark result.

Without a TPU this refuses to run: non-zero exit, no result line. The last
line of stdout on success is
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Run: python chip_smoke.py        (from the root of a checkout or an export)
"""

import contextlib
import dataclasses
import gc
import json
import os
import statistics
import sys
import time
import traceback
from typing import Any, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SEED = 0
#: tolerance of every bf16 kernel-vs-reference comparison, fixed beforehand
#: from the dtype: max|kernel - ref| <= 4 * eps(bf16) * max|ref|
BF16_TOL = 4 * 2.0 ** -8


class SmokeFailure(Exception):
    """A phase's check did not hold."""


class PhaseFailed(Exception):
    """Whatever stopped phase ``name`` (a failed check or any error)."""

    def __init__(self, name: str, cause: BaseException):
        super().__init__(f"{name}: {type(cause).__name__}: {cause}")
        self.name = name


@contextlib.contextmanager
def phase(name: str):
    try:
        yield
    except Exception as e:      # noqa: BLE001 - re-raised with the phase
        raise PhaseFailed(name, e) from e


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that sets a shape. ``full()`` is what the chip runs;
    tests/test_chip_smoke.py rehearses the control flow at a toy size on
    the CPU (which proves nothing about the chip)."""
    serve_cfg: Any
    slots: int
    page: int
    max_seq: int
    prompt_lens: Tuple[int, ...]    # wave 1; [shared_at] carries the prefix
    shared_at: int
    shared_prefix: int
    warm_len: int                   # wave 2: prefix + fresh tail
    new_tokens: int
    full_depth: int                 # four-chip second serve
    train_cfg: Any
    batch: int
    seq: int
    parity_flash_bh: int
    fence_tol: float                # |block_until_ready - read| / read

    @staticmethod
    def full() -> "Sizes":
        import jax.numpy as jnp
        from paddle_tpu.models import llama as L
        return Sizes(
            serve_cfg=L.llama2_7b(num_hidden_layers=8, dtype=jnp.bfloat16),
            slots=16, page=16, max_seq=2048,
            prompt_lens=(32, 64, 128, 200, 320, 450, 640, 800, 1000),
            shared_at=4, shared_prefix=256, warm_len=356, new_tokens=32,
            full_depth=32,
            train_cfg=L.llama2_7b(vocab_size=8192, num_hidden_layers=4,
                                  max_position_embeddings=2048,
                                  dtype=jnp.bfloat16),
            batch=8, seq=2048, parity_flash_bh=64, fence_tol=0.1)


def bytes_in_use(chips: int) -> list:
    """Bytes each of the first ``chips`` devices holds now; on a mesh they
    must be each chip's share, not everything on device 0."""
    import jax
    per_chip = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                for d in jax.devices()[:chips]]
    if chips > 1 and min(per_chip) > 0:
        check(max(per_chip) < 1.25 * min(per_chip),
              f"bytes in use are not spread over the mesh: {per_chip}")
    return per_chip


def release() -> int:
    """Collect the previous phase's garbage (engine <-> scheduler callbacks
    are a reference cycle) and return the bytes device 0 still holds."""
    gc.collect()
    return bytes_in_use(1)[0]


def require_kernels(lowered, names, where: str, sites=None) -> dict:
    """No kernel gave way to its reference: every named Pallas kernel is a
    Mosaic custom call in the lowering; ``sites`` holds kernels to an exact
    number of call sites."""
    from paddle_tpu.ops._common import mosaic_kernels
    found = mosaic_kernels(lowered)
    missing = sorted(set(names) - set(found))
    check(not missing, f"{where}: Pallas kernels {missing} are not in the "
                       f"lowered program (found {found})")
    wrong = {k: found.get(k, 0) for k, n in (sites or {}).items()
             if found.get(k, 0) != n}
    check(not wrong, f"{where}: call sites {wrong}, expected {sites}")
    return found


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def init_serving_params(cfg, mesh=None):
    """Seeded random weights, generated ON the device(s) they live on: under
    a mesh each chip draws its own shard (full-depth weights never exist on
    one chip). Values do not depend on the sharding."""
    import jax
    from jax.sharding import NamedSharding
    from paddle_tpu.models import llama as L
    shardings = None if mesh is None else {
        k: NamedSharding(mesh, spec)
        for k, spec in L.serving_param_specs(cfg).items()}
    return jax.jit(lambda: L.init_stacked_params(cfg, seed=SEED),
                   out_shardings=shardings)()


def make_prompts(sz: Sizes):
    import numpy as np
    rng = np.random.RandomState(SEED)
    vocab = sz.serve_cfg.vocab_size
    prefix = rng.randint(1, vocab, (sz.shared_prefix,))
    wave1 = [rng.randint(1, vocab, (n,)) for n in sz.prompt_lens]
    wave1[sz.shared_at][:sz.shared_prefix] = prefix
    warm = np.concatenate(
        [prefix, rng.randint(1, vocab, (sz.warm_len - sz.shared_prefix,))])
    return [p.astype(np.int32) for p in wave1], warm.astype(np.int32)


def serve_phase(sz: Sizes, name: str = "serve", cfg=None, mesh=None):
    """Serve two waves through ServingScheduler; returns the greedy streams
    (request order) for cross-run comparison."""
    import jax
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.inference.sampling import SamplerConfig
    from paddle_tpu.models import llama as L
    from paddle_tpu.observability.runtime import recompiles
    from paddle_tpu.serving import ServingScheduler

    cfg = cfg or sz.serve_cfg
    t0 = time.perf_counter()
    params = init_serving_params(cfg, mesh)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=sz.new_tokens, seed=SEED),
        num_slots=sz.slots, page_size=sz.page, max_seq_len=sz.max_seq,
        prefix_cache=True, mesh=mesh)
    sched = ServingScheduler(eng)
    misses0 = recompiles.count("cbe.unified_step")
    compile0 = recompiles.compile_seconds_total("cbe.unified_step")

    wave1, warm = make_prompts(sz)
    sampled_at = 3
    handles = [
        sched.submit(p, sampler=SamplerConfig(
            temperature=0.8, top_k=40, top_p=0.95, seed=7)
            if i == sampled_at else None)
        for i, p in enumerate(wave1)]      # all before the first step
    round_s = []

    def drain():
        while sched.pending and not sched.degraded:
            t = time.perf_counter()
            sched.step(params)
            round_s.append(time.perf_counter() - t)
        # the scheduler turns a failing engine.step (a compile error, a
        # kernel Mosaic refuses) into drained requests and a normal return:
        # right for production, so the smoke has to ask
        check(not sched.degraded,
              "scheduler degraded: engine.step failed repeatedly — "
              + "; ".join(sorted({str(h.stream.error) for h in handles
                                  if h.stream.error is not None})))

    drain()
    hits0 = eng.cache.snapshot()["hits"]
    handles.append(sched.submit(warm))     # second wave: the warm prefix
    drain()

    failures = sched.metrics.counters.get("step_failures_total", 0)
    check(failures == 0, f"step_failures_total = {failures}")
    for i, h in enumerate(handles):
        check(h.done and h.stream.error is None,
              f"request {i}: state {h.state}, error {h.stream.error!r}")
        n = len(h.stream.tokens)
        check(n == sz.new_tokens,
              f"request {i}: {n} tokens, budget {sz.new_tokens}")
        check(all(0 <= t < cfg.vocab_size for t in h.stream.tokens),
              f"request {i}: token outside the vocabulary")
    misses = recompiles.count("cbe.unified_step") - misses0
    check(misses <= 2, f"{misses} compiles of cbe.unified_step (<= 2)")
    eng.mgr.check_conservation()
    snap = eng.cache.snapshot()
    check(snap["hits"] > hits0
          and snap["cached_tokens"] >= sz.shared_prefix,
          f"warm-prefix request shows no cache hit: {snap}")
    kernels = require_kernels(eng.lower_unified_step(),
                              ("ragged_paged_attention", "rms_norm_fwd"),
                              "unified step")
    chips = mesh.size if mesh is not None else 1
    say(name, ok=True, layers=cfg.num_hidden_layers, chips=chips,
        weights_gb=round(L.param_nbytes(cfg) / 1e9, 2),
        pool_gb=round(2 * eng.mgr.k_pages.nbytes / 1e9, 2),
        requests=len(handles), prompt_tokens=int(
            sum(len(p) for p in wave1) + len(warm)),
        tokens=sum(len(h.stream.tokens) for h in handles),
        init_s=round(init_s, 2),
        compile_s=round(recompiles.compile_seconds_total(
            "cbe.unified_step") - compile0, 2),
        compiles=int(misses), rounds=len(round_s),
        steady_round_s=round(statistics.median(round_s[1:]), 4),
        cache_hits=snap["hits"], cached_tokens=snap["cached_tokens"],
        kernels=kernels, bytes_in_use_per_chip=bytes_in_use(chips))
    return [h.stream.tokens for i, h in enumerate(handles)
            if i != sampled_at]


def compare_streams(one_chip, four_chip) -> None:
    """Greedy streams of the same model at mp=1 and mp=4. Not byte-equal by
    design: at random-init weights the logits are near-uniform and the TP
    all-reduce's bf16 rounding flips near-tied argmaxes, after which a
    stream diverges for good. A sharding bug (wrong head on a chip) agrees
    on ~1/vocab of first tokens; rounding agrees on most."""
    first = sum(a[0] == b[0] for a, b in zip(one_chip, four_chip))
    prefix = [next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                   len(a)) / len(a) for a, b in zip(one_chip, four_chip)]
    say("4chips.streams", first_token_agree=f"{first}/{len(one_chip)}",
        identical=sum(p == 1.0 for p in prefix),
        mean_common_prefix=round(statistics.mean(prefix), 3))
    check(2 * first >= len(one_chip),
          f"only {first}/{len(one_chip)} greedy first tokens agree between "
          "the one-chip and the four-chip engine")


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------
def _err(got, ref) -> float:
    """max|got - ref| / max|ref| in float32."""
    import jax.numpy as jnp
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def parity_phase(sz: Sizes, interpret: bool = False) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.ops import rms_norm as rn

    cfg = sz.serve_cfg
    nh, nkv, d, h = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim, cfg.hidden_size)
    rng = np.random.RandomState(SEED)
    key = jax.random.key(SEED)
    errs = {}

    # -- ragged paged attention at the unified step's shapes: T = slots
    # packed tokens, one pool layer, four mixes through ONE compiled
    # kernel (its grid's extent is data). dense: a long decode row, a short
    # decode row, a prefill span starting mid-page, idle rows between, one
    # pad slot. sparse: two decode rows far apart, every other row starved.
    # empty: a round of pad slots only (no block to read; zeros out).
    # blocks: the kernel folds G pages of a row a step; decode rows of 1,
    # exactly G and G + 5 pages and a prefill span that crosses a block
    # boundary into page 2G + 1, so last blocks hold 1 to G of their slots.
    # windowed / blocks_windowed: the dense and the blocks mix again under
    # a sliding window (a second program: the mask's lower bound and each
    # row's first listed page, from which its blocks count; the long rows
    # and the prefill spans lose pages, and a list starts mid-block).
    t, rows, width = sz.slots, sz.slots, sz.max_seq // sz.page
    n_pages = rows * width + 1
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (t, nh, d), jnp.bfloat16)
    k_pages = jax.random.normal(kk, (n_pages, sz.page, nkv, d), jnp.bfloat16)
    v_pages = jax.random.normal(kv, (n_pages, sz.page, nkv, d), jnp.bfloat16)
    tables = (1 + rng.permutation(n_pages - 1)).reshape(rows, width)
    span = t - 3                    # prefill tokens; one slot stays a pad
    group = pa.ragged_block_pages(sz.page, width)
    mixes = {   # name -> [(row, first position, tokens)], packed in order
        "dense": [(0, sz.max_seq - 2, 1), (1, sz.page + 2, 1),
                  (rows - 1, 3 * sz.page + 1, span)],
        "sparse": [(1, 2 * sz.page - 1, 1), (rows - 2, sz.page, 1)],
        "empty": [],
        "blocks": [(0, sz.page - 2, 1), (1, group * sz.page - 1, 1),
                   (2, (group + 5) * sz.page - 3, 1),
                   (rows - 1, 2 * group * sz.page - 5, span)],
    }
    scale = 1.0 / d ** 0.5
    ragged = jax.jit(lambda *a: pa.ragged_paged_attention_pallas(
        *a, scale=scale, interpret=interpret))
    ragged_ref = jax.jit(lambda *a: pa.ragged_paged_attention_array(
        *a, scale=scale))
    # the window is an operand: one windowed program for both spans
    windowed = jax.jit(lambda *a: pa.ragged_paged_attention_pallas(
        *a[:-1], scale=scale, interpret=interpret, window=a[-1]))
    windowed_ref = jax.jit(lambda *a: pa.ragged_paged_attention_array(
        *a[:-1], scale=scale, window=a[-1]))
    cases = [(name, spans, ragged, ragged_ref, ())
             for name, spans in mixes.items()]
    cases += [("windowed", mixes["dense"], windowed, windowed_ref,
               (jnp.int32(3 * sz.page - 1),)),
              ("blocks_windowed", mixes["blocks"], windowed, windowed_ref,
               (jnp.int32((group + 2) * sz.page + 5),))]
    def packed(spans):
        """A mix's (token_row, positions, kv_lens), its spans packed in
        order into the ``t`` slots."""
        token_row = np.full((t,), -1, np.int32)
        positions = np.zeros((t,), np.int32)
        kv_lens = np.zeros((rows,), np.int32)
        at = 0
        for row, first, n in spans:
            token_row[at:at + n] = row
            # a toy table is narrower than the mix: stay inside it
            positions[at:at + n] = np.minimum(first + np.arange(n),
                                              sz.max_seq - 1)
            kv_lens[row] = positions[at + n - 1] + 1
            at += n
        return token_row, positions, kv_lens

    for name, spans, ragged, ragged_ref, window in cases:
        token_row, positions, kv_lens = packed(spans)
        args = (q, k_pages, v_pages, jnp.asarray(tables, jnp.int32),
                jnp.asarray(token_row), jnp.asarray(positions),
                jnp.asarray(kv_lens)) + window
        if not interpret and name == "dense":   # one program for all four
            require_kernels(ragged.lower(*args),
                            ("ragged_paged_attention",), "ragged parity")
        got = ragged(*args)
        pad = token_row < 0
        check(bool(jnp.all(got[pad] == 0)),
              f"ragged kernel ({name}): pad slots not 0")
        check(bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))),
              f"ragged kernel ({name}): non-finite output")
        if spans:
            errs[f"ragged_paged_attention.{name}"] = _err(
                got[~pad], ragged_ref(*args)[~pad])

    # -- the latent (MLA) ragged kernel on the same mixes (its rows' tokens
    # are contiguous in them): one pool without a head axis, an entry of 640
    # lanes (A.X-K1's 576 numbers), values its first 512; heads as A.X-K1's
    lat_d, lat_v, lat_h = 640, 512, 64
    kq, kp, key = jax.random.split(key, 3)
    q_lat = jax.random.normal(kq, (t, lat_h, lat_d), jnp.bfloat16)
    lat_pool = jax.random.normal(kp, (n_pages, sz.page, lat_d), jnp.bfloat16)
    mla = jax.jit(lambda *a: pa.mla_paged_attention_pallas(
        *a, scale=0.13, value_dim=lat_v, interpret=interpret))
    mla_ref = jax.jit(lambda *a: pa.mla_paged_attention_array(
        *a, scale=0.13, value_dim=lat_v))
    # and one mix of its own: rows whose tables name the SAME leading pages
    # (prefix-cache hits borrow them), which the kernel folds once for the
    # whole group under the lowest row's item: decode rows and a prefill
    # row on up to three shared blocks, more members than a tile of tokens,
    # and a row with pages of its own beside them
    borrowed = min(3, width // group - 1) * group
    shared_tables = tables.copy()
    shared_tables[1:rows - 3, :borrowed] = tables[0, :borrowed]
    base = borrowed * sz.page
    shared = [(r, base + 5 + 9 * r, 1) for r in range(rows - 4)] \
        + [(rows - 4, base + 1, 3), (rows - 1, 3 * sz.page + 2, 1)]
    for name, spans in list(mixes.items()) + [("shared", shared)]:
        token_row, positions, kv_lens = packed(spans)
        args = (q_lat, lat_pool,
                jnp.asarray(shared_tables if name == "shared" else tables,
                            jnp.int32),
                jnp.asarray(token_row), jnp.asarray(positions),
                jnp.asarray(kv_lens))
        if not interpret and name == "dense":
            require_kernels(mla.lower(*args), ("mla_paged_attention",),
                            "latent parity")
        if name == "shared":
            check(int(pa.ragged_shared_blocks(
                shared_tables, kv_lens, sz.page).sum())
                == (rows - 4) * (borrowed // group),
                "latent kernel (shared): the mix's rows share no block")
        got = mla(*args)
        pad = token_row < 0
        check(bool(jnp.all(got[pad] == 0)),
              f"latent kernel ({name}): pad slots not 0")
        check(bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))),
              f"latent kernel ({name}): non-finite output")
        if spans:
            errs[f"mla_paged_attention.{name}"] = _err(
                got[~pad], mla_ref(*args)[~pad])

    # -- rms_norm fwd+bwd at the serve (T x h) and train (B*S x h) row counts
    for rows_ in (sz.slots, sz.batch * sz.seq):
        kx, kw, kg, key = jax.random.split(key, 4)
        x = jax.random.normal(kx, (rows_, h), jnp.bfloat16)
        w = 1 + 0.1 * jax.random.normal(kw, (h,), jnp.bfloat16)
        g = jax.random.normal(kg, (rows_, h), jnp.bfloat16)

        def fwd_bwd(f, x, w, g):
            y, vjp = jax.vjp(f, x, w)
            return (y,) + vjp(g)

        kern = jax.jit(lambda x, w, g: fwd_bwd(
            lambda a, b: rn.rms_norm_array(a, b, cfg.rms_norm_eps), x, w, g))
        if not interpret:
            require_kernels(kern.lower(x, w, g),
                            ("rms_norm_fwd", "rms_norm_bwd"),
                            "rms_norm parity")
        ref_f = jax.jit(lambda x, w, g: fwd_bwd(
            lambda a, b: rn._rms_norm_ref(a, b, cfg.rms_norm_eps), x, w, g))
        for part, a, b in zip(("y", "dx", "dw"), kern(x, w, g),
                              ref_f(x, w, g)):
            errs[f"rms_norm[{rows_}].{part}"] = _err(a, b)

    # -- flash attention fwd+bwd at the train step's (S, d), causal
    kq, kk, kv, kg = jax.random.split(key, 4)
    shape = (sz.parity_flash_bh, sz.seq, sz.train_cfg.head_dim)
    q, k, v, g = (jax.random.normal(kk_, shape, jnp.bfloat16)
                  for kk_ in (kq, kk, kv, kg))
    scale = 1.0 / shape[-1] ** 0.5

    def attn_fwd_bwd(f, q, k, v, g):
        y, vjp = jax.vjp(lambda a, b, c: f(a, b, c, scale, True), q, k, v)
        return (y,) + vjp(g)

    kern = jax.jit(lambda *a: attn_fwd_bwd(fa.flash_attention_bhsd, *a))
    if not interpret:
        require_kernels(kern.lower(q, k, v, g),
                        ("flash_attention_fwd", "flash_attention_bwd_dq",
                         "flash_attention_bwd_dkv"), "flash parity")
    ref_f = jax.jit(lambda *a: attn_fwd_bwd(fa._attn_ref, *a))
    for part, a, b in zip(("out", "dq", "dk", "dv"), kern(q, k, v, g),
                          ref_f(q, k, v, g)):
        errs[f"flash_attention.{part}"] = _err(a, b)

    errs = {k: round(v, 5) for k, v in errs.items()}
    worst = max(errs, key=errs.get)
    check(errs[worst] <= BF16_TOL,
          f"{worst}: normalized max error {errs[worst]} > {BF16_TOL} "
          f"(all: {errs})")
    say("parity", ok=True, tolerance=BF16_TOL, normalized_max_error=errs)


# ---------------------------------------------------------------------------
# train + fence
# ---------------------------------------------------------------------------
def train_phase(sz: Sizes, name: str = "train", degrees=None,
                fence: bool = True) -> float:
    """A few optimizer steps on one repeated batch; returns the first loss.
    ``fence`` also times steady steps to block_until_ready and to a
    float(loss) read."""
    import jax
    import numpy as np
    from paddle_tpu.models import llama as L
    from paddle_tpu.parallel import mesh as pmesh

    cfg = sz.train_cfg
    degrees = degrees or {}
    chips = int(np.prod(list(degrees.values()) or [1]))
    mesh = pmesh.build_mesh(degrees, devices=jax.devices()[:chips])
    step, init_fn = L.build_hybrid_train_step(cfg, mesh)
    params, opt_state = init_fn(seed=SEED)
    rng = np.random.RandomState(SEED)
    ids = rng.randint(0, cfg.vocab_size, (1, sz.batch, sz.seq)).astype(
        np.int32)
    labels = np.roll(ids, -1, axis=-1)
    kernels = require_kernels(
        step.lower(params, opt_state, ids, labels),
        ("rms_norm_fwd", "rms_norm_bwd"), "train step",
        # the backward reads the forward's saved out and lse: a second
        # forward site is the S^2 kernel replayed under remat
        sites={"flash_attention_fwd": 1, "flash_attention_bwd_dq": 1,
               "flash_attention_bwd_dkv": 1})

    t0 = time.perf_counter()
    loss, params, opt_state = step(params, opt_state, ids, labels)
    losses = [float(loss)]
    compile_s = time.perf_counter() - t0
    for _ in range(2):
        loss, params, opt_state = step(params, opt_state, ids, labels)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    facts = dict(ok=True, chips=chips, degrees=degrees,
                 params_m=round(L.param_count(cfg) / 1e6),
                 tokens_per_step=sz.batch * sz.seq,
                 compile_and_first_step_s=round(compile_s, 2),
                 losses=[round(x, 4) for x in losses], kernels=kernels,
                 bytes_in_use_per_chip=bytes_in_use(chips))
    if not fence:
        say(name, **facts)
        return losses[0]

    # the fence: the same steady step, waited for two ways, alternating.
    # If block_until_ready returned before the device finished, its time
    # would collapse to the enqueue time while the value read stayed put.
    enqueue_s, bur_s, read_s = [], [], []
    for i in range(4):
        t0 = time.perf_counter()
        loss, params, opt_state = step(params, opt_state, ids, labels)
        enqueue_s.append(time.perf_counter() - t0)
        if i % 2 == 0:
            jax.block_until_ready(loss)
            bur_s.append(time.perf_counter() - t0)
        else:
            float(loss)
            read_s.append(time.perf_counter() - t0)
    bur, read = statistics.mean(bur_s), statistics.mean(read_s)
    agree = abs(bur - read) <= sz.fence_tol * read
    say(name, **facts, steady_step_s=round(read, 4))
    say("fence", ok=agree,
        block_until_ready_s=round(bur, 4), float_read_s=round(read, 4),
        enqueue_s=round(statistics.mean(enqueue_s), 4),
        holds="block_until_ready is a real fence on this platform" if agree
        else "block_until_ready returned early; only a value read fences")
    check(agree,
          f"fence: block_until_ready {bur:.4f}s vs float(loss) {read:.4f}s "
          f"differ by more than {sz.fence_tol:.0%}")
    return losses[0]


# ---------------------------------------------------------------------------
def run_phases(sz: Sizes, n_devices: int, interpret: bool = False) -> None:
    """Every phase in order. ``interpret`` is the CPU rehearsal's switch
    (tests/test_chip_smoke.py): Pallas in interpret mode, no kernel
    expected in any lowering."""
    with phase("serve"):
        streams = serve_phase(sz)
        say("release", bytes_in_use_after_serve=release())
    with phase("parity"):
        parity_phase(sz, interpret)
        say("release", bytes_in_use_after_parity=release())
    with phase("train+fence"):
        loss1 = train_phase(sz)
        say("release", bytes_in_use_after_train=release())
    if n_devices < 4:
        say("4chips", skipped=f"{n_devices} chip(s) in this process")
        return
    import jax
    from paddle_tpu.parallel.mesh import serving_mesh
    mesh = serving_mesh(4, jax.devices()[:4])
    with phase("4chips.serve"):
        compare_streams(streams, serve_phase(sz, "4chips.serve", mesh=mesh))
        release()
    with phase("4chips.serve_full_depth"):
        serve_phase(sz, "4chips.serve_full_depth", mesh=mesh,
                    cfg=dataclasses.replace(
                        sz.serve_cfg, num_hidden_layers=sz.full_depth))
        release()
    with phase("4chips.train"):
        loss4 = train_phase(sz, "4chips.train", fence=False,
                            degrees={"mp": 2, "sharding": 2})
        check(abs(loss4 - loss1) <= 1e-2 * abs(loss1),
              f"first-step loss on four chips {loss4} vs one chip {loss1}")


def main() -> int:
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found platform {dev.platform!r}); "
              "this smoke proves nothing off the chip and refuses to run",
              file=sys.stderr)
        return 4
    try:
        from paddle_tpu.compile_cache import (cache_entries,
                                              enable_compile_cache)
    except ImportError as e:
        print(f"chip_smoke: run from the root of the repo ({e})",
              file=sys.stderr)
        return 4
    cache_dir = enable_compile_cache()
    say("start", compile_cache_dir=cache_dir,
        cache_entries_before=cache_entries(cache_dir),
        note="serve depth cut 32 -> 8 layers to fit one 16 GB chip; width, "
             "heads, ff and vocab are Llama-2-7B's")
    t0 = time.perf_counter()
    try:
        run_phases(Sizes.full(), device["count"])
    except PhaseFailed as e:
        traceback.print_exc()
        print(json.dumps({"ok": False, "failed_phase": e.name,
                          "error": str(e)}), flush=True)
        return 1
    say("done", wall_s=round(time.perf_counter() - t0, 1),
        cache_entries_after=cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
