#!/usr/bin/env python
"""Compile the serving and training steps for a v5e host WITHOUT a chip.

libtpu compiles ahead of time for a topology it is only told about:
``jax.experimental.topologies`` yields the four ``TpuDevice``s of a
``v5e:2x2`` host, and lowering a jitted function over abstract arguments
sharded onto them runs the real XLA:TPU + Mosaic compiler (it rejects an
oversize program with the usual "Ran out of memory in memory space hbm").
That catches, on a CPU-only box, the failures a chip run would otherwise
spend its budget finding: a Pallas kernel Mosaic refuses, a kernel that
meets GSPMD outside ``shard_map``, a program that does not fit HBM.

Checked at Llama-2-7B width (h=4096, 32x128 heads, ff=11008, bf16) and
2 layers: the engine's unified step on one chip and on
``serving_mesh(4)``, the one-chip hybrid train step, and the unified step
of an AFMoE engine at Trinity-Mini's widths (a window in the ragged kernel,
the grouped expert product), of an A.X-K1 engine (the latent ragged
kernel over a pool without a head axis), of a Jamba engine (the selective
scan over each row's state, multi-query pools without a head axis) and of a
SmallThinker engine (the windowed ragged kernel at 7 query heads a KV head,
ReGLU experts) — each must compile and carry its Pallas
kernels, by name, in the lowering. What it
cannot show is whether the programs RUN correctly; that is
``chip_smoke.py``'s job, on the chip.

Run: python scripts/tpu_aot_check.py   (tests/test_tpu_aot.py runs the
same checks). Prints one JSON line; exit 0 = all compiled.
"""

import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def v5e_devices():
    """The four devices of a v5e:2x2 host, described by libtpu (raises if
    the installed libtpu cannot describe the topology)."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices


@contextlib.contextmanager
def tpu_kernel_selection():
    """Select kernels as on a TPU host: ``ops._common.on_tpu`` reads the
    default backend, which here is the CPU, while the compile target is
    the described topology."""
    from paddle_tpu.ops import _common
    real = _common.on_tpu
    _common.on_tpu = lambda: True
    try:
        yield
    finally:
        _common.on_tpu = real


def _compile(name, lowered, expect):
    from paddle_tpu.ops._common import mosaic_kernels
    t0 = time.perf_counter()
    kernels = mosaic_kernels(lowered)
    missing = sorted(set(expect) - set(kernels))
    if missing:
        raise AssertionError(
            f"{name}: {missing} not in the lowering (found {kernels}) — a "
            "Pallas kernel gave way to its XLA reference")
    lowered.compile()
    return {"kernels": kernels,
            "compile_s": round(time.perf_counter() - t0, 2)}


def serving_engine():
    """A 7B-width engine (2 layers) whose unified step is the program
    chip_smoke.py serves with."""
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.inference.decoding import ContinuousBatchingEngine
    from paddle_tpu.inference.sampling import SamplerConfig
    from paddle_tpu.models import llama as L

    cfg = L.llama2_7b(num_hidden_layers=2, dtype=jnp.bfloat16)
    # the engine itself lives on the local backend with a token pool;
    # only the lowering targets the described chips
    eng = ContinuousBatchingEngine(cfg, num_slots=16, page_size=16,
                                   max_seq_len=2048, num_pages=129,
                                   prefix_cache=True)
    # a sampled request switches the step to its full sampling epilogue —
    # the program chip_smoke.py serves with
    eng.submit(np.ones((4,), np.int32), sampler=SamplerConfig(seed=0))
    return eng


def check_unified_step(eng, devices, chips,
                       expect=("ragged_paged_attention", "rms_norm_fwd")):
    """The engine's unified ragged step on ``chips`` described chips: the
    ragged paged-attention kernel + rms_norm (+ what ``expect`` adds)."""
    from paddle_tpu.parallel.mesh import serving_mesh
    return _compile(f"unified_step/mp{chips}", eng.lower_unified_step(
        mesh=serving_mesh(chips, devices)), expect=expect)


def afmoe_engine():
    """An engine at Trinity-Mini's widths (all 128 experts, one dense and
    one expert layer, a sliding and a full one, a quarter of the vocabulary):
    the unified step with the windowed ragged kernel and the grouped expert
    product."""
    import jax.numpy as jnp
    from paddle_tpu.inference.decoding import ContinuousBatchingEngine
    from paddle_tpu.models import afmoe as A

    cfg = A.AfmoeConfig(vocab_size=50048, num_hidden_layers=2,
                        num_dense_layers=1,
                        layer_types=(A.SLIDING, A.FULL), dtype=jnp.bfloat16)
    return ContinuousBatchingEngine(cfg, num_slots=32, page_size=16,
                                    max_seq_len=8192, num_pages=1025,
                                    prefix_cache=True)


def axk1_engine():
    """An engine at A.X-K1's widths (12 of the 192 experts held, one dense
    and one expert layer, a sixteenth of the vocabulary): the unified step
    with the latent ragged kernel over a one-array pool and the grouped
    expert product."""
    import jax.numpy as jnp
    from paddle_tpu.inference.decoding import ContinuousBatchingEngine
    from paddle_tpu.models import axk1 as X

    cfg = X.Axk1Config(
        vocab_size=10240, num_hidden_layers=2, experts_held=12,
        rope_scaling=dict(type="yarn", factor=32, beta_fast=32, beta_slow=1,
                          mscale=1, mscale_all_dim=1,
                          original_max_position_embeddings=4096),
        dtype=jnp.bfloat16)
    return ContinuousBatchingEngine(cfg, num_slots=32, page_size=16,
                                    max_seq_len=17408, num_pages=1089,
                                    prefix_cache=True)


def longcat_engine():
    """An engine at LongCat-Flash's widths (16 of the 512 routed experts
    held beside the 256 zero-compute ones, two layers = four cache layers,
    an eighth of the vocabulary; 128 rows of 4,096 positions, a token pool):
    the unified step with the latent ragged kernel twice a scanned layer and
    the grouped expert product."""
    import jax.numpy as jnp
    from paddle_tpu.inference.decoding import ContinuousBatchingEngine
    from paddle_tpu.models import longcat_flash as F

    cfg = F.LongcatFlashConfig(vocab_size=16384, num_layers=2,
                               experts_held=16, dtype=jnp.bfloat16)
    return ContinuousBatchingEngine(cfg, num_slots=128, page_size=16,
                                    max_seq_len=4096, num_pages=1025,
                                    prefix_cache=True)


def jamba_engine():
    """An engine at Jamba2-3B's published sizes (all 28 layers, the whole
    vocabulary; 128 rows of 4,096 positions, a token pool): the unified step
    with the
    selective scan over each row's state, the ragged kernel over K and V
    pools WITHOUT a head axis (multi-query attention) and rms_norm. The
    scanned runs of Mamba layers cost the compile nothing (~6 s whole)."""
    import jax.numpy as jnp
    from paddle_tpu.inference.decoding import ContinuousBatchingEngine
    from paddle_tpu.models import jamba as J

    cfg = J.JambaConfig(dtype=jnp.bfloat16)
    return ContinuousBatchingEngine(cfg, num_slots=128, page_size=16,
                                    max_seq_len=4096, num_pages=1025)


def smallthinker_engine():
    """An engine at SmallThinker-21BA3B's widths (all 64 experts, a global
    and a window layer, an eighth of the vocabulary; 32 rows of 16,384
    positions, a token pool): the unified step with the windowed ragged
    kernel at 7 query heads a KV head and the grouped ReGLU product."""
    import jax.numpy as jnp
    from paddle_tpu.inference.decoding import ContinuousBatchingEngine
    from paddle_tpu.models import smallthinker as S

    cfg = S.SmallThinkerConfig(vocab_size=18992, num_hidden_layers=2,
                               dtype=jnp.bfloat16)
    return ContinuousBatchingEngine(cfg, num_slots=32, page_size=16,
                                    max_seq_len=16384, num_pages=1025,
                                    prefix_cache=True)


def check_train_step(devices):
    """bench.py's llama7b_layer geometry (B=8, S=2048, default remat) on
    one chip: flash attention fwd+bwd and rms_norm fwd+bwd."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import llama as L
    from paddle_tpu.parallel import mesh as pmesh

    cfg = L.llama2_7b(vocab_size=8192, num_hidden_layers=2,
                      max_position_embeddings=2048, dtype=jnp.bfloat16)
    step, _ = L.build_hybrid_train_step(
        cfg, pmesh.build_mesh({}, devices=devices[:1]), learning_rate=1e-4)
    params = jax.eval_shape(lambda: L.init_stacked_params(cfg))
    f32 = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params)
    opt = {"step": jax.ShapeDtypeStruct((), jnp.int32), "m": f32, "v": f32}
    batch = jax.ShapeDtypeStruct((1, 8, 2048), jnp.int32)
    return _compile(
        "train_step", step.lower(params, opt, batch, batch),
        expect=("flash_attention_fwd", "flash_attention_bwd_dq",
                "flash_attention_bwd_dkv", "rms_norm_fwd", "rms_norm_bwd"))


def run_checks():
    devices = v5e_devices()
    with tpu_kernel_selection():
        eng = serving_engine()
        return {
            "device_kind": devices[0].device_kind,
            "unified_step_mp1": check_unified_step(eng, devices, 1),
            "unified_step_mp4": check_unified_step(eng, devices, 4),
            "afmoe_unified_step_mp1": check_unified_step(
                afmoe_engine(), devices, 1,
                expect=("ragged_paged_attention", "rms_norm_fwd",
                        "moe_grouped_matmul")),
            "axk1_unified_step_mp1": check_unified_step(
                axk1_engine(), devices, 1,
                expect=("mla_paged_attention", "rms_norm_fwd",
                        "moe_grouped_matmul")),
            "longcat_unified_step_mp1": check_unified_step(
                longcat_engine(), devices, 1,
                expect=("mla_paged_attention", "rms_norm_fwd",
                        "moe_grouped_matmul")),
            "jamba_unified_step_mp1": check_unified_step(
                jamba_engine(), devices, 1,
                expect=("mamba_ragged_scan", "ragged_paged_attention",
                        "rms_norm_fwd")),
            "smallthinker_unified_step_mp1": check_unified_step(
                smallthinker_engine(), devices, 1,
                expect=("ragged_paged_attention", "rms_norm_fwd",
                        "moe_grouped_matmul")),
            "train_step": check_train_step(devices),
        }


if __name__ == "__main__":
    print(json.dumps(run_checks()))
