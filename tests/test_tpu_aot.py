"""Ahead-of-time compile check against a described v5e host (no chip):
scripts/tpu_aot_check.py compiles the unified serving step (one chip and
serving_mesh(4)) and the train step with the real XLA:TPU + Mosaic compiler.
~45 s of compiling, so outside tier-1; scripts/verify.sh runs it as a stage."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))

import tpu_aot_check  # noqa: E402


@pytest.mark.slow
def test_serving_and_train_steps_compile_for_v5e_with_their_kernels():
    try:
        tpu_aot_check.v5e_devices()
    except Exception as e:  # noqa: BLE001 - any libtpu/topology failure
        pytest.skip(f"libtpu cannot describe a v5e:2x2 topology here: {e}")
    out = tpu_aot_check.run_checks()
    assert out["device_kind"] == "TPU v5 lite"
    # _compile() raised if a kernel was missing or a program did not compile
    for name in ("unified_step_mp1", "unified_step_mp4"):
        assert out[name]["kernels"]["ragged_paged_attention"] == 1
        assert out[name]["kernels"]["rms_norm_fwd"] == 3
    # one call site of the ragged kernel a scanned stack (dense, expert);
    # gate, up and down of the expert layer
    afmoe = out["afmoe_unified_step_mp1"]["kernels"]
    assert afmoe["ragged_paged_attention"] == 2
    assert afmoe["moe_grouped_matmul"] == 3
    # A.X-K1: the latent kernel in both stacks, no GQA kernel anywhere
    axk1 = out["axk1_unified_step_mp1"]["kernels"]
    assert axk1["mla_paged_attention"] == 2
    assert axk1["moe_grouped_matmul"] == 3
    assert "ragged_paged_attention" not in axk1
    # LongCat-Flash: the latent kernel at both sub-layers of the ONE scanned
    # layer, the grouped product's three, no GQA kernel anywhere
    longcat = out["longcat_unified_step_mp1"]["kernels"]
    assert longcat["mla_paged_attention"] == 2
    assert longcat["moe_grouped_matmul"] == 3
    assert "ragged_paged_attention" not in longcat
    # Jamba: the scan in the Mamba layers' scanned body, the ragged kernel
    # at its two attention layers over pools without a head axis
    jamba = out["jamba_unified_step_mp1"]["kernels"]
    assert jamba["mamba_ragged_scan"] >= 1
    assert jamba["ragged_paged_attention"] == 2
    assert "mla_paged_attention" not in jamba
    # one call site each: a second forward site would be the S^2 kernel
    # replayed under remat (the backward reads the saved out and lse)
    train = out["train_step"]["kernels"]
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"):
        assert train[kernel] == 1, train
