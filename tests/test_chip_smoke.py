"""Rehearsal of chip_smoke.py's control flow at a toy size on the CPU mesh.

It proves NOTHING about the chip — the Pallas kernels are not even selected
here (the ragged kernel runs in interpret mode, the others compare a
reference with itself). It exists so that a later change to the engine,
scheduler or train-step API breaks a CPU test instead of a chip run.
~25 s with the refusal check below (the driver checks that contract
itself after every PR), so outside tier-1; scripts/verify.sh runs the file
as a stage."""

import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.mark.slow
def test_rehearse_every_phase_at_toy_size(monkeypatch, capsys):
    from paddle_tpu.models import llama as L
    toy = L.LlamaConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=256, dtype=jnp.bfloat16)
    sz = chip_smoke.Sizes(
        serve_cfg=toy, slots=4, page=4, max_seq=64,
        prompt_lens=(5, 9, 12, 7, 20, 30), shared_at=4, shared_prefix=16,
        warm_len=24, new_tokens=6, full_depth=3, train_cfg=toy, batch=2,
        seq=128, parity_flash_bh=4,
        fence_tol=1.0)      # a 20 ms CPU step jitters by more than the
    #                         chip's 10%; only the flow is rehearsed
    # off the chip no Pallas kernel is in any lowering
    monkeypatch.setattr(chip_smoke, "require_kernels",
                        lambda lowered, names, where, sites=None: {})
    chip_smoke.run_phases(sz, n_devices=4, interpret=True)
    out = capsys.readouterr().out
    for phase in ("serve", "parity", "train", "fence", "4chips.serve",
                  "4chips.serve_full_depth", "4chips.train"):
        assert f'"phase": "{phase}", "ok": true' in out, phase


@pytest.mark.slow
def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    """The driver's contract: off the chip — here, and in a directory that
    holds chip_smoke.py and nothing else — a non-zero exit and no result."""
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    for cwd in (REPO, tmp_path):
        r = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=cwd, text=True,
            capture_output=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode != 0
        assert '"ok"' not in r.stdout
        assert "no TPU" in r.stderr
