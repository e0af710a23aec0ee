"""The training system under test: ``models.llama.build_hybrid_train_step``
on the mesh the configuration names, its ``init_fn``, and the step itself.

The configuration gives the model's sizes and the USER's choices (tokens per
batch, optimizer, learning rate, mesh axes, dtype). ``remat``,
``remat_policy``, ``k_steps``, ``zero_gather`` and the pipeline schedule are
the program's defaults at this commit.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from perfbench.adapters.serve_llama import (  # noqa: F401
    ReferenceWeights, enable_cache, fold_seed, llama_config)

LOOPS = ("train",)


class Trainer:
    def __init__(self, config: Dict, chips: int, seed: int, seq_len: int):
        import jax
        from paddle_tpu.models import llama as L
        from paddle_tpu.parallel import mesh as pmesh

        t0 = time.perf_counter()
        train = config["train"]
        if train["optimizer"] != "adamw":
            raise ValueError("build_hybrid_train_step trains with AdamW")
        self.cfg = llama_config(config, train["dtype"])
        self.vocab_size = self.cfg.vocab_size
        self.seq_len = int(seq_len)
        self.batch = int(train["batch_tokens"]) // self.seq_len
        degrees = dict(train.get("mesh", {}))
        if int(np.prod(list(degrees.values()) or [1])) != chips:
            raise ValueError(f"mesh {degrees} does not use {chips} chip(s)")
        mesh = pmesh.build_mesh(degrees, devices=jax.devices()[:chips])
        self._step, init_fn = L.build_hybrid_train_step(
            self.cfg, mesh, learning_rate=float(train["learning_rate"]))
        # one jitted call: weights and optimizer state made on the device;
        # the seed is an argument, so that all seeds share one program
        self.params, self.opt_state = jax.jit(init_fn)(fold_seed(seed))
        jax.block_until_ready(self.params)
        self.load_seconds = {"weights_and_state": time.perf_counter() - t0}

    def make_batch(self, rng: np.random.Generator):
        """Fresh uniform tokens (1, B, S) and the labels shifted by one."""
        ids = rng.integers(0, self.vocab_size, (1, self.batch, self.seq_len),
                           dtype=np.int32)
        return ids, np.roll(ids, -1, axis=-1)

    def repeat_batch(self, sequence: np.ndarray):
        """``batch`` copies of one sequence, in the compiled shape."""
        ids = np.broadcast_to(sequence.astype(np.int32)[None, None, :],
                              (1, self.batch, self.seq_len)).copy()
        return ids, np.roll(ids, -1, axis=-1)

    def step(self, ids, labels):
        """Enqueue one optimizer step; returns the loss (a device scalar)."""
        loss, self.params, self.opt_state = self._step(
            self.params, self.opt_state, ids, labels)
        return loss

    def kernels(self, ids, labels) -> Dict[str, int]:
        from paddle_tpu.ops._common import mosaic_kernels
        return mosaic_kernels(
            self._step.lower(self.params, self.opt_state, ids, labels))

    def reference_weights(self) -> ReferenceWeights:
        return ReferenceWeights(self.params)
