"""The serving system under test for a Jamba configuration: the SAME
``ContinuousBatchingEngine`` behind ``ServingScheduler`` as
``serve_llama.py`` drives, built from ``models.jamba.JambaConfig``: the
engine takes the model's step, the pages of its attention layers AND the
state a row keeps in its Mamba layers from the configuration's class.
Everything but the model's configuration, its weights and their names under
the plain reference is ``serve_llama.Server``'s.

The program is imported as this file is: a commit that cannot serve the
model fails here, at once, before any weight is drawn. Program names this
file calls beyond ``serve_llama.py``'s: ``models.jamba.{JambaConfig,
init_stacked_params}`` and its weight names, ``serving.SchedulerConfig(
max_queue_depth)`` and ``engine.mgr.arrays`` (PERF.md section 3).
"""

from __future__ import annotations

import inspect
import time
from typing import Dict

from paddle_tpu.models import jamba as J      # first: see the module doc

from perfbench import harness

_llama = harness.load_module("perfbench/adapters/serve_llama.py")

LOOPS = _llama.LOOPS
enable_cache = _llama.enable_cache
fold_seed = _llama.fold_seed


def jamba_config(model: Dict, serving: Dict):
    import jax.numpy as jnp
    if model.get("sliding_window") is not None:
        raise ValueError("models.jamba has no sliding-window attention")
    return J.JambaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_hidden_layers=model["num_hidden_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        attn_layer_period=model["attn_layer_period"],
        attn_layer_offset=model["attn_layer_offset"],
        mamba_d_state=model["mamba_d_state"],
        mamba_d_conv=model["mamba_d_conv"],
        mamba_expand=model["mamba_expand"],
        mamba_dt_rank=model["mamba_dt_rank"],
        num_experts=model["num_experts"],
        max_position_embeddings=model["max_position_embeddings"],
        rms_norm_eps=model["rms_norm_eps"],
        tie_word_embeddings=model["tie_word_embeddings"],
        dtype=getattr(jnp, serving["dtype"]),
        state_dtype=getattr(jnp, serving["state_dtype"]))


class ReferenceWeights:
    """The program's two stacks of weights (``m_*``: Mamba layers, ``a_*``:
    attention layers) under the plain reference's names, one layer sliced at
    a time (``x @ W`` orientation on both sides). The program holds
    ``A_log`` and the conv's weight with d_inner last; the reference gets
    them (d_inner, .) as published."""

    _FF = {"pre_ff_layernorm": "ln_ff", "gate_proj": "w_gate",
           "up_proj": "w_up", "down_proj": "w_down",
           "input_layernorm": "ln_in"}
    _ATTENTION = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv",
                  "o_proj": "wo"}
    _MAMBA = {"in_proj": "in_proj", "conv1d_bias": "conv_b",
              "x_proj": "x_proj", "dt_layernorm": "dt_norm",
              "b_layernorm": "b_norm", "c_layernorm": "c_norm",
              "dt_proj": "dt_proj", "dt_proj_bias": "dt_bias",
              "D": "d_skip", "out_proj": "out_proj"}

    def __init__(self, params: Dict, layer_kinds):
        self._p = params
        self._kinds = tuple(layer_kinds)
        self.embed = params["embed"]
        self.norm = params["ln_f"]

    def layer(self, i: int) -> Dict:
        kind = self._kinds[i]
        j = self._kinds[:i].count(kind)         # its index in its stack
        if kind == "attention":
            names, stack = {**self._FF, **self._ATTENTION}, "a_"
        else:
            names, stack = {**self._FF, **self._MAMBA}, "m_"
        out = {ref: self._p[stack + own][j] for ref, own in names.items()}
        if kind == "mamba":
            out["A_log"] = self._p["m_a_log"][j].T
            out["conv1d_weight"] = self._p["m_conv_w"][j].T
        return out


class Server(_llama.Server):
    def __init__(self, config: Dict, chips: int, seed: int):
        import jax
        from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                                   GenerationConfig)
        from paddle_tpu.observability.runtime import recompiles
        from paddle_tpu.serving import SchedulerConfig, ServingScheduler

        if chips != 1:
            raise ValueError("models.jamba serves on one chip (every weight "
                             "replicated; the state pool lives on one chip)")
        t0 = time.perf_counter()
        serving = config["serving"]
        self.cfg = cfg = jamba_config(config, serving)
        self.vocab_size = cfg.vocab_size
        self._recompiles = recompiles
        self._mesh = None
        # one jitted call on the device, the seed an ARGUMENT (as a
        # constant every seed would be a program of its own)
        self.params = jax.jit(
            lambda s: J.init_stacked_params(cfg, seed=s))(fold_seed(seed))
        jax.block_until_ready(self.params)
        t1 = time.perf_counter()
        page = inspect.signature(
            ContinuousBatchingEngine.__init__).parameters["page_size"].default
        self.engine = ContinuousBatchingEngine(
            cfg, GenerationConfig(seed=fold_seed(seed)),
            num_slots=int(serving["num_slots"]),
            max_seq_len=int(serving["max_seq_len"]),
            num_pages=int(serving["kv_pool_tokens"]) // page + 1,
            prefix_cache=bool(serving["prefix_cache"]))
        # the admission queue holds as many requests as the user has
        # callers (the scheduler's own 64 sheds half of 128 callers' first
        # requests, which all arrive before the first step)
        self.sched = ServingScheduler(self.engine, SchedulerConfig(
            max_queue_depth=int(serving["max_queue_depth"])))
        jax.block_until_ready(self.engine.mgr.arrays)
        self.load_seconds = {"weights": t1 - t0,
                             "engine": time.perf_counter() - t1}
        self._misses0 = 0.0

    def reference_weights(self) -> ReferenceWeights:
        return ReferenceWeights(self.params, self.cfg.layer_kinds)
