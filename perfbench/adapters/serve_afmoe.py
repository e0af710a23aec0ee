"""The serving system under test for an AFMoE configuration (Trinity-Mini):
the SAME ``ContinuousBatchingEngine`` behind ``ServingScheduler`` as
``serve_llama.py`` drives, built from ``models.afmoe.AfmoeConfig``: the
engine takes the model's step from the configuration's class. Everything but
the model's configuration, its weights and their names under the plain
reference is ``serve_llama.Server``'s.

The program is imported as this file is: a commit that cannot serve the
model fails here, at once, before any weight is drawn. Program names this
file calls beyond ``serve_llama.py``'s: ``models.afmoe.{AfmoeConfig,
init_stacked_params, serving_param_specs}`` (PERF.md section 3).
"""

from __future__ import annotations

import inspect
import time
from typing import Dict

from paddle_tpu.models import afmoe as A      # first: see the module doc

from perfbench import harness

_llama = harness.load_module("perfbench/adapters/serve_llama.py")

LOOPS = _llama.LOOPS
enable_cache = _llama.enable_cache
fold_seed = _llama.fold_seed


def afmoe_config(model: Dict, dtype: str):
    import jax.numpy as jnp
    return A.AfmoeConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_hidden_layers=model["num_hidden_layers"],
        num_dense_layers=model["num_dense_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], num_experts=model["num_experts"],
        num_experts_per_tok=model["num_experts_per_tok"],
        num_shared_experts=model["num_shared_experts"],
        route_norm=model["route_norm"], route_scale=model["route_scale"],
        sliding_window=model["sliding_window"],
        global_attn_every_n_layers=model["global_attn_every_n_layers"],
        layer_types=tuple(model["layer_types"]),
        max_position_embeddings=model["max_position_embeddings"],
        rms_norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        mup_enabled=model["mup_enabled"], dtype=getattr(jnp, dtype))


class ReferenceWeights:
    """The program's two stacks of weights (``d_*``: dense layers, ``e_*``:
    expert layers) under the plain reference's names, one layer sliced at a
    time (``x @ W`` orientation on both sides)."""

    _ATTENTION = {
        "q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "gate_proj": "wg",
        "o_proj": "wo", "q_norm": "q_norm", "k_norm": "k_norm",
        "input_layernorm": "ln_in",
        "post_attention_layernorm": "ln_post_attn",
        "pre_mlp_layernorm": "ln_pre_mlp",
        "post_mlp_layernorm": "ln_post_mlp"}
    _SWIGLU = ("gate_proj", "up_proj", "down_proj")

    def __init__(self, params: Dict, num_dense_layers: int):
        self._p = params
        self._dense = num_dense_layers
        self.embed = params["embed"]
        self.norm = params["ln_f"]
        self.lm_head = params["lm_head"]

    def layer(self, i: int) -> Dict:
        dense = i < self._dense
        stack, j = ("d_", i) if dense else ("e_", i - self._dense)

        def mlp(prefix):
            return {ref: self._p[stack + prefix + ref.split("_")[0]][j]
                    for ref in self._SWIGLU}

        out = {ref: self._p[stack + own][j]
               for ref, own in self._ATTENTION.items()}
        out["mlp"] = mlp("w_") if dense else {
            "router": self._p["e_router"][j],
            "expert_bias": self._p["e_expert_bias"][j],
            "experts": mlp("we_"), "shared": mlp("ws_")}
        return out


class Server(_llama.Server):
    def __init__(self, config: Dict, chips: int, seed: int):
        import jax
        from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                                   GenerationConfig)
        from paddle_tpu.observability.runtime import recompiles
        from paddle_tpu.serving import ServingScheduler

        if chips != 1:
            raise ValueError("models.afmoe serves on one chip (every weight "
                             "replicated; no tensor or expert parallelism)")
        t0 = time.perf_counter()
        serving = config["serving"]
        self.cfg = cfg = afmoe_config(config, serving["dtype"])
        self.vocab_size = cfg.vocab_size
        self._recompiles = recompiles
        self._mesh = None
        # one jitted call on the device, the seed an ARGUMENT (as a
        # constant every seed would be a program of its own)
        self.params = jax.jit(
            lambda s: A.init_stacked_params(cfg, seed=s))(fold_seed(seed))
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
        self.sched = ServingScheduler(self.engine)
        jax.block_until_ready(self.engine.mgr.k_pages)
        self.load_seconds = {"weights": t1 - t0,
                             "engine": time.perf_counter() - t1}
        self._misses0 = 0.0

    def reference_weights(self) -> ReferenceWeights:
        return ReferenceWeights(self.params, self.cfg.num_dense_layers)
