"""The serving system under test for an A.X-K1 configuration: the SAME
``ContinuousBatchingEngine`` behind ``ServingScheduler`` as
``serve_llama.py`` drives, built from ``models.axk1.Axk1Config``: the engine
takes the model's step AND its cache layout (one latent array, no V) from
the configuration's class. Everything but the model's configuration, its
weights, their names under the plain reference and the window's share of
cached prompt tokens is ``serve_llama.Server``'s.

The program is imported as this file is: a commit that cannot serve the
model fails here, at once, before any weight is drawn. Program names this
file calls beyond ``serve_llama.py``'s: ``models.axk1.{Axk1Config,
init_stacked_params}`` and its weight names (PERF.md section 3).
"""

from __future__ import annotations

import inspect
import time
from typing import Dict

from paddle_tpu.models import axk1 as X       # first: see the module doc

from perfbench import harness

_llama = harness.load_module("perfbench/adapters/serve_llama.py")

LOOPS = _llama.LOOPS
enable_cache = _llama.enable_cache
fold_seed = _llama.fold_seed


def axk1_config(model: Dict, dtype: str):
    """The file's ``n_routed_experts`` is the experts HELD here (listed
    under ``reduced``); the router keeps ``published_n_routed_experts``."""
    import jax.numpy as jnp
    return X.Axk1Config(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_hidden_layers=model["num_hidden_layers"],
        first_k_dense_replace=model["first_k_dense_replace"],
        num_attention_heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        n_routed_experts=model["published_n_routed_experts"],
        experts_held=model["n_routed_experts"],
        first_expert=model["first_expert"],
        n_shared_experts=model["n_shared_experts"],
        num_experts_per_tok=model["num_experts_per_tok"],
        n_group=model["n_group"], topk_group=model["topk_group"],
        norm_topk_prob=model["norm_topk_prob"],
        routed_scaling_factor=model["routed_scaling_factor"],
        max_position_embeddings=model["max_position_embeddings"],
        rms_norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        rope_scaling=model["rope_scaling"], dtype=getattr(jnp, dtype))


class ReferenceWeights:
    """The program's two stacks of weights (``d_*``: dense layers, ``e_*``:
    expert layers) under the plain reference's names, one layer sliced at a
    time (``x @ W`` orientation on both sides). The program holds
    ``kv_b_proj`` as its two halves a head (``w_uk``, ``w_uv``); the
    reference gets them joined again, head-major."""

    _ATTENTION = {
        "q_a_proj": "w_qa", "q_a_layernorm": "q_norm", "q_b_proj": "w_qb",
        "kv_a_proj": "w_kva", "kv_a_layernorm": "kv_norm", "o_proj": "wo",
        "input_layernorm": "ln_in", "post_attention_layernorm": "ln_post"}
    _SWIGLU = ("gate_proj", "up_proj", "down_proj")

    def __init__(self, params: Dict, num_dense_layers: int):
        self._p = params
        self._dense = num_dense_layers
        self.embed = params["embed"]
        self.norm = params["ln_f"]
        self.lm_head = params["lm_head"]

    def layer(self, i: int) -> Dict:
        import jax.numpy as jnp
        dense = i < self._dense
        stack, j = ("d_", i) if dense else ("e_", i - self._dense)

        def mlp(prefix):
            return {ref: self._p[stack + prefix + ref.split("_")[0]][j]
                    for ref in self._SWIGLU}

        out = {ref: self._p[stack + own][j]
               for ref, own in self._ATTENTION.items()}
        w_uk, w_uv = self._p[stack + "w_uk"][j], self._p[stack + "w_uv"][j]
        out["kv_b_proj"] = jnp.concatenate([w_uk, w_uv], axis=-1).reshape(
            w_uk.shape[0], -1)
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
            raise ValueError("models.axk1 serves on one chip (every weight "
                             "replicated; a latent cache has no head axis)")
        t0 = time.perf_counter()
        serving = config["serving"]
        self.cfg = cfg = axk1_config(config, serving["dtype"])
        self.vocab_size = cfg.vocab_size
        self._recompiles = recompiles
        self._mesh = None
        # one jitted call on the device, the seed an ARGUMENT (as a
        # constant every seed would be a program of its own)
        self.params = jax.jit(
            lambda s: X.init_stacked_params(cfg, seed=s))(fold_seed(seed))
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
        jax.block_until_ready(self.engine.mgr.pools)
        self.load_seconds = {"weights": t1 - t0,
                             "engine": time.perf_counter() - t1}
        self._misses0 = 0.0
        self._cached0 = 0

    def _cached_tokens(self) -> int:
        cache = self.engine.cache
        return 0 if cache is None else int(cache.snapshot()["cached_tokens"])

    def begin_window(self) -> None:
        super().begin_window()
        self._cached0 = self._cached_tokens()

    def counters(self) -> Dict[str, float]:
        out = super().counters()
        # prompt tokens the prefix cache served to requests admitted in the
        # window (the warm-up's are not the window's)
        out["prefix_cache.window_cached_tokens"] = \
            self._cached_tokens() - self._cached0
        return out

    def reference_weights(self) -> ReferenceWeights:
        return ReferenceWeights(self.params, self.cfg.first_k_dense_replace)
