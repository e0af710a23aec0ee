"""The serving system under test for a LongCat-Flash configuration: the SAME
``ContinuousBatchingEngine`` behind ``ServingScheduler`` as
``serve_llama.py`` drives, built from ``models.longcat_flash.
LongcatFlashConfig``: the engine takes the model's step AND its cache layout
(one latent array, no V, two cache layers a model layer) from the
configuration's class. Everything but the model's configuration, its weights
and their names under the plain reference is ``serve_llama.Server``'s.

The program is imported as this file is: a commit that cannot serve the
model fails here, at once, before any weight is drawn. Program names this
file calls beyond ``serve_llama.py``'s: ``models.longcat_flash.
{LongcatFlashConfig, init_stacked_params}`` and its weight names,
``serving.SchedulerConfig(max_queue_depth)`` and ``engine.mgr.pools``
(PERF.md section 3).
"""

from __future__ import annotations

import inspect
import time
from typing import Dict

from paddle_tpu.models import longcat_flash as F    # first: the module doc

from perfbench import harness

_llama = harness.load_module("perfbench/adapters/serve_llama.py")

LOOPS = _llama.LOOPS
enable_cache = _llama.enable_cache
fold_seed = _llama.fold_seed


def longcat_config(model: Dict, dtype: str):
    """The file's ``n_routed_experts`` is the experts HELD here (listed
    under ``reduced``); the router keeps ``published_n_routed_experts``
    routed outputs and ``zero_expert_num`` zero-compute ones."""
    import jax.numpy as jnp
    if model["attention_method"] != "MLA" \
            or model["zero_expert_type"] != "identity" \
            or model["attention_bias"]:
        raise ValueError("models.longcat_flash has latent attention without "
                         "bias and identity zero-compute experts only")
    return F.LongcatFlashConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        ffn_hidden_size=model["ffn_hidden_size"],
        expert_ffn_hidden_size=model["expert_ffn_hidden_size"],
        num_layers=model["num_layers"],
        num_attention_heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        mla_scale_q_lora=model["mla_scale_q_lora"],
        mla_scale_kv_lora=model["mla_scale_kv_lora"],
        n_routed_experts=model["published_n_routed_experts"],
        experts_held=model["n_routed_experts"],
        first_expert=model["first_expert"],
        zero_expert_num=model["zero_expert_num"], moe_topk=model["moe_topk"],
        routed_scaling_factor=model["routed_scaling_factor"],
        max_position_embeddings=model["max_position_embeddings"],
        rms_norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        dtype=getattr(jnp, dtype))


class ReferenceWeights:
    """The program's stacked weights under the plain reference's names, one
    layer sliced at a time (``x @ W`` orientation on both sides): a layer's
    two sub-layers, its router and its held experts. The program holds
    ``kv_b_proj`` as its two halves a head (``w_uk``, ``w_uv``); the
    reference gets them joined again, head-major."""

    _SUB = {"q_a_proj": "w_qa", "q_a_layernorm": "q_norm", "q_b_proj": "w_qb",
            "kv_a_proj": "w_kva", "kv_a_layernorm": "kv_norm", "o_proj": "wo",
            "input_layernorm": "ln_in", "post_attention_layernorm": "ln_post"}
    _SWIGLU = ("gate_proj", "up_proj", "down_proj")

    def __init__(self, params: Dict):
        self._p = params
        self.embed = params["embed"]
        self.norm = params["ln_f"]
        self.lm_head = params["lm_head"]

    def _sub(self, l: int, i: int) -> Dict:
        import jax.numpy as jnp
        out = {ref: self._p[own][l, i] for ref, own in self._SUB.items()}
        w_uk, w_uv = self._p["w_uk"][l, i], self._p["w_uv"][l, i]
        out["kv_b_proj"] = jnp.concatenate([w_uk, w_uv], axis=-1).reshape(
            w_uk.shape[0], -1)
        out["mlp"] = {ref: self._p["w_" + ref.split("_")[0]][l, i]
                      for ref in self._SWIGLU}
        return out

    def layer(self, l: int) -> Dict:
        return {"sub": [self._sub(l, 0), self._sub(l, 1)],
                "router": self._p["router"][l],
                "expert_bias": self._p["expert_bias"][l],
                "experts": {ref: self._p["we_" + ref.split("_")[0]][l]
                            for ref in self._SWIGLU}}


class Server(_llama.Server):
    def __init__(self, config: Dict, chips: int, seed: int):
        import jax
        from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                                   GenerationConfig)
        from paddle_tpu.observability.runtime import recompiles
        from paddle_tpu.serving import SchedulerConfig, ServingScheduler

        if chips != 1:
            raise ValueError("models.longcat_flash serves on one chip (every "
                             "weight replicated; a latent cache has no head "
                             "axis)")
        t0 = time.perf_counter()
        serving = config["serving"]
        self.cfg = cfg = longcat_config(config, serving["dtype"])
        self.vocab_size = cfg.vocab_size
        self._recompiles = recompiles
        self._mesh = None
        # one jitted call on the device, the seed an ARGUMENT (as a
        # constant every seed would be a program of its own)
        self.params = jax.jit(
            lambda s: F.init_stacked_params(cfg, seed=s))(fold_seed(seed))
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
        # the admission queue holds as many requests as the user has callers
        # (the scheduler's own 64 sheds half of 128 callers' first requests)
        self.sched = ServingScheduler(self.engine, SchedulerConfig(
            max_queue_depth=int(serving["max_queue_depth"])))
        jax.block_until_ready(self.engine.mgr.pools)
        self.load_seconds = {"weights": t1 - t0,
                             "engine": time.perf_counter() - t1}
        self._misses0 = 0.0

    def reference_weights(self) -> ReferenceWeights:
        return ReferenceWeights(self.params)
