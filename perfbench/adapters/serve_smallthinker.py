"""The serving system under test for a SmallThinker configuration: the SAME
``ContinuousBatchingEngine`` behind ``ServingScheduler`` as
``serve_llama.py`` drives, built from ``models.smallthinker.
SmallThinkerConfig``: the engine takes the model's step from the
configuration's class. Everything but the model's configuration, its weights
and their names under the plain reference is ``serve_llama.Server``'s.

The program is imported as this file is: a commit that cannot serve the
model fails here, at once, before any weight is drawn. Program names this
file calls beyond ``serve_llama.py``'s: ``models.smallthinker.
{SmallThinkerConfig, init_stacked_params}`` and its weight names,
``serving.SchedulerConfig(max_queue_depth)``,
``engine.cache.snapshot()["cached_tokens"]`` (PERF.md section 3).
"""

from __future__ import annotations

import inspect
import time
from typing import Dict

from paddle_tpu.models import smallthinker as S     # first: the module doc

from perfbench import harness

_llama = harness.load_module("perfbench/adapters/serve_llama.py")

LOOPS = _llama.LOOPS
enable_cache = _llama.enable_cache
fold_seed = _llama.fold_seed


def smallthinker_config(model: Dict, dtype: str):
    """The file holds the published layouts whole (52 layers); a stage of
    ``num_hidden_layers`` layers holds layers 0 .. n - 1, so it takes their
    first n entries."""
    import jax.numpy as jnp
    n = model["num_hidden_layers"]
    return S.SmallThinkerConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_hidden_layers=n,
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        moe_ffn_hidden_size=model["moe_ffn_hidden_size"],
        moe_num_primary_experts=model["moe_num_primary_experts"],
        moe_num_active_primary_experts=model[
            "moe_num_active_primary_experts"],
        moe_primary_router_apply_softmax=model[
            "moe_primary_router_apply_softmax"],
        norm_topk_prob=model["norm_topk_prob"],
        rope_layout=tuple(model["rope_layout"][:n]),
        sliding_window_layout=tuple(model["sliding_window_layout"][:n]),
        sliding_window_size=model["sliding_window_size"],
        rope_theta=model["rope_theta"], rms_norm_eps=model["rms_norm_eps"],
        tie_word_embeddings=model["tie_word_embeddings"],
        max_position_embeddings=model["max_position_embeddings"],
        dtype=getattr(jnp, dtype))


class ReferenceWeights:
    """The program's stacked weights under the plain reference's names, one
    layer sliced at a time (``x @ W`` orientation on both sides)."""

    _OWN = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_proj": "wo",
            "input_layernorm": "ln_in",
            "post_attention_layernorm": "ln_post", "router": "router"}
    _EXPERTS = {"gate_proj": "we_gate", "up_proj": "we_up",
                "down_proj": "we_down"}

    def __init__(self, params: Dict):
        self._p = params
        self.embed = params["embed"]
        self.norm = params["ln_f"]
        self.lm_head = params["lm_head"]

    def layer(self, l: int) -> Dict:
        out = {ref: self._p[own][l] for ref, own in self._OWN.items()}
        out["experts"] = {ref: self._p[own][l]
                          for ref, own in self._EXPERTS.items()}
        return out


class Server(_llama.Server):
    def __init__(self, config: Dict, chips: int, seed: int):
        import jax
        from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                                   GenerationConfig)
        from paddle_tpu.observability.runtime import recompiles
        from paddle_tpu.serving import SchedulerConfig, ServingScheduler

        if chips != 1:
            raise ValueError("models.smallthinker serves on one chip (every "
                             "weight replicated; no tensor or expert "
                             "parallelism)")
        t0 = time.perf_counter()
        serving = config["serving"]
        self.cfg = cfg = smallthinker_config(config, serving["dtype"])
        self.vocab_size = cfg.vocab_size
        self._recompiles = recompiles
        self._mesh = None
        # one jitted call on the device, the seed an ARGUMENT (as a
        # constant every seed would be a program of its own)
        self.params = jax.jit(
            lambda s: S.init_stacked_params(cfg, seed=s))(fold_seed(seed))
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
        self.sched = ServingScheduler(self.engine, SchedulerConfig(
            max_queue_depth=int(serving["max_queue_depth"])))
        jax.block_until_ready(self.engine.mgr.pools)
        self.load_seconds = {"weights": t1 - t0,
                             "engine": time.perf_counter() - t1}
        self._misses0 = 0.0
        self._cached0 = 0

    def _cached_tokens(self) -> int:
        return int(self.engine.cache.snapshot()["cached_tokens"])

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
        return ReferenceWeights(self.params)
