"""The serving system under test: ``ContinuousBatchingEngine`` behind
``ServingScheduler``, driven by the scheduler's documented single-threaded
loop (``submit`` ... ``step(params)``).

Adapters are the ONLY files of the benchmark that import ``paddle_tpu``.
Every program name this file calls is listed in PERF.md section 3; a
refactor keeps those names or asks a ``benchmark`` issue first.

The configuration file gives the model's published sizes and the USER's
choices (slots, context, KV pool, prefix cache, dtype). No engine default is
pinned: chunk, step_tokens, page_size, unified, fused_tail, speculative are
whatever the program's defaults are at this commit, so that a PR which
changes a default is measured.
"""

from __future__ import annotations

import gc
import inspect
import time
from typing import Callable, Dict, List, Optional

import numpy as np

LOOPS = ("open", "batch")


def llama_config(model: Dict, dtype: str):
    import jax.numpy as jnp
    from paddle_tpu.models import llama as L
    heads = model["num_attention_heads"]
    if model.get("head_dim", model["hidden_size"] // heads) \
            != model["hidden_size"] // heads:
        raise ValueError("LlamaConfig derives head_dim = hidden_size / heads")
    if model.get("sliding_window") is not None:
        raise ValueError("the engine has no sliding-window attention")
    return L.LlamaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_hidden_layers=model["num_hidden_layers"],
        num_attention_heads=heads,
        num_key_value_heads=model["num_key_value_heads"],
        max_position_embeddings=model["max_position_embeddings"],
        rms_norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        tie_word_embeddings=model["tie_word_embeddings"],
        dtype=getattr(jnp, dtype))


class ReferenceWeights:
    """The program's stacked weights under the plain reference's names, one
    layer sliced at a time (``x @ W`` orientation on both sides)."""

    _NAMES = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_proj": "wo",
              "gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down",
              "input_layernorm": "ln1", "post_attention_layernorm": "ln2"}

    def __init__(self, params: Dict):
        self._p = params
        self.embed = params["embed"]
        self.norm = params["ln_f"]
        self.lm_head = params["lm_head"]

    def layer(self, i: int) -> Dict:
        return {ref: self._p[own][i] for ref, own in self._NAMES.items()}


def fold_seed(seed: int) -> int:
    """``--seed`` may be larger than int32 holds, which is what a Python int
    becomes as the argument of a jitted call (no x64). A seed below 2**31 is
    itself, so it draws the weights it always drew; a larger one is folded
    into that range."""
    return seed if seed < 2 ** 31 else seed % (2 ** 31 - 1)


def enable_cache() -> str:
    from paddle_tpu.compile_cache import enable_compile_cache
    return enable_compile_cache()


class Server:
    def __init__(self, config: Dict, chips: int, seed: int):
        import jax
        from jax.sharding import NamedSharding
        from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                                   GenerationConfig)
        from paddle_tpu.models import llama as L
        from paddle_tpu.observability.runtime import recompiles
        from paddle_tpu.parallel.mesh import serving_mesh
        from paddle_tpu.serving import ServingScheduler

        t0 = time.perf_counter()
        serving = config["serving"]
        self.cfg = llama_config(config, serving["dtype"])
        self.vocab_size = self.cfg.vocab_size
        self._recompiles = recompiles
        self._mesh = (serving_mesh(chips, jax.devices()[:chips])
                      if chips > 1 else None)
        shardings = None if self._mesh is None else {
            k: NamedSharding(self._mesh, spec)
            for k, spec in L.serving_param_specs(self.cfg).items()}
        # one jitted call, on the device(s), in the served dtype; under a
        # mesh each chip draws its own shard. The seed is an ARGUMENT: as a
        # constant it would make every seed a program of its own, compiled
        # anew (34 s on four chips, PR 22)
        cfg = self.cfg
        self.params = jax.jit(
            lambda s: L.init_stacked_params(cfg, seed=s),
            out_shardings=shardings)(fold_seed(seed))
        jax.block_until_ready(self.params)
        t1 = time.perf_counter()
        page = inspect.signature(
            ContinuousBatchingEngine.__init__).parameters["page_size"].default
        self.engine = ContinuousBatchingEngine(
            self.cfg, GenerationConfig(seed=fold_seed(seed)),
            num_slots=int(serving["num_slots"]),
            max_seq_len=int(serving["max_seq_len"]),
            num_pages=int(serving["kv_pool_tokens"]) // page + 1,
            prefix_cache=bool(serving["prefix_cache"]), mesh=self._mesh)
        self.sched = ServingScheduler(self.engine)
        jax.block_until_ready(self.engine.mgr.k_pages)
        #: where the load time went (set-up only the program can shorten)
        self.load_seconds = {"weights": t1 - t0,
                             "engine": time.perf_counter() - t1}
        self._misses0 = 0.0

    # -- the loop's three calls ---------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               on_token: Callable[[int], None]):
        return self.sched.submit(prompt, max_new_tokens=max_new_tokens,
                                 on_token=on_token)

    def step(self) -> None:
        self.sched.step(self.params)

    def busy(self) -> bool:
        return self.sched.pending > 0 and not self.sched.degraded

    # -- what a request came to ---------------------------------------------
    @staticmethod
    def outcome(handle) -> Optional[str]:
        """None while running, "ok" when complete, else what went wrong."""
        if not handle.done:
            return None
        if handle.stream.error is not None:
            return repr(handle.stream.error)
        return "ok" if handle.state == "done" else handle.state

    @staticmethod
    def tokens(handle) -> List[int]:
        return handle.stream.tokens

    # -- counters -----------------------------------------------------------
    def begin_window(self) -> None:
        """The window's own counters: compile misses from here on, and a
        fresh queue-wait histogram (warm-up admissions are not the window's)."""
        from paddle_tpu.core.histogram import Histogram
        self._misses0 = self._recompiles.count("cbe.unified_step")
        self.sched.metrics.histograms["queue_wait_ms"] = Histogram()

    def counters(self) -> Dict[str, float]:
        m = self.sched.metrics
        qw = m.histograms["queue_wait_ms"]
        out = {
            "recompiles_in_window":
                self._recompiles.count("cbe.unified_step") - self._misses0,
            "step_failures_total": m.counters.get("step_failures_total", 0),
            "requests_shed_total": m.shed_total,
            "queue_wait_count": qw.count,
            "queue_depth": self.sched.queue_depth,
            "inflight": self.sched.inflight,
        }
        if qw.count:
            out["queue_wait_p50_ms"] = qw.percentile(0.5)
        if self.engine.cache is not None:
            snap = self.engine.cache.snapshot()
            out.update({f"prefix_cache.{k}": v for k, v in snap.items()
                        if isinstance(v, (int, float))})
        return out

    def problems(self) -> List[str]:
        """chip_smoke's checks: the scheduler turns a failing engine.step
        into drained requests and a normal return, so the benchmark asks."""
        out = []
        if self.sched.degraded:
            out.append("scheduler degraded: engine.step failed repeatedly")
        n = self.sched.metrics.counters.get("step_failures_total", 0)
        if n:
            out.append(f"step_failures_total = {n}")
        return out

    def kernels(self) -> Dict[str, int]:
        """Pallas kernels in the lowering of the step the engine serves
        with, by ``pallas_call`` name."""
        from paddle_tpu.ops._common import mosaic_kernels
        return mosaic_kernels(self.engine.lower_unified_step())

    # -- after the window ---------------------------------------------------
    def release_engine(self) -> None:
        """Drop the engine and its KV pool (the reference needs the room);
        the weights stay."""
        self.engine.token_callback = self.engine.finish_callback = None
        self.engine = self.sched = None
        gc.collect()

    def reference_weights(self) -> ReferenceWeights:
        return ReferenceWeights(self.params)
