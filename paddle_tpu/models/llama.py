"""Llama model family — the flagship workload (BASELINE.md workload #2).

Two faces over ONE weight set:

* **Imperative module** (`LlamaForCausalLM`): paddle-shaped nn.Layer built
  from the TP meta_parallel layers; runs eagerly, under jit.TrainStep, or
  under the GSPMD HybridTrainStep (dp/mp/sharding/sp via NamedShardings).
  Reference surface: PaddleNLP LlamaForCausalLM over
  fleet meta_parallel mp_layers (SURVEY.md §2.4, §3.2).

* **Functional hybrid step** (`build_hybrid_train_step`): the TP×PP×DP×SP
  compiled path — one shard_map program over the full mesh with Megatron-style
  explicit collectives for mp, the fill-drain ppermute pipeline for pp
  (parallel/pipeline.py), batch sharding for dp/sharding, and sequence
  sharding for sp. Used by fleet PP training, __graft_entry__.dryrun_multichip
  and bench.py.

Decoder math follows Llama-2: RMSNorm → QKV (GQA) → RoPE → causal flash
attention → out-proj → residual; RMSNorm → SwiGLU MLP → residual.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nn import functional as F
from ..nn.layer import Layer
from ..nn.common_layers import RMSNorm
from ..ops import rope as rope_ops
from ..ops import flash_attention as fa
from ..ops.rms_norm import rms_norm_array, rms_norm_replicated
from ..distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
from ..core.compat import shard_map

#: per-layer tensors in the stacked functional layout (leading L axis).
LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ln1", "ln2")


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: Any = jnp.float32
    # context-parallel attention flavor when sep_degree > 1:
    # "ulysses" (all_to_all head repartition) or "ring" (ppermute KV ring)
    sep_mode: str = "ulysses"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def llama2_7b(**over) -> LlamaConfig:
    return LlamaConfig(**{**dict(
        hidden_size=4096, intermediate_size=11008, num_hidden_layers=32,
        num_attention_heads=32, num_key_value_heads=32), **over})


def llama2_13b(**over) -> LlamaConfig:
    return LlamaConfig(**{**dict(
        hidden_size=5120, intermediate_size=13824, num_hidden_layers=40,
        num_attention_heads=40, num_key_value_heads=40), **over})


def llama_tiny(**over) -> LlamaConfig:
    return LlamaConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128), **over})


# ===========================================================================
# Imperative model
# ===========================================================================
class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, d = config.hidden_size, config.head_dim
        self.q_proj = ColumnParallelLinear(h, h, has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(
            h, config.num_key_value_heads * d, has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(
            h, config.num_key_value_heads * d, has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(h, h, has_bias=False, input_is_parallel=True)

    def forward(self, x, cos, sin):
        cfg = self.config
        b, s, _ = x.shape
        d = cfg.head_dim
        q = self.q_proj(x).reshape([b, s, -1, d])
        k = self.k_proj(x).reshape([b, s, -1, d])
        v = self.v_proj(x).reshape([b, s, -1, d])
        q, k = rope_ops.fused_rotary_position_embedding(q, k, cos, sin)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(out.reshape([b, s, -1]))


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.gate_proj = ColumnParallelLinear(h, m, has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(h, m, has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(m, h, has_bias=False, input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, cos, sin):
        x = x + self.self_attn(self.input_layernorm(x), cos, sin)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        from ..nn.layer import LayerList
        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        cfg = self.config
        s = input_ids.shape[1]
        cos, sin = rope_ops.build_rope_cache(s, cfg.head_dim, cfg.rope_theta)
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, cos, sin)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None  # logits via embed weightᵀ
        else:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=True)

    def forward(self, input_ids):
        h = self.llama(input_ids)
        if self.lm_head is None:
            from ..core import math_ops as M
            return M.matmul(h, self.llama.embed_tokens.weight, transpose_y=True)
        return self.lm_head(h)

    def compute_loss(self, input_ids, labels):
        logits = self(input_ids)
        return F.cross_entropy(
            logits.reshape([-1, self.config.vocab_size]),
            labels.reshape([-1]), ignore_index=-100)


# ===========================================================================
# Functional forward (serial; single-device oracle + graft entry)
# ===========================================================================
def forward_stacked(params: Dict[str, Any], ids, config: LlamaConfig):
    """Pure single-device forward over the stacked param layout → logits."""
    cos, sin = rope_ops.build_rope_cache(ids.shape[-1], config.head_dim,
                                         config.rope_theta)
    x = jnp.take(params["embed"], ids.astype(jnp.int32), axis=0)

    def body(carry, lp):
        out = _decoder_layer_manual(lp, carry, cos, sin, config=config,
                                    mp_axis=None, fsdp_axis=None)
        return out.astype(carry.dtype), None

    layer_params = {k: params[k] for k in LAYER_KEYS}
    x, _ = lax.scan(body, x, layer_params)
    x = _rms(x, params["ln_f"], config.rms_norm_eps)
    return jnp.einsum("bsh,hv->bsv", x, _dense(params["lm_head"]))


def loss_stacked(params: Dict[str, Any], ids, labels, config: LlamaConfig):
    logits = forward_stacked(params, ids, config).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels.astype(jnp.int32)[..., None],
                                 axis=-1)[..., 0]
    return -jnp.mean(picked)


# ===========================================================================
# Functional TP×PP×DP×SP hybrid step
# ===========================================================================
def init_stacked_params(config: LlamaConfig, seed: int = 0) -> Dict[str, Any]:
    """Weights in the stacked functional layout: per-layer tensors stacked on
    a leading L axis (pipeline shards slice it)."""
    L, h, m = config.num_hidden_layers, config.hidden_size, config.intermediate_size
    d = config.head_dim
    kvh = config.num_key_value_heads * d
    key = jax.random.key(seed)
    ks = jax.random.split(key, 12)
    std = 0.02
    dt = config.dtype

    def rnd(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    return {
        "embed": rnd(ks[0], (config.vocab_size, h)),
        "wq": rnd(ks[1], (L, h, h)),
        "wk": rnd(ks[2], (L, h, kvh)),
        "wv": rnd(ks[3], (L, h, kvh)),
        "wo": rnd(ks[4], (L, h, h)),
        "w_gate": rnd(ks[5], (L, h, m)),
        "w_up": rnd(ks[6], (L, h, m)),
        "w_down": rnd(ks[7], (L, m, h)),
        "ln1": jnp.ones((L, h), dt),
        "ln2": jnp.ones((L, h), dt),
        "ln_f": jnp.ones((h,), dt),
        "lm_head": rnd(ks[8], (h, config.vocab_size)),
    }


def param_count(config: LlamaConfig) -> int:
    """Parameter count of the stacked layout (embed + L decoder layers +
    final norm + lm_head) — the analytic twin of walking a real pytree,
    for capacity planning before any weights exist."""
    L, h, m = (config.num_hidden_layers, config.hidden_size,
               config.intermediate_size)
    kvh = config.num_key_value_heads * config.head_dim
    per_layer = 2 * h * h + 2 * h * kvh + 3 * h * m + 2 * h
    return (config.vocab_size * h + L * per_layer + h
            + h * config.vocab_size)


def param_nbytes(config: LlamaConfig) -> int:
    """Device bytes the stacked weights occupy at ``config.dtype`` — the
    ``weight_bytes`` input of the HBM capacity planner
    (``observability.memory.plan_capacity``); matches
    ``pytree_nbytes(init_stacked_params(config))`` exactly."""
    return param_count(config) * jnp.dtype(config.dtype).itemsize


def kv_geometry(config: LlamaConfig, page_size: int) -> Dict[str, int]:
    """The paged-KV geometry kwargs of the HBM capacity planner: one
    call site for "what does a page of this model cost" so planner
    examples, benches and the engine agree byte-for-byte."""
    return {
        "num_layers": config.num_hidden_layers,
        "num_kv_heads": config.num_key_value_heads,
        "head_dim": config.head_dim,
        "page_size": page_size,
        "dtype_bytes": jnp.dtype(config.dtype).itemsize,
    }


def stacked_param_specs(config: LlamaConfig) -> Dict[str, P]:
    """PartitionSpecs: L axis over pp, Megatron dims over mp, row-sharded big
    matrices additionally over 'sharding' (ZeRO-3 style weight sharding)."""
    return {
        "embed": P("mp", None),
        "wq": P("pp", ("dp", "sharding"), "mp"),
        "wk": P("pp", ("dp", "sharding"), "mp"),
        "wv": P("pp", ("dp", "sharding"), "mp"),
        "wo": P("pp", "mp", ("dp", "sharding")),
        "w_gate": P("pp", ("dp", "sharding"), "mp"),
        "w_up": P("pp", ("dp", "sharding"), "mp"),
        "w_down": P("pp", "mp", ("dp", "sharding")),
        "ln1": P("pp", None),
        "ln2": P("pp", None),
        "ln_f": P(),
        "lm_head": P(None, "mp"),
    }


def serving_param_specs(config: LlamaConfig) -> Dict[str, P]:
    """Megatron TP specs for the SERVING path: ``mp`` only (serving
    replicas have no dp/pp/sharding state — one replica = one TP mesh).
    Attention projections are column-parallel (head-output dim over
    ``mp``, whole heads per chip so the head-sharded paged KV pool lines
    up), ``wo``/``w_down`` row-parallel (XLA inserts the all-reduce),
    and ``embed``/``lm_head``/norms replicate so the packed-token gather
    and the per-row logits stay chip-local and bitwise identical to the
    single-chip program."""
    col, row = P(None, None, "mp"), P(None, "mp", None)
    return {
        "embed": P(), "lm_head": P(), "ln_f": P(),
        "ln1": P(None, None), "ln2": P(None, None),
        "wq": col, "wk": col, "wv": col,
        "w_gate": col, "w_up": col,
        "wo": row, "w_down": row,
    }


def shard_params_tp(params: Dict[str, Any], mesh: Mesh,
                    config: LlamaConfig) -> Dict[str, Any]:
    """Place a stacked-param dict onto a serving TP mesh
    (``serving_param_specs``). Weight-only-quantized leaves
    (``{"q", "scale"}`` from ``quantization.quantize_stacked_params``)
    shard ``q`` like the dense weight and ``scale`` (L, out) along the
    output dim for column-parallel weights (row-parallel scales
    replicate — their out dim is unsharded)."""
    specs = serving_param_specs(config)
    out: Dict[str, Any] = {}
    for k, v in params.items():
        spec = specs.get(k, P())
        if isinstance(v, dict):           # weight-only int8: {"q","scale"}
            # scale is (..., out): it shards along out exactly when the
            # dense weight is column-parallel (row-parallel/replicated
            # weights keep their out dim whole -> replicated scale)
            out_axis = spec[-1] if len(spec) == 3 else None
            scale_spec = P(*([None] * (v["scale"].ndim - 1) + [out_axis]))
            out[k] = {
                "q": jax.device_put(v["q"], NamedSharding(mesh, spec)),
                "scale": jax.device_put(
                    v["scale"], NamedSharding(mesh, scale_spec)),
            }
        else:
            out[k] = jax.device_put(v, NamedSharding(mesh, spec))
    return out


def _rms(x, w, eps):
    # fused Pallas rms_norm on TPU (ops/rms_norm.py), XLA ref path elsewhere
    return rms_norm_array(x, w, eps)


def _dense(w):
    """Materialize a possibly weight-only-quantized weight ({"q","scale"}
    from paddle_tpu.quantization.quantize_stacked_params) into its dense
    form. Called inside the per-layer scan body so only ONE layer's weight
    is dequantized at a time and XLA fuses the multiply into the consuming
    einsum — int8 storage halves the HBM bytes the decode loop waits on.
    Dense arrays pass through untouched."""
    if isinstance(w, dict):
        from ..quantization import weight_dequantize
        return weight_dequantize(w["q"], w["scale"])
    return w


def _mm_prefill(x, w):
    """Prefill-side matmul ``x @ w`` with the A8W8 fast path.

    Prefill is COMPUTE-bound (decode is bandwidth-bound), so for int8-
    quantized weights the dequantize-then-bf16-matmul of `_dense` wastes
    the MXU's 2x int8 throughput AND pays the dequant tax that made CB
    int8 LOSE to bf16 at mixed workloads (VERDICT r4 missing #4a). With
    FLAGS_serving_a8w8_prefill (default on) quantized weights run
    int8 x int8 -> int32 with per-token activation scales — the reference
    fused_multi_transformer_int8's prefill arrangement
    (fused_multi_transformer_int8_op.cu:§0). Decode keeps weight-only
    dequant: there the fused dequant is free and avoids per-step
    activation-quant noise."""
    if isinstance(w, dict):
        from ..flags import flag_value
        # t (dim -2) == 1 is the decode shape: stay weight-only there
        if flag_value("serving_a8w8_prefill") and w["q"].ndim == 2 \
                and x.ndim >= 2 and x.shape[-2] > 1:
            from ..ops.fused_transformer_block import _int8_mm
            return _int8_mm(x, w["q"], w["scale"])
    return jnp.einsum("...h,hd->...d", x, _dense(w))


def _decoder_layer_manual(p, x, cos, sin, config: LlamaConfig, mp_axis,
                          fsdp_axis, sep_axis=None):
    """One decoder layer inside shard_map. Weight locals: wq (h, h/mp) etc.
    (the fsdp axis shards the *contraction* dim h — all-gathered here, which
    is the ZeRO-3 gather; XLA overlaps it with the previous layer).

    When ``sep_axis`` is set, activations arrive sequence-sharded and
    attention runs Ulysses-style (SURVEY.md §5.7 mechanism 2): all_to_all
    repartitions (heads_local → seq_full) before attention and back after, so
    causal attention always sees the full sequence per head subset.
    """
    b, s, h = x.shape
    d = config.head_dim

    def gather_in(w):
        if fsdp_axis is not None:
            return lax.all_gather(w, fsdp_axis, axis=0, tiled=True)
        return w

    def gather_out(w):
        if fsdp_axis is not None:
            return lax.all_gather(w, fsdp_axis, axis=1, tiled=True)
        return w

    xn = _rms(x, p["ln1"], config.rms_norm_eps)
    q = jnp.einsum("bsh,hd->bsd", xn, gather_in(_dense(p["wq"])))
    k = jnp.einsum("bsh,hd->bsd", xn, gather_in(_dense(p["wk"])))
    v = jnp.einsum("bsh,hd->bsd", xn, gather_in(_dense(p["wv"])))
    nh_local = q.shape[-1] // d
    nkv_local = k.shape[-1] // d
    q = q.reshape(b, s, nh_local, d)
    k = k.reshape(b, s, nkv_local, d)
    v = v.reshape(b, s, nkv_local, d)
    q, k = rope_ops.apply_rope_array(q, k, cos, sin)
    sep_mode = getattr(config, "sep_mode", "ulysses")
    if sep_axis is not None and sep_mode == "ring":
        # blockwise ring attention: KV rotates over the sep ICI ring with
        # online-softmax merge (ops/ring_attention.py, SURVEY.md §5.7 (3))
        from ..ops import ring_attention as ra
        attn = ra.ring_attention_array(q, k, v, sep_axis, causal=True,
                                       scale=1.0 / math.sqrt(d))
    else:
        if sep_axis is not None:
            # (b, s_local, nh, d) -> (b, s_full, nh/sep, d)
            q, k, v = (lax.all_to_all(t, sep_axis, split_axis=2, concat_axis=1,
                                      tiled=True) for t in (q, k, v))
        attn = fa._sdpa_array(q, k, v, scale=1.0 / math.sqrt(d), causal=True)
        if sep_axis is not None:
            attn = lax.all_to_all(attn, sep_axis, split_axis=1, concat_axis=2,
                                  tiled=True)
    # named for remat_policy="offload", which streams this copy to host
    from jax.ad_checkpoint import checkpoint_name as _ckpt_name
    attn = _ckpt_name(attn, "attn_out")
    attn = attn.reshape(b, s, -1)
    out = jnp.einsum("bsd,dh->bsh", attn, gather_out(_dense(p["wo"])))
    if mp_axis is not None:
        out = lax.psum(out, mp_axis)
    # int8-quantized weights dequantize to f32 (weight_dequantize): pin
    # the residual carry dtype exactly like the serving scan paths do,
    # or every layer silently widens the whole activation stream to f32
    # (tpu-lint dtype-flow triage; no-op cast for dense bf16 weights)
    x = x + out.astype(x.dtype)

    xn = _rms(x, p["ln2"], config.rms_norm_eps)
    g = jnp.einsum("bsh,hm->bsm", xn, gather_in(_dense(p["w_gate"])))
    u = jnp.einsum("bsh,hm->bsm", xn, gather_in(_dense(p["w_up"])))
    dn = jnp.einsum("bsm,mh->bsh", jax.nn.silu(g) * u, gather_out(_dense(p["w_down"])))
    if mp_axis is not None:
        dn = lax.psum(dn, mp_axis)
    return x + dn.astype(x.dtype)


#: fsdp-sharded dim of each stacked layer weight (leading dim is L)
_ZG_DIM = {"wq": 1, "wk": 1, "wv": 1, "w_gate": 1, "w_up": 1,
           "wo": 2, "w_down": 2}


def build_hybrid_train_step(config: LlamaConfig, mesh: Mesh,
                            learning_rate: float = 1e-3,
                            remat: bool = True,
                            seq_shard: bool = False,
                            virtual_pp: int = 1,
                            remat_policy: str = "full",
                            pipeline_schedule: str = "fill_drain",
                            zero_gather: str = "per_layer",
                            k_steps: int = 1):
    """Returns (step_fn, init_fn).

    step_fn(params, opt_state, batch_ids, batch_labels) ->
        (loss, params, opt_state) — jitted, fully sharded.

    ``k_steps > 1`` compiles k optimizer steps into ONE dispatch
    (lax.scan over a leading k axis the batch arrays must then carry;
    the returned loss is the last step's). One host round-trip per k
    steps instead of per step.

    Parallelism inside: dp (batch), pp (ppermute pipeline: fill-drain, or
    the interleaved virtual-pipeline schedule when ``virtual_pp > 1`` —
    each pp stage holds virtual_pp strided layer chunks, cutting the
    bubble by that factor), mp (Megatron collectives), sharding (ZeRO-3
    weight sharding with per-layer all_gather), and — with
    ``seq_shard=True`` and a ``sep`` mesh axis — Ulysses context
    parallelism (activations sequence-sharded; all_to_all head/seq
    repartition around attention).
    Optimizer: fused AdamW (state sharded like the weights).

    ``remat_policy`` (with ``remat``): "full" (default) keeps a layer's
    input and the flash forward kernel's ``out`` and ``lse`` (one more
    (B, S, hidden) array a layer a microbatch) and recomputes the rest, so
    the backward never runs the S^2 kernel a second time; "dots" also
    keeps the matmul outputs; "offload" streams the attention output to
    pinned host memory.

    ``pipeline_schedule``: "fill_drain" (default; becomes the interleaved
    virtual-pipeline schedule when virtual_pp > 1) or "1f1b" — the
    memory-scheduled one-forward-one-backward program
    (parallel/pipeline.py::pipeline_1f1b): O(stages) activation memory
    instead of O(microbatches), the schedule the reference's
    PipelineParallel runs by default (SURVEY.md §2.4 PP row). 1f1b
    composes with dp/mp/sharding; virtual_pp and seq_shard are
    fill-drain/interleave-only.

    Note: with virtual_pp > 1 the stacked layer arrays are stored in the
    interleave-permuted order (init_fn applies it); checkpoints of these
    params carry that layout.
    """
    from ..parallel import pipeline as ppipe

    if pipeline_schedule not in ("fill_drain", "1f1b"):
        raise ValueError(f"unknown pipeline_schedule {pipeline_schedule!r}")
    if zero_gather not in ("per_layer", "per_step"):
        raise ValueError(f"unknown zero_gather {zero_gather!r} "
                         "(expected 'per_layer' or 'per_step')")
    if zero_gather == "per_step" and pipeline_schedule == "1f1b":
        raise ValueError("zero_gather='per_step' is a fill-drain-family "
                         "option (1f1b gathers per layer)")
    if remat_policy not in ("full", "dots", "offload"):
        raise ValueError(f"unknown remat_policy {remat_policy!r} "
                         "(expected 'full', 'dots' or 'offload')")
    if pipeline_schedule == "1f1b":
        if mesh.shape.get("pp", 1) <= 1:
            raise ValueError("pipeline_schedule='1f1b' needs a pp axis > 1")
        if virtual_pp > 1:
            raise ValueError("1f1b and virtual_pp are mutually exclusive "
                             "(interleave is a fill-drain-family schedule)")
        if seq_shard:
            raise ValueError("1f1b with sequence parallelism is not "
                             "supported; use the fill-drain schedule")

    pp = mesh.shape.get("pp", 1)
    mp = mesh.shape.get("mp", 1)
    sep = mesh.shape.get("sep", 1)
    sep_axis = "sep" if (seq_shard and sep > 1) else None
    if seq_shard and sep <= 1:
        raise ValueError("seq_shard=True requires a 'sep' mesh axis of size>1")
    sep_mode = getattr(config, "sep_mode", "ulysses")
    if sep_mode not in ("ulysses", "ring"):
        raise ValueError(f"unknown sep_mode {sep_mode!r} "
                         f"(expected 'ulysses' or 'ring')")
    if sep_axis is not None:
        nh, nkv = config.num_attention_heads, config.num_key_value_heads
        if sep_mode == "ulysses":
            # Ulysses repartitions heads over sep; ring never splits heads
            if nh % (mp * sep) or nkv % (mp * sep):
                raise ValueError(
                    f"Ulysses sep={sep} with mp={mp} needs heads divisible "
                    f"by mp*sep (got q={nh}, kv={nkv})")
        elif nh % mp or nkv % mp:
            raise ValueError(
                f"ring sep with mp={mp} needs heads divisible by mp "
                f"(got q={nh}, kv={nkv})")
    fsdp = mesh.shape.get("sharding", 1) * mesh.shape.get("dp", 1)
    mp_axis = "mp" if mp > 1 else None
    fsdp_axes = ("dp", "sharding")
    fsdp_axis = fsdp_axes if fsdp > 1 else None
    specs = stacked_param_specs(config)
    eps = config.rms_norm_eps

    vpp = max(int(virtual_pp), 1)
    if vpp > 1 and pp <= 1:
        raise ValueError("virtual_pp > 1 requires a pp mesh axis of size > 1")
    if config.num_hidden_layers % (pp * vpp):
        raise ValueError(
            f"num_hidden_layers {config.num_hidden_layers} must divide by "
            f"pp*virtual_pp = {pp * vpp}")
    layers_per_chunk = config.num_hidden_layers // (pp * vpp)
    if vpp > 1:
        # storage order: device-contiguous blocks hold strided model chunks
        layer_order = np.asarray(
            [c * layers_per_chunk + r
             for c in ppipe.interleave_chunk_order(pp, vpp)
             for r in range(layers_per_chunk)])
    else:
        layer_order = None

    # ---- closures shared by the fill-drain and 1f1b spmd bodies ------------
    def make_embed(params):
        """Token-embedding lookup; vocab-parallel over mp when sharded.
        Returns (embed_fn, vocab_shard_start, vocab_shard_size)."""
        if mp_axis is not None:
            per = params["embed"].shape[0]
            start = lax.axis_index(mp_axis) * per

            def embed(i):
                i32 = i.astype(jnp.int32) - start
                ok = (i32 >= 0) & (i32 < per)
                e = jnp.take(params["embed"], jnp.where(ok, i32, 0), axis=0)
                return lax.psum(jnp.where(ok[..., None], e, 0.0), mp_axis)

            return embed, start, per

        def embed(i):
            return jnp.take(params["embed"], i.astype(jnp.int32), axis=0)

        return embed, None, None

    def make_stage_fn(cos, sin, use_sep, stage_fsdp="default"):
        ax = sep_axis if use_sep else None
        fsdp = fsdp_axis if stage_fsdp == "default" else stage_fsdp

        def stage_fn(sparams, x):
            def layer_body(carry, lp):
                fn = functools.partial(_decoder_layer_manual, config=config,
                                       mp_axis=mp_axis, fsdp_axis=fsdp,
                                       sep_axis=ax)
                if remat:
                    if remat_policy == "dots":
                        # save matmul outputs, recompute elementwise/norms:
                        # backward skips the FLOP-heavy recompute of full
                        # remat at a modest activation-memory cost
                        fn = jax.checkpoint(
                            fn, policy=jax.checkpoint_policies.dots_saveable)
                    elif remat_policy == "offload":
                        # VERDICT r3 item 9: stream the attention outputs
                        # to pinned HOST memory during forward and fetch
                        # them back for backward — no recompute, no HBM
                        # residency (core/offload.py's memory kind)
                        fn = jax.checkpoint(
                            fn, policy=jax.checkpoint_policies
                            .save_and_offload_only_these_names(
                                names_which_can_be_saved=[],
                                names_which_can_be_offloaded=["attn_out"],
                                offload_src="device",
                                offload_dst="pinned_host"))
                    else:
                        # recompute everything but the flash forward: its
                        # out and lse are kept, so the backward re-derives
                        # q, k, v and does not run the S^2 kernel again
                        fn = jax.checkpoint(
                            fn, policy=jax.checkpoint_policies
                            .save_only_these_names(*fa.SAVED_RESIDUALS))
                return fn(lp, carry, cos, sin), None

            layer_params = {k: sparams[k] for k in LAYER_KEYS}
            x, _ = lax.scan(layer_body, x, layer_params)
            return x

        return stage_fn

    def head_ce(hp, y, lab):
        """ln_f + lm_head + token CE over arbitrary leading dims (mean)."""
        out = _rms(y, hp["ln_f"], eps)
        logits = jnp.einsum("...sh,hv->...sv", out, _dense(hp["lm_head"]))
        lg = logits.astype(jnp.float32)
        lab32 = lab.astype(jnp.int32)
        if mp_axis is not None:
            from ..distributed.meta_parallel.mp_layers import \
                vocab_parallel_ce_array
            return jnp.mean(vocab_parallel_ce_array(lg, lab32, mp_axis))
        logp = jax.nn.log_softmax(lg, axis=-1)
        picked = jnp.take_along_axis(logp, lab32[..., None],
                                     axis=-1)[..., 0]
        return -jnp.mean(picked)

    def spmd_loss(params, ids, labels):
        """Runs per-device inside shard_map. ids/labels: (M, mb_local, S_local)."""
        M, mb, S = ids.shape
        s_glob = S * sep if sep_axis is not None else S
        cos, sin = rope_ops.build_rope_cache(s_glob, config.head_dim,
                                             config.rope_theta)
        if sep_axis is not None:
            # RoPE runs pre-all_to_all on the local chunk: slice its positions
            off = lax.axis_index(sep_axis) * S
            cos = lax.dynamic_slice_in_dim(cos, off, S, axis=0)
            sin = lax.dynamic_slice_in_dim(sin, off, S, axis=0)

        embed, _, _ = make_embed(params)

        local = {k: params[k] for k in LAYER_KEYS}
        if zero_gather == "per_step" and fsdp_axis is not None:
            # ZeRO gather HOISTED above the microbatch loop and the remat
            # scope: weights gather ONCE per step (AD transposes it to one
            # reduce_scatter of the summed grads) instead of per microbatch
            # x remat replay — the dossier (benchmarks/bench_hybrid_cost.py)
            # measured the per-layer mode's sharding traffic scaling with
            # Lpd x M x replays and saturating the axis at pod microbatch
            # counts. Cost: the stage's full unsharded weights stay live
            # through backward (ZeRO-1-style memory for ZeRO-3 comm).
            local = {k: (lax.all_gather(v, fsdp_axis, axis=_ZG_DIM[k],
                                        tiled=True) if k in _ZG_DIM else v)
                     for k, v in local.items()}
            stage_fn = make_stage_fn(cos, sin, use_sep=True,
                                     stage_fsdp=None)
        else:
            stage_fn = make_stage_fn(cos, sin, use_sep=True)

        x = embed(ids)  # (M, mb, S, h)

        if pp > 1:
            if vpp > 1:
                # local leaves: (L/pp, ...) -> (vpp, layers_per_chunk, ...);
                # stage_fn scans whatever layer dim it receives, so it IS
                # the chunk function
                chunks = jax.tree_util.tree_map(
                    lambda a: a.reshape((vpp, layers_per_chunk) + a.shape[1:]),
                    local)
                out = ppipe.pipeline_spmd_interleaved(
                    stage_fn, chunks, x, vpp, axis_name="pp")
            else:
                out = ppipe.pipeline_spmd(stage_fn, local, x, axis_name="pp")
            out = ppipe.last_stage_broadcast(out, "pp")
        else:
            def micro_body(_, xm):
                return None, stage_fn(local, xm)
            _, out = lax.scan(micro_body, None, x)

        # lm_head spec P(None, 'mp') is sliced by shard_map, so logits are
        # vocab-sharded when mp>1 and head_ce runs the vocab-parallel CE
        loss = head_ce({"ln_f": params["ln_f"],
                        "lm_head": params["lm_head"]}, out, labels)
        # mean over dp/sharding batch shards (+ sep sequence shards)
        for ax in ("dp", "sharding"):
            if mesh.shape.get(ax, 1) > 1:
                loss = lax.pmean(loss, ax)
        if sep_axis is not None:
            loss = lax.pmean(loss, sep_axis)
        return loss

    def spmd_1f1b_loss_grads(params, ids, labels):
        """Per-device 1F1B: loss AND hand-scheduled grads in one program.

        The pipeline computes layer grads internally (jax.vjp per tick);
        the replication sums shard_map's AD transpose would have inserted
        (for replicated/partial-view tensors) are added explicitly below.
        """
        M, mb, S = ids.shape
        cos, sin = rope_ops.build_rope_cache(S, config.head_dim,
                                             config.rope_theta)
        embed, start, per = make_embed(params)
        stage_fn = make_stage_fn(cos, sin, use_sep=False)

        x = embed(ids)                                   # (M, mb, S, h)
        h = x.shape[-1]
        ids32 = ids.astype(jnp.int32)
        layer_params = {k: params[k] for k in LAYER_KEYS}
        head_params = {"ln_f": params["ln_f"],
                       "lm_head": params["lm_head"]}

        def gin_reducer(acc, gx, m_b):
            # embedding backward folded per backward tick: scatter-add this
            # microbatch's d loss/d x rows into the local vocab shard, so no
            # O(M) input-grad buffer rides the scan. gx is this mp slice's
            # PARTIAL gradient — psum first so every vocab shard sees the
            # full rows.
            g = gx.astype(jnp.float32)
            if mp_axis is not None:
                g = lax.psum(g, mp_axis)
            gf = g.reshape(-1, h)
            idx = lax.dynamic_index_in_dim(ids32, m_b, 0,
                                           keepdims=False).reshape(-1)
            if mp_axis is not None:
                local = idx - start
                ok = (local >= 0) & (local < per)
                return acc.at[jnp.where(ok, local, 0)].add(
                    jnp.where(ok[:, None], gf, 0.0))
            return acc.at[idx].add(gf)

        loss, lgrads, hgrads, gembed = ppipe.pipeline_1f1b(
            stage_fn, layer_params, x, labels, head_ce, axis_name="pp",
            head_params=head_params, strip_stage_dim=False,
            input_grad_reducer=gin_reducer,
            input_grad_init=jnp.zeros(params["embed"].shape, jnp.float32))
        loss = ppipe.last_stage_broadcast(loss, "pp")
        hgrads = jax.tree_util.tree_map(
            lambda a: ppipe.last_stage_broadcast(a, "pp"), hgrads)
        gembed = lax.psum(gembed, "pp")    # valid on stage 0 only

        if mp_axis is not None:
            # jax transposes psum as psum: the REPLICATED unit seed at the
            # loss head inflates by mp at its first psum crossing (the CE
            # denom/target psums), after which partial cotangents sum
            # correctly at every later crossing — so every grad below the
            # head is uniformly mp x too large. Rescale once.
            inv_mp = 1.0 / mesh.shape["mp"]
            lgrads = jax.tree_util.tree_map(lambda a: a * inv_mp, lgrads)
            hgrads = jax.tree_util.tree_map(lambda a: a * inv_mp, hgrads)
            gembed = gembed * inv_mp
            # ln grads are per-mp-slice partials (their consumers are the
            # column-sharded matmuls): sum them
            hgrads = {"ln_f": lax.psum(hgrads["ln_f"], mp_axis),
                      "lm_head": hgrads["lm_head"]}
            lgrads = {k: (lax.psum(v, mp_axis) if k in ("ln1", "ln2") else v)
                      for k, v in lgrads.items()}

        # batch shards: matmul grads arrive summed over (dp, sharding) via
        # the ZeRO all_gather transpose; replicated tensors need the psum;
        # everything needs 1/R for global-batch-mean semantics
        R = mesh.shape.get("dp", 1) * mesh.shape.get("sharding", 1)
        if R > 1:
            loss = lax.pmean(loss, ("dp", "sharding"))
            gembed = lax.psum(gembed, ("dp", "sharding"))
            hgrads = jax.tree_util.tree_map(
                lambda a: lax.psum(a, ("dp", "sharding")), hgrads)
            lgrads = {k: (lax.psum(v, ("dp", "sharding"))
                          if k in ("ln1", "ln2") else v)
                      for k, v in lgrads.items()}
            inv = 1.0 / R
            lgrads = {k: v * inv for k, v in lgrads.items()}
            hgrads = jax.tree_util.tree_map(lambda a: a * inv, hgrads)
            gembed = gembed * inv

        grads = dict(lgrads)
        grads["ln_f"] = hgrads["ln_f"]
        grads["lm_head"] = hgrads["lm_head"]
        grads["embed"] = gembed
        grads = {k: g.astype(params[k].dtype) for k, g in grads.items()}
        return loss, grads

    batch_in_spec = P(None, ("dp", "sharding"),
                      "sep" if sep_axis is not None else None)

    def loss_shardmapped(params, ids, labels):
        f = shard_map(
            spmd_loss, mesh=mesh,
            in_specs=(specs, batch_in_spec, batch_in_spec),
            out_specs=P(), check_vma=False)
        return f(params, ids, labels)

    def loss_and_grads_1f1b(params, ids, labels):
        f = shard_map(
            spmd_1f1b_loss_grads, mesh=mesh,
            in_specs=(specs, batch_in_spec, batch_in_spec),
            out_specs=(P(), specs), check_vma=False)
        return f(params, ids, labels)

    # --- fused AdamW over the sharded pytree --------------------------------
    b1, b2, adam_eps, wd = 0.9, 0.95, 1e-8, 0.1

    def init_fn(seed: int = 0):
        params = init_stacked_params(config, seed)
        if layer_order is not None:
            params = {k: (v[layer_order] if k in LAYER_KEYS else v)
                      for k, v in params.items()}
        params = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                  for k, v in params.items()}
        opt_state = {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree_util.tree_map(lambda v: jnp.zeros_like(v, jnp.float32), params),
            "v": jax.tree_util.tree_map(lambda v: jnp.zeros_like(v, jnp.float32), params),
        }
        return params, opt_state

    state_specs = {"step": P(), "m": specs, "v": specs}

    def step(params, opt_state, ids, labels):
        if pipeline_schedule == "1f1b":
            loss, grads = loss_and_grads_1f1b(params, ids, labels)
        else:
            loss, grads = jax.value_and_grad(loss_shardmapped)(
                params, ids, labels)
        t = opt_state["step"] + 1

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m2 = b1 * m + (1 - b1) * g32
            v2 = b2 * v + (1 - b2) * g32 * g32
            mh = m2 / (1 - b1 ** t.astype(jnp.float32))
            vh = v2 / (1 - b2 ** t.astype(jnp.float32))
            p2 = p.astype(jnp.float32) - learning_rate * (
                mh / (jnp.sqrt(vh) + adam_eps) + wd * p.astype(jnp.float32))
            return p2.astype(p.dtype), m2, v2

        new_p, new_m, new_v = {}, {}, {}
        for k in params:
            new_p[k], new_m[k], new_v[k] = upd(params[k], grads[k],
                                               opt_state["m"][k],
                                               opt_state["v"][k])
        return loss, new_p, {"step": t, "m": new_m, "v": new_v}

    ns = lambda spec_tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
    if k_steps > 1:
        # k TRAINING STEPS per dispatch: one lax.scan over a leading
        # k-axis of the batch with the (params, opt_state) carry donated.
        # Amortizes the per-dispatch host cost (the same lever as
        # jit.TrainStep.multi_step).
        # step_fn(params, opt_state, ids, labels) with ids/labels carrying
        # a leading k axis; returns the LAST step's loss.
        def multi(params, opt_state, ids, labels):
            def body(carry, batch):
                p, o = carry
                loss, p, o = step(p, o, batch[0], batch[1])
                return (p, o), loss
            (p, o), losses = jax.lax.scan(
                body, (params, opt_state), (ids, labels))
            return losses[-1], p, o

        kb_spec = P(None, *batch_in_spec)
        step_jit = jax.jit(
            multi,
            in_shardings=(ns(specs), ns(state_specs), ns(kb_spec), ns(kb_spec)),
            out_shardings=(NamedSharding(mesh, P()), ns(specs), ns(state_specs)),
            donate_argnums=(0, 1),
        )
        return step_jit, init_fn
    step_jit = jax.jit(
        step,
        in_shardings=(ns(specs), ns(state_specs), ns(batch_in_spec), ns(batch_in_spec)),
        out_shardings=(NamedSharding(mesh, P()), ns(specs), ns(state_specs)),
        donate_argnums=(0, 1),
    )
    return step_jit, init_fn


def microbatch(ids: np.ndarray, labels: np.ndarray, num_micro: int):
    """(B, S) -> (M, B/M, S)."""
    B = ids.shape[0]
    assert B % num_micro == 0
    return (ids.reshape(num_micro, B // num_micro, -1),
            labels.reshape(num_micro, B // num_micro, -1))


# ===========================================================================
# Serving: the ragged step over the paged KV pool (ops/paged_attention.py)
# ===========================================================================
def ragged_step(params, ids, token_row, positions, kv_lens, last_idx,
                k_pages, v_pages, block_tables, config: LlamaConfig,
                mesh: Optional[Mesh] = None, mp_axis: str = "mp",
                logits_epilogue=None):
    """One forward over a RAGGED packed token batch — the unified model
    step behind the engine's single-dispatch serving loop.

    Mixed prefill+decode in one program: every live row contributes a
    span of the flat token axis (a decode row its one new token, a
    prefill row the next chunk of its prompt — a warm/COW suffix row is
    just "a row whose first position > 0"). Rope is taken at each
    token's absolute position, K/V scatter into the row's pages, and
    attention is the ragged paged kernel's one mask rule
    ``key_pos <= position`` (ops.paged_attention.ragged_paged_attention),
    which subsumes the in-prompt causal mask, the suffix offset mask and
    the decode ``kv_len`` mask. The compiled shape depends only on
    (T, rows, table width) — never on the request mix.

    ids:       (T,) int32 packed tokens (pad slots: anything)
    token_row: (T,) int32 owning row per token; -1 = pad slot
    positions: (T,) int32 absolute KV position per token
    kv_lens:   (R,) int32 per-row attendable span this call (0 = idle)
    last_idx:  (C,) int32 flat token indices to take logits at. The
               unified engine passes one per row (C == R, each row's
               last token); the speculative engine passes PER-CANDIDATE
               indices (C == R * (k+1)) — every token of a drafted span
               yields its own next-token logits, which is what turns the
               single dispatch into the draft verifier. Unused entries
               may point anywhere; callers mask the resulting logits.
    k_pages/v_pages: (L, P, page, nkv, d); block_tables: (R, max_pages)
    Returns (logits (C, V), k_pages', v_pages').

    Multi-chip TP (``mesh`` given, mp degree > 1): weights are placed by
    ``shard_params_tp`` and the paged pools head-sharded over ``mp_axis``
    (``PagedKVCacheManager(mesh=...)``) — on the XLA path GSPMD
    partitions every einsum/gather from those layouts alone (attention
    is head-parallel, ``wo``/``w_down`` become partial-sum all-reduces),
    so the traced program here is UNCHANGED and the mesh is only
    forwarded to the two Pallas kernels, which cannot be auto-partitioned
    and run under ``shard_map`` instead: attention with each chip's GQA
    group slice, ``rms_norm`` replicated (``rms_norm_replicated``).
    """
    from ..ops import paged_attention as pa

    def rms(xv, wv):
        # activations and norm weights are replicated over the TP mesh
        return rms_norm_replicated(xv, wv, config.rms_norm_eps, mesh)

    t = ids.shape[0]
    d = config.head_dim
    page = k_pages.shape[2]
    n_rows, width = block_tables.shape
    s_max = width * page
    cos_full, sin_full = rope_ops.build_rope_cache(s_max, config.head_dim,
                                                   config.rope_theta)
    # clamp: over-decoded tokens past the table span land in the last
    # slot (their outputs are trimmed by the host)
    pos_c = jnp.minimum(positions.astype(jnp.int32), s_max - 1)
    cos = jnp.take(cos_full, pos_c, axis=0)[None]          # (1, T, d)
    sin = jnp.take(sin_full, pos_c, axis=0)[None]
    x = jnp.take(params["embed"], ids.astype(jnp.int32), axis=0)[None]

    valid = token_row >= 0
    row_c = jnp.clip(token_row.astype(jnp.int32), 0, n_rows - 1)
    page_idx = pos_c // page
    page_off = pos_c % page
    phys = jnp.take(block_tables.reshape(-1), row_c * width + page_idx)
    phys = jnp.where(valid, phys, 0)                       # pads -> page 0

    # Pools travel FLAT (L*P, page, nkv, d) in the scan CARRY with
    # per-layer page-id offsets l*P: as scan xs/ys XLA would write fresh
    # pool buffers, a full copy of both pools per step; carried scatters
    # update in place. The manager reserves page 0, so every layer slab's
    # page l*P+0 is the garbage page and padded block-table slots stay
    # safe after the offset.
    n_layers, pool_p = k_pages.shape[0], k_pages.shape[1]
    kp_flat = k_pages.reshape((n_layers * pool_p,) + k_pages.shape[2:])
    vp_flat = v_pages.reshape((n_layers * pool_p,) + v_pages.shape[2:])
    # the ragged kernel's row groups and work list: once a step, every layer
    groups = pa.ragged_row_groups(block_tables, token_row, pos_c, kv_lens,
                                  page)

    def body(carry, lp_l):
        xc, kp, vp = carry
        lp, l = lp_l
        xn = rms(xc, lp["ln1"])
        q = _mm_prefill(xn, lp["wq"]).reshape(1, t, -1, d)
        k = _mm_prefill(xn, lp["wk"]).reshape(1, t, -1, d)
        v = _mm_prefill(xn, lp["wv"]).reshape(1, t, -1, d)
        q, k = rope_ops.apply_rope_array(q, k, cos, sin)
        # scatter FIRST: every token (decode and prefill alike) attends
        # through the page gather, its own fresh K/V included
        kp = kp.at[phys + l * pool_p, page_off].set(k[0].astype(kp.dtype))
        vp = vp.at[phys + l * pool_p, page_off].set(v[0].astype(vp.dtype))
        attn = pa.ragged_paged_attention(
            q[0], kp, vp, block_tables + l * pool_p, token_row, pos_c,
            kv_lens, scale=1.0 / math.sqrt(d),
            mesh=mesh, mp_axis=mp_axis, groups=groups)     # (T, nh, d)
        xo = xc + _mm_prefill(attn.reshape(1, t, -1),
                              lp["wo"]).astype(xc.dtype)
        xn2 = rms(xo, lp["ln2"])
        g = _mm_prefill(xn2, lp["w_gate"])
        u = _mm_prefill(xn2, lp["w_up"])
        xo = xo + jnp.einsum("btm,mh->bth", jax.nn.silu(g) * u,
                             _dense(lp["w_down"]))
        # int8-quantized weights dequantize to f32; keep the carry dtype
        return (xo.astype(xc.dtype), kp, vp), None

    layer_params = {k: params[k] for k in LAYER_KEYS}
    (x, kp_flat, vp_flat), _ = lax.scan(
        body, (x, kp_flat, vp_flat),
        (layer_params, jnp.arange(n_layers)))
    x = rms(x, params["ln_f"])
    # lm_head over ONLY each row's last token: (R, h) @ (h, V), not the
    # full (T, V) logits
    h_last = jnp.take(x[0], last_idx.astype(jnp.int32), axis=0)
    logits = jnp.einsum("rh,hv->rv", h_last, _dense(params["lm_head"]))
    if logits_epilogue is not None:
        # in-program hook over the per-row logits (e.g. the grammar
        # mask of inference.constrain — applied BEFORE any sampling
        # epilogue so constrained rows renormalize over legal tokens)
        logits = logits_epilogue(logits)
    return (logits, kp_flat.reshape(k_pages.shape),
            vp_flat.reshape(v_pages.shape))
