"""AFMoE decoder (Arcee Trinity family, ``model_type: afmoe``) on the serving
path: the stacked functional weights and the ragged model step the
continuous-batching engine dispatches, with ``models.llama.ragged_step``'s
signature.

The layer, as ``perfbench/reference/afmoe.py`` computes it too (x: tokens x
hidden):

- ``x0 = embed[ids] * sqrt(hidden)`` (``mup_enabled``); ``logits = rms(x_L;
  ln_f) @ lm_head`` (untied).
- attention: ``a = rms(x; ln_in)``; ``q, k, v, g = a Wq, a Wk, a Wv, a Wg``;
  RMS norm of ``q`` and ``k`` over ``head_dim``; rope (absolute positions) on
  ``sliding_attention`` layers, NONE on ``full_attention`` layers; causal
  softmax attention, grouped KV heads, on sliding layers also ``key_pos > pos
  - sliding_window``; ``x = x + rms((attn * sigmoid(g)) Wo; ln_post_attn)``.
- feed-forward: ``m = rms(x; ln_pre_mlp)``; the first ``num_dense_layers``
  layers a SwiGLU of width ``intermediate_size``; the others ``s =
  sigmoid(float32(m) @ W_router)``, ``sel = top_k(s + expert_bias)``, ``w =
  s[sel] / (sum(s[sel]) + 1e-20) * route_scale``, ``f = shared(m) + sum_e w_e
  expert_e(m)`` with SwiGLU experts of width ``moe_intermediate_size``; ``x =
  x + rms(f; ln_post_mlp)``.

Dense and expert layers are two scanned stacks (``d_*`` and ``e_*`` weights,
leading axis = layers of that kind). A layer's window and whether it ropes
are per-layer ARRAYS fed to the scan, so one program serves any
``layer_types``. The router runs in float32 (its weight is stored so): in
bfloat16 a near-tie between the k-th and the next score flips an expert.
The residual stream is float32 whatever ``dtype`` the model is served in
(a few KB; the branches, the KV pages and every weight are ``dtype``).
One chip only: every weight is replicated and the engine refuses a mesh of
degree > 1 (tensor parallelism and an expert axis are not written).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import rope as rope_ops
from ..ops.moe_ops import grouped_expert_ffn
from ..ops.rms_norm import rms_norm_replicated

#: a window no table span reaches: a ``full_attention`` layer's entry in the
#: per-layer window array (``pos - _NO_WINDOW`` stays inside int32)
_NO_WINDOW = 1 << 30

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class AfmoeConfig:
    """The published keys of an ``afmoe`` ``config.json`` that set a shape
    or an equation. ``layer_types`` None derives the published pattern:
    every ``global_attn_every_n_layers``-th layer full, the rest sliding."""
    #: the module the serving engine takes this model's step from
    serving_module = "paddle_tpu.models.afmoe"

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    layer_types: Optional[Tuple[str, ...]] = None
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    mup_enabled: bool = True
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = tuple(
                FULL if (i + 1) % n == 0 else SLIDING
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers as "
                f"{SLIDING!r} or {FULL!r}, got {self.layer_types}")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers outside the model's depth")

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers


def afmoe_tiny(**over) -> AfmoeConfig:
    """A CPU-test size with both layer kinds and both feed-forward kinds."""
    return AfmoeConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=3, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, sliding_window=8,
        layer_types=(SLIDING, SLIDING, FULL),
        max_position_embeddings=128), **over})


def attention_windows(config: AfmoeConfig) -> Tuple[Optional[int], ...]:
    """Per layer the sliding window, or None for a full-attention layer:
    what the engine's work record counts the ragged kernel's pages by."""
    return tuple(config.sliding_window if kind == SLIDING else None
                 for kind in config.layer_types)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
_ATTN_KEYS = ("wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm", "ln_in",
              "ln_post_attn", "ln_pre_mlp", "ln_post_mlp")
_NORM_KEYS = ("q_norm", "k_norm", "ln_in", "ln_post_attn", "ln_pre_mlp",
              "ln_post_mlp")
_DENSE_KEYS = ("w_gate", "w_up", "w_down")
_MOE_KEYS = ("router", "expert_bias", "ws_gate", "ws_up", "ws_down")
#: the routed experts' weights are NOT sliced by the layer scan: the grouped
#: product reads a layer's experts in place from the whole stack (a slice
#: handed to a kernel is a copy: 1.6 GB a layer a micro-round here)
_EXPERT_KEYS = ("we_gate", "we_up", "we_down")


def _shapes(config: AfmoeConfig) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """Every weight's (shape, dtype): ``d_*`` over the dense layers, ``e_*``
    over the expert layers."""
    c, dt = config, config.dtype
    h, d = c.hidden_size, c.head_dim
    q, kv = c.num_attention_heads * d, c.num_key_value_heads * d
    mi, ms = c.moe_intermediate_size, \
        c.moe_intermediate_size * c.num_shared_experts
    attn = {"wq": (h, q), "wk": (h, kv), "wv": (h, kv), "wg": (h, q),
            "wo": (q, h), "q_norm": (d,), "k_norm": (d,), "ln_in": (h,),
            "ln_post_attn": (h,), "ln_pre_mlp": (h,), "ln_post_mlp": (h,)}
    dense = {"w_gate": (h, c.intermediate_size),
             "w_up": (h, c.intermediate_size),
             "w_down": (c.intermediate_size, h)}
    moe = {"we_gate": (c.num_experts, h, mi), "we_up": (c.num_experts, h, mi),
           "we_down": (c.num_experts, mi, h), "ws_gate": (h, ms),
           "ws_up": (h, ms), "ws_down": (ms, h)}
    out = {"embed": ((c.vocab_size, h), dt), "ln_f": ((h,), dt),
           "lm_head": ((h, c.vocab_size), dt)}
    for prefix, n, groups in (("d_", c.num_dense_layers, (attn, dense)),
                              ("e_", c.num_expert_layers, (attn, moe))):
        for group in groups:
            for k, shape in group.items():
                out[prefix + k] = ((n,) + shape, dt)
    # the router is float32 whatever the model is served in (module doc)
    out["e_router"] = ((c.num_expert_layers, h, c.num_experts), jnp.float32)
    out["e_expert_bias"] = ((c.num_expert_layers, c.num_experts),
                            jnp.float32)
    return out


def init_stacked_params(config: AfmoeConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded weights in the stacked layout: normal, std 0.02; norm weights
    1; ``expert_bias`` drawn too (std 0.02), so that the experts selected
    (by ``s + expert_bias``) and their weights (from ``s``) differ."""
    shapes = _shapes(config)
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    out = {}
    for key, (name, (shape, dt)) in zip(keys, sorted(shapes.items())):
        if name == "ln_f" or name[2:] in _NORM_KEYS:    # past "d_" / "e_"
            out[name] = jnp.ones(shape, dt)
        else:
            out[name] = (jax.random.normal(key, shape, jnp.float32)
                         * 0.02).astype(dt)
    return out


def param_count(config: AfmoeConfig) -> int:
    return sum(math.prod(shape) for shape, _ in _shapes(config).values())


def param_nbytes(config: AfmoeConfig) -> int:
    """Device bytes of ``init_stacked_params(config)``."""
    return sum(math.prod(shape) * jnp.dtype(dt).itemsize
               for shape, dt in _shapes(config).values())


def kv_geometry(config: AfmoeConfig, page_size: int) -> Dict[str, int]:
    """The paged-KV geometry of the HBM capacity planner. Window layers
    keep every page for a sequence's life (one uniform block table), so
    all layers count alike."""
    return {
        "num_layers": config.num_hidden_layers,
        "num_kv_heads": config.num_key_value_heads,
        "head_dim": config.head_dim,
        "page_size": page_size,
        "dtype_bytes": jnp.dtype(config.dtype).itemsize,
    }


def serving_param_specs(config: AfmoeConfig) -> Dict[str, P]:
    """All replicated: this model serves on one chip (module doc)."""
    return {k: P() for k in _shapes(config)}


def shard_params_tp(params: Dict[str, Any], mesh: Mesh,
                    config: AfmoeConfig) -> Dict[str, Any]:
    """Place the weights on a (degree-1) serving mesh, replicated."""
    return {k: jax.device_put(v, NamedSharding(mesh, P()))
            for k, v in params.items()}


# ---------------------------------------------------------------------------
# the ragged step
# ---------------------------------------------------------------------------
def _mm(x, w):
    return jnp.einsum("...h,hd->...d", x, w)


def _swiglu(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


def route(m, router, expert_bias, config: AfmoeConfig):
    """The router's choice for tokens ``m`` (T, h): (experts (T, k) int32,
    weights (T, k) float32). Float32 at HIGHEST precision throughout."""
    s = jax.nn.sigmoid(jnp.einsum(
        "th,he->te", m.astype(jnp.float32), router,
        precision=lax.Precision.HIGHEST))
    _, sel = lax.top_k(s + expert_bias, config.num_experts_per_tok)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if config.route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * config.route_scale


def ragged_step(params, ids, token_row, positions, kv_lens, last_idx,
                k_pages, v_pages, block_tables, config: AfmoeConfig,
                mesh: Optional[Mesh] = None, mp_axis: str = "mp",
                logits_epilogue=None):
    """One forward over a ragged packed token batch: the contract of
    ``models.llama.ragged_step`` (same arguments), for this model. Returns
    ``(logits (C, V), k_pages', v_pages', aux)``; ``aux`` int32 (expert
    layers, 3): per expert layer the experts hit, the largest number of
    assignments one expert received and the assignments made
    (``ops.moe_ops.grouped_expert_ffn``), which the engine carries out of
    its scan of micro-rounds untouched."""
    from ..ops import paged_attention as pa

    def rms(xv, wv):
        return rms_norm_replicated(xv, wv, config.rms_norm_eps, mesh)

    t = ids.shape[0]
    d, nh, nkv = (config.head_dim, config.num_attention_heads,
                  config.num_key_value_heads)
    page = k_pages.shape[2]
    n_rows, width = block_tables.shape
    s_max = width * page
    cos_full, sin_full = rope_ops.build_rope_cache(s_max, d,
                                                   config.rope_theta)
    pos_c = jnp.minimum(positions.astype(jnp.int32), s_max - 1)
    cos = jnp.take(cos_full, pos_c, axis=0)[None]           # (1, T, d)
    sin = jnp.take(sin_full, pos_c, axis=0)[None]
    # the residual stream is float32 whatever the model is served in: 32
    # tokens of it cost nothing, and every layer's norms, and above all the
    # router's near-ties, read it; rounded to bfloat16 at each of its ten
    # additions it flipped experts several times as often. The branches
    # (their matmul inputs, the KV pages, the expert products) are served
    # in ``config.dtype``.
    f32, dt = jnp.float32, config.dtype
    x = jnp.take(params["embed"], ids.astype(jnp.int32), axis=0).astype(f32)
    if config.mup_enabled:
        x = x * math.sqrt(config.hidden_size)

    valid = token_row >= 0
    row_c = jnp.clip(token_row.astype(jnp.int32), 0, n_rows - 1)
    phys = jnp.take(block_tables.reshape(-1), row_c * width + pos_c // page)
    phys = jnp.where(valid, phys, 0)                        # pads -> page 0
    page_off = pos_c % page

    # flat-pool carry with per-layer page offsets, as in llama.ragged_step
    n_layers, pool_p = k_pages.shape[0], k_pages.shape[1]
    kp_flat = k_pages.reshape((n_layers * pool_p,) + k_pages.shape[2:])
    vp_flat = v_pages.reshape((n_layers * pool_p,) + v_pages.shape[2:])
    # the ragged kernel's row groups: once a step, every layer
    groups = pa.ragged_row_groups(block_tables, token_row, pos_c, kv_lens,
                                  page)

    def attention(xc, kp, vp, lp, l, window, ropes):
        a = rms(xc, lp["ln_in"]).astype(dt)
        q = rms(_mm(a, lp["wq"]).reshape(t, nh, d), lp["q_norm"])
        k = rms(_mm(a, lp["wk"]).reshape(t, nkv, d), lp["k_norm"])
        v = _mm(a, lp["wv"]).reshape(t, nkv, d)
        qr, kr = rope_ops.apply_rope_array(q[None], k[None], cos, sin)
        q, k = jnp.where(ropes, qr[0], q), jnp.where(ropes, kr[0], k)
        kp = kp.at[phys + l * pool_p, page_off].set(k.astype(kp.dtype))
        vp = vp.at[phys + l * pool_p, page_off].set(v.astype(vp.dtype))
        attn = pa.ragged_paged_attention(
            q, kp, vp, block_tables + l * pool_p, token_row, pos_c, kv_lens,
            scale=1.0 / math.sqrt(d), mesh=mesh, mp_axis=mp_axis,
            window=window, groups=groups)                   # (T, nh, d)
        gated = attn.reshape(t, nh * d) * jax.nn.sigmoid(
            _mm(a, lp["wg"])).astype(attn.dtype)
        xo = xc + rms(_mm(gated, lp["wo"]), lp["ln_post_attn"]).astype(f32)
        return xo, kp, vp

    def dense_layer(carry, lp_l):
        xc, kp, vp = carry
        lp, l, window, ropes = lp_l
        xo, kp, vp = attention(xc, kp, vp, lp, l, window, ropes)
        m = rms(xo, lp["ln_pre_mlp"]).astype(dt)
        f = _swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
        xo = xo + rms(f, lp["ln_post_mlp"]).astype(f32)
        return (xo, kp, vp), None

    def expert_layer(carry, lp_l):
        xc, kp, vp = carry
        lp, l, window, ropes = lp_l
        xo, kp, vp = attention(xc, kp, vp, lp, l, window, ropes)
        m = rms(xo, lp["ln_pre_mlp"])       # float32: the router's input
        sel, w = route(m, lp["router"], lp["expert_bias"], config)
        m = m.astype(dt)
        routed, stats = grouped_expert_ffn(
            m, sel, w, valid, *(params["e_" + k] for k in _EXPERT_KEYS),
            layer=l - n_dense)
        f = _swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"]) + routed
        xo = xo + rms(f, lp["ln_post_mlp"]).astype(f32)
        return (xo, kp, vp), stats

    n_dense = config.num_dense_layers
    windows = jnp.asarray([w if w is not None else _NO_WINDOW
                           for w in attention_windows(config)], jnp.int32)
    ropes = jnp.asarray([kind == SLIDING for kind in config.layer_types])
    carry = (x, kp_flat, vp_flat)
    aux = jnp.zeros((0, 3), jnp.int32)
    for prefix, keys, body, lo, hi in (
            ("d_", _ATTN_KEYS + _DENSE_KEYS, dense_layer, 0, n_dense),
            ("e_", _ATTN_KEYS + _MOE_KEYS, expert_layer, n_dense, n_layers)):
        if hi == lo:
            continue
        stack = {k: params[prefix + k] for k in keys}
        carry, stats = lax.scan(
            body, carry,
            (stack, jnp.arange(lo, hi), windows[lo:hi], ropes[lo:hi]))
        if stats is not None:
            aux = stats
    x, kp_flat, vp_flat = carry
    x = rms(x, params["ln_f"]).astype(dt)
    h_last = jnp.take(x, last_idx.astype(jnp.int32), axis=0)
    logits = jnp.einsum("rh,hv->rv", h_last, params["lm_head"])
    if logits_epilogue is not None:
        logits = logits_epilogue(logits)
    return (logits, kp_flat.reshape(k_pages.shape),
            vp_flat.reshape(v_pages.shape), aux)
