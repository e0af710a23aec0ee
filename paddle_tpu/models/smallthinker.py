"""SmallThinker decoder (PowerInfer's SmallThinker-21BA3B / 4BA0.6B) on the
serving path: the stacked functional weights and the ragged model step the
continuous-batching engine dispatches, with ``models.llama.ragged_step``'s
signature.

The layer, as ``perfbench/reference/smallthinker.py`` computes it too (x:
tokens x hidden; pre-norm, every norm a weighted RMS, no bias anywhere):

- ``x0 = embed[ids]``; ``logits = rms(x_L; ln_f) @ lm_head`` (untied).
- ``a = rms(x; ln_in)``. The ROUTER reads ``a``, before the attention:
  ``p = softmax(float32(a) @ W_r)`` over ``moe_num_primary_experts``,
  ``sel = top_k(p)`` (``moe_num_active_primary_experts``), ``w = p[sel] /
  sum(p[sel])`` where ``norm_topk_prob``.
- attention: ``q, k, v = a Wq, a Wk, a Wv`` (``num_attention_heads`` and
  ``num_key_value_heads`` of ``head_dim``; the query width need not be the
  hidden size); rotary embedding (half rotation, ``rope_theta``) on a layer
  whose ``rope_layout`` entry is 1, no positional term where it is 0; causal
  softmax attention, grouped KV heads, scale ``head_dim^-0.5``, where
  ``sliding_window_layout`` is 1 also ``key_pos > pos -
  sliding_window_size``; ``x = x + attn Wo``.
- experts: ``m = rms(x; ln_post)``; ``x = x + sum_j w_j E_{sel_j}(m)``, each
  expert a ReGLU ``down(relu(m W_gate) * (m W_up))`` of
  ``moe_ffn_hidden_size``. No shared expert, no dense layer.

Every layer is alike, so ONE scanned stack; a layer's window and whether it
ropes are per-layer ARRAYS fed to the scan (``models.afmoe``), read from the
two published layouts. The router's weight is stored in float32 and it runs
at HIGHEST precision (``models.afmoe``: near-ties); the residual stream is
float32, the branches, the KV pages and every other weight ``dtype``. The
routed experts' stack is read in place by the grouped product. One chip
only: every weight is replicated and the engine refuses a mesh of degree >
1.
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
from ..ops.moe_ops import expert_stat_names, grouped_expert_ffn
from ..ops.rms_norm import rms_norm_replicated

#: a window no table span reaches: a global layer's entry in the per-layer
#: window array (``models.afmoe``)
_NO_WINDOW = 1 << 30
#: the published layouts' period: one global NoPE layer, three RoPE window
#: layers
_PERIOD = (0, 1, 1, 1)


@dataclasses.dataclass
class SmallThinkerConfig:
    """The published keys of a SmallThinker ``config.json`` that set a shape
    or an equation. ``rope_layout`` / ``sliding_window_layout`` None derive
    the published period."""
    #: the module the serving engine takes this model's step from
    serving_module = "paddle_tpu.models.smallthinker"

    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rope_layout: Optional[Tuple[int, ...]] = None
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    sliding_window_size: int = 4096
    rope_theta: float = 1.5e6
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 16384
    dtype: Any = jnp.float32

    def __post_init__(self):
        n = self.num_hidden_layers
        for key in ("rope_layout", "sliding_window_layout"):
            layout = getattr(self, key)
            layout = tuple(_PERIOD[i % 4] for i in range(n)) \
                if layout is None else tuple(int(v) for v in layout)
            if len(layout) != n or set(layout) - {0, 1}:
                raise ValueError(f"{key} must give each of the {n} layers 0 "
                                 f"or 1, got {layout}")
            setattr(self, key, layout)
        if not self.moe_primary_router_apply_softmax \
                or self.tie_word_embeddings:
            raise ValueError("models.smallthinker has a softmax router and "
                             "an untied head only")


def smallthinker_tiny(**over) -> SmallThinkerConfig:
    """A CPU-test size: one whole period (a global layer, three window
    layers of 8 positions), 7 query heads a KV head, queries wider than the
    hidden size, 8 experts, top-2."""
    return SmallThinkerConfig(**{**dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=14, num_key_value_heads=2, head_dim=16,
        moe_ffn_hidden_size=32, moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, sliding_window_size=8,
        rope_theta=10000.0, max_position_embeddings=512), **over})


def attention_windows(config: SmallThinkerConfig
                      ) -> Tuple[Optional[int], ...]:
    """Per layer the sliding window, or None for a global layer: what the
    engine's work record counts the ragged kernel's pages by."""
    return tuple(config.sliding_window_size if w else None
                 for w in config.sliding_window_layout)


def routing_columns(config: SmallThinkerConfig) -> Tuple[str, ...]:
    """The names of the columns of the routing record the step returns
    (``ops.moe_ops.grouped_expert_ffn``'s stats, ReGLU's two among them)."""
    return expert_stat_names(activation="relu")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
_NORM_KEYS = ("ln_in", "ln_post")
#: per layer, sliced by the layer scan
_LAYER_KEYS = ("wq", "wk", "wv", "wo", "router") + _NORM_KEYS
#: read in place from the whole stack by the grouped product (a slice handed
#: to a kernel is a copy; ``models.afmoe``)
_EXPERT_KEYS = ("we_gate", "we_up", "we_down")


def _shapes(config: SmallThinkerConfig
            ) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """Every weight's (shape, dtype), stacked over the layers."""
    c, dt = config, config.dtype
    h, d, n = c.hidden_size, c.head_dim, c.num_hidden_layers
    q, kv = c.num_attention_heads * d, c.num_key_value_heads * d
    e, m = c.moe_num_primary_experts, c.moe_ffn_hidden_size
    layer = {"wq": (h, q), "wk": (h, kv), "wv": (h, kv), "wo": (q, h),
             "ln_in": (h,), "ln_post": (h,), "we_gate": (e, h, m),
             "we_up": (e, h, m), "we_down": (e, m, h)}
    out = {"embed": ((c.vocab_size, h), dt), "ln_f": ((h,), dt),
           "lm_head": ((h, c.vocab_size), dt),
           # the router is float32 whatever the model is served in
           "router": ((n, h, e), jnp.float32)}
    for k, shape in layer.items():
        out[k] = ((n,) + shape, dt)
    return out


def init_stacked_params(config: SmallThinkerConfig,
                        seed: int = 0) -> Dict[str, Any]:
    """Seeded weights in the stacked layout: normal, std 0.02; norm weights
    1."""
    shapes = _shapes(config)
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    out = {}
    for key, (name, (shape, dt)) in zip(keys, sorted(shapes.items())):
        if name == "ln_f" or name in _NORM_KEYS:
            out[name] = jnp.ones(shape, dt)
        else:
            out[name] = (jax.random.normal(key, shape, jnp.float32)
                         * 0.02).astype(dt)
    return out


def param_count(config: SmallThinkerConfig) -> int:
    return sum(math.prod(shape) for shape, _ in _shapes(config).values())


def param_nbytes(config: SmallThinkerConfig) -> int:
    """Device bytes of ``init_stacked_params(config)``."""
    return sum(math.prod(shape) * jnp.dtype(dt).itemsize
               for shape, dt in _shapes(config).values())


def serving_param_specs(config: SmallThinkerConfig) -> Dict[str, P]:
    """All replicated: this model serves on one chip (module doc)."""
    return {k: P() for k in _shapes(config)}


def shard_params_tp(params: Dict[str, Any], mesh: Mesh,
                    config: SmallThinkerConfig) -> Dict[str, Any]:
    """Place the weights on a (degree-1) serving mesh, replicated."""
    return {k: jax.device_put(v, NamedSharding(mesh, P()))
            for k, v in params.items()}


# ---------------------------------------------------------------------------
# the ragged step
# ---------------------------------------------------------------------------
def _mm(x, w):
    return jnp.einsum("...h,hd->...d", x, w)


def route(a, router, config: SmallThinkerConfig):
    """The router's choice for the layer's normed input ``a`` (T, h):
    (experts (T, k) int32, weights (T, k) float32). Softmax over all
    experts, top-k, renormalised over the chosen where ``norm_topk_prob``.
    Float32 at HIGHEST precision throughout."""
    with jax.named_scope("moe.router"):
        p = jax.nn.softmax(jnp.einsum(
            "th,he->te", a.astype(jnp.float32), router,
            precision=lax.Precision.HIGHEST), axis=-1)
        w, sel = lax.top_k(p, config.moe_num_active_primary_experts)
        if config.norm_topk_prob:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return sel.astype(jnp.int32), w


def ragged_step(params, ids, token_row, positions, kv_lens, last_idx,
                k_pages, v_pages, block_tables, config: SmallThinkerConfig,
                mesh: Optional[Mesh] = None, mp_axis: str = "mp",
                logits_epilogue=None):
    """One forward over a ragged packed token batch: the contract of
    ``models.llama.ragged_step`` (same arguments), for this model. Returns
    ``(logits (C, V), k_pages', v_pages', aux)``; ``aux`` int32 (layers,
    5), the columns ``routing_columns`` names: per layer the experts hit,
    the largest number of assignments one expert received, the assignments
    made, the intermediate units ReLU left active and the units computed
    (``ops.moe_ops.grouped_expert_ffn``)."""
    from ..ops import paged_attention as pa

    c = config

    def rms(xv, wv):
        return rms_norm_replicated(xv, wv, c.rms_norm_eps, mesh)

    t = ids.shape[0]
    d, nh, nkv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
    page = k_pages.shape[2]
    n_rows, width = block_tables.shape
    pos_c = jnp.minimum(positions.astype(jnp.int32), width * page - 1)
    cos, sin = rope_ops.rope_tables(pos_c,
                                    rope_ops.rope_inv_freq(d, c.rope_theta))
    # float32 residual stream, branches in ``dtype`` (models.afmoe)
    f32, dt = jnp.float32, c.dtype
    x = jnp.take(params["embed"], ids.astype(jnp.int32), axis=0).astype(f32)

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

    def layer(carry, lp_l):
        xc, kp, vp = carry
        lp, l, window, ropes = lp_l
        a = rms(xc, lp["ln_in"])            # float32: the router's input
        # the router reads the layer's input, ahead of the attention
        sel, w = route(a, lp["router"], c)
        a = a.astype(dt)
        q = _mm(a, lp["wq"]).reshape(t, nh, d)
        k = _mm(a, lp["wk"]).reshape(t, nkv, d)
        v = _mm(a, lp["wv"]).reshape(t, nkv, d)
        qr, kr = rope_ops.apply_rope_array(q[None], k[None], cos, sin)
        q, k = jnp.where(ropes, qr[0], q), jnp.where(ropes, kr[0], k)
        kp = kp.at[phys + l * pool_p, page_off].set(k.astype(kp.dtype))
        vp = vp.at[phys + l * pool_p, page_off].set(v.astype(vp.dtype))
        attn = pa.ragged_paged_attention(
            q, kp, vp, block_tables + l * pool_p, token_row, pos_c, kv_lens,
            scale=1.0 / math.sqrt(d), mesh=mesh, mp_axis=mp_axis,
            window=window, groups=groups)                   # (T, nh, d)
        xc = xc + _mm(attn.reshape(t, nh * d), lp["wo"]).astype(f32)
        m = rms(xc, lp["ln_post"]).astype(dt)
        e, stats = grouped_expert_ffn(
            m, sel, w, valid, *(params[k] for k in _EXPERT_KEYS), layer=l,
            activation="relu")
        return (xc + e.astype(f32), kp, vp), stats

    windows = jnp.asarray([w if w is not None else _NO_WINDOW
                           for w in attention_windows(c)], jnp.int32)
    ropes = jnp.asarray(c.rope_layout, bool)
    stack = {k: params[k] for k in _LAYER_KEYS}
    (x, kp_flat, vp_flat), aux = lax.scan(
        layer, (x, kp_flat, vp_flat),
        (stack, jnp.arange(n_layers), windows, ropes))
    x = rms(x, params["ln_f"]).astype(dt)
    h_last = jnp.take(x, last_idx.astype(jnp.int32), axis=0)
    logits = jnp.einsum("rh,hv->rv", h_last, params["lm_head"])
    if logits_epilogue is not None:
        logits = logits_epilogue(logits)
    return (logits, kp_flat.reshape(k_pages.shape),
            vp_flat.reshape(v_pages.shape), aux)
