"""LongCat-Flash decoder (shortcut-connected MoE, zero-compute experts) on
the serving path: the stacked functional weights and the ragged model step
the continuous-batching engine dispatches, with ONE latent cache array
(``models.axk1``'s entry) on TWO cache layers a model layer
(``cache_layout``).

The layer, as ``perfbench/reference/longcat_flash.py`` computes it in its
expanded form (x: tokens x hidden; pre-norm, every norm a weighted RMS, no
bias anywhere). A layer has two sub-layers i = 0, 1, each with its own
norms, attention and dense feed-forward, and ONE router and ONE set of
experts, whose branch is taken after the first attention and added after
the second feed-forward (the shortcut: in a deployment the token exchange
overlaps with everything between)::

    x  = x + MLA_0(rms(x; ln_in_0))
    m  = rms(x; ln_post_0)
    e  = MoE(m)
    x  = x + FFN_0(m)
    x  = x + MLA_1(rms(x; ln_in_1))
    x  = x + FFN_1(rms(x; ln_post_1)) + e

- ``MLA_i(a)`` is ``models.axk1``'s latent attention with both low-rank
  branches rescaled and no YaRN: ``c_q = rms(a W_qa; q_norm)``, ``[q_nope_h
  | q_rope_h] = s_q c_q W_qb`` with ``s_q = (hidden / q_lora_rank)^0.5``
  (``mla_scale_q_lora``); ``[c | k_r] = a W_kva``, ``c_kv = s_kv rms(c;
  kv_norm)`` with ``s_kv = (hidden / kv_lora_rank)^0.5``
  (``mla_scale_kv_lora``: the latent, so keys' nope part AND values; not
  ``k_r``); softmax scale ``(nope + rope)^-0.5``. The served step is the
  ABSORBED form; a token's cache entry is ``[c_kv (normed, scaled) | k_r
  (roped)]`` on cache layer ``2 l + i``, held in whole lanes.
- ``FFN_i`` a SwiGLU of ``ffn_hidden_size``.
- ``MoE(m)``: ``s = softmax(float32(m) W_r)`` over ``n_routed_experts +
  zero_expert_num`` outputs; ``sel = top_k(s + b)``, ``b`` a selection bias
  that is a weight; ``w_j = routed_scaling_factor s[sel_j]`` (no ``b``, not
  renormalised); ``e = sum_j w_j E_{sel_j}(m)`` with ``E_e`` a SwiGLU of
  ``expert_ffn_hidden_size`` for ``e < n_routed_experts`` and the IDENTITY
  beyond (a zero-compute expert). No shared expert.

**A chip's share.** The router keeps all its outputs; this chip holds
experts ``first_expert .. first_expert + experts_held - 1``, computes their
part for the tokens routed to them AND every zero-compute assignment of its
tokens (an identity is computed where the token lives); what the other
routed experts would add is left out, and that partial result goes on. No
code stands in for the absent chips (``ops.moe_ops.grouped_expert_ffn``).

The residual stream and the router are float32 whatever ``dtype`` the model
is served in (``models.afmoe``: the router's near-ties); branches, weights
and the cache are ``dtype``. One chip only: every weight is replicated and
a latent cache has no head axis to split, so the engine refuses a mesh of
degree > 1.
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
from ..ops.paged_attention import CacheLayout
from ..ops.rms_norm import rms_norm_replicated
from .axk1 import absorb_queries

_LANES = 128


@dataclasses.dataclass
class LongcatFlashConfig:
    """The published keys of a LongCat-Flash ``config.json`` that set a
    shape or an equation, and the chip's share (``experts_held``,
    ``first_expert``)."""
    #: the module the serving engine takes this model's step from
    serving_module = "paddle_tpu.models.longcat_flash"

    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28                # each two sub-layers (module doc)
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512         # the router's outputs with weights
    zero_expert_num: int = 256          # ... and its identities, after them
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    #: experts this chip holds, ``first_expert`` onward (None: all)
    experts_held: Optional[int] = None
    first_expert: int = 0
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.n_routed_experts):
            raise ValueError("the experts held lie outside the router's")

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def latent_dim(self) -> int:
        """Numbers of a token's cache entry that mean something."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def entry_dim(self) -> int:
        """Width of a token's cache entry: ``latent_dim`` in whole lanes."""
        return -(-self.latent_dim // _LANES) * _LANES


def longcat_flash_tiny(**over) -> LongcatFlashConfig:
    """A CPU-test size: two layers (four cache layers), 8 routed and 4
    zero-compute experts, top-4."""
    return LongcatFlashConfig(**{**dict(
        vocab_size=256, hidden_size=64, ffn_hidden_size=96,
        expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
        zero_expert_num=4, moe_topk=4, max_position_embeddings=512), **over})


def cache_layout(config: LongcatFlashConfig) -> CacheLayout:
    """What a token keeps per CACHE layer: ONE entry ``[c_kv | k_rope]``
    (padded to whole lanes), no V, no head axis; two cache layers a model
    layer, one a sub-layer's attention."""
    return CacheLayout(((config.entry_dim,),), head_axis=None,
                       layers=2 * config.num_layers)


def lora_scales(config: LongcatFlashConfig) -> Tuple[float, float]:
    """(s_q, s_kv) of the module doc; 1 where the configuration's flag is
    off."""
    c = config
    return ((c.hidden_size / c.q_lora_rank) ** 0.5
            if c.mla_scale_q_lora else 1.0,
            (c.hidden_size / c.kv_lora_rank) ** 0.5
            if c.mla_scale_kv_lora else 1.0)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
_NORM_KEYS = ("q_norm", "kv_norm", "ln_in", "ln_post")
#: per SUB-layer, stacked (layers, 2, ...): attention and dense feed-forward
_SUB_KEYS = ("w_qa", "w_qb", "w_kva", "w_uk", "w_uv", "wo", "w_gate", "w_up",
             "w_down") + _NORM_KEYS
#: per layer, stacked (layers, ...)
_ROUTER_KEYS = ("router", "expert_bias")
#: read in place from the whole stack by the grouped product (a slice handed
#: to a kernel is a copy; ``models.afmoe``)
_EXPERT_KEYS = ("we_gate", "we_up", "we_down")


def _shapes(config: LongcatFlashConfig
            ) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """Every weight's (shape, dtype). ``W_kvb`` is held as its two halves a
    head, ``w_uk`` and ``w_uv`` (kv_lora, heads, .), which the absorbed form
    multiplies from different sides."""
    c, dt = config, config.dtype
    h, nh, cq, ckv = (c.hidden_size, c.num_attention_heads, c.q_lora_rank,
                      c.kv_lora_rank)
    nope, rope, v = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    f, m, n = c.ffn_hidden_size, c.expert_ffn_hidden_size, c.num_layers
    sub = {"w_qa": (h, cq), "q_norm": (cq,),
           "w_qb": (cq, nh * (nope + rope)), "w_kva": (h, ckv + rope),
           "kv_norm": (ckv,), "w_uk": (ckv, nh, nope), "w_uv": (ckv, nh, v),
           "wo": (nh * v, h), "ln_in": (h,), "ln_post": (h,),
           "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)}
    out = {"embed": ((c.vocab_size, h), dt), "ln_f": ((h,), dt),
           "lm_head": ((h, c.vocab_size), dt),
           "we_gate": ((n, c.experts_held, h, m), dt),
           "we_up": ((n, c.experts_held, h, m), dt),
           "we_down": ((n, c.experts_held, m, h), dt),
           # the router is float32 whatever the model is served in
           "router": ((n, h, c.router_width), jnp.float32),
           "expert_bias": ((n, c.router_width), jnp.float32)}
    for k, shape in sub.items():
        out[k] = ((n, 2) + shape, dt)
    return out


def init_stacked_params(config: LongcatFlashConfig,
                        seed: int = 0) -> Dict[str, Any]:
    """Seeded weights in the stacked layout: normal, std 0.02; norm weights
    1; ``expert_bias`` normal with std 1 / router width, the MEAN score: a
    softmax's scores are that small, so a bias of ``models.axk1``'s 0.02
    would choose the same experts for every token, and zeros would leave
    selection and weighting the same."""
    shapes = _shapes(config)
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    out = {}
    for key, (name, (shape, dt)) in zip(keys, sorted(shapes.items())):
        if name == "ln_f" or name in _NORM_KEYS:
            out[name] = jnp.ones(shape, dt)
        else:
            std = 1.0 / config.router_width if name == "expert_bias" else 0.02
            out[name] = (jax.random.normal(key, shape, jnp.float32)
                         * std).astype(dt)
    return out


def param_count(config: LongcatFlashConfig) -> int:
    return sum(math.prod(shape) for shape, _ in _shapes(config).values())


def param_nbytes(config: LongcatFlashConfig) -> int:
    """Device bytes of ``init_stacked_params(config)``."""
    return sum(math.prod(shape) * jnp.dtype(dt).itemsize
               for shape, dt in _shapes(config).values())


def serving_param_specs(config: LongcatFlashConfig) -> Dict[str, P]:
    """All replicated: this model serves on one chip (module doc)."""
    return {k: P() for k in _shapes(config)}


def shard_params_tp(params: Dict[str, Any], mesh: Mesh,
                    config: LongcatFlashConfig) -> Dict[str, Any]:
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


def route(m, router, expert_bias, config: LongcatFlashConfig):
    """The router's choice for tokens ``m`` (T, h) over ALL its outputs,
    routed experts then zero-compute ones: (experts (T, k) int32, weights
    (T, k) float32). Softmax scores, one flat top-k on ``s + b``, weights
    ``s`` at the chosen (no ``b``, not renormalised) times
    ``routed_scaling_factor``. Float32 at HIGHEST precision throughout."""
    with jax.named_scope("moe.router"):
        s = jax.nn.softmax(jnp.einsum(
            "th,he->te", m.astype(jnp.float32), router,
            precision=lax.Precision.HIGHEST), axis=-1)
        _, sel = lax.top_k(s + expert_bias, config.moe_topk)
        w = jnp.take_along_axis(s, sel, axis=-1)
        return sel.astype(jnp.int32), w * config.routed_scaling_factor


def ragged_step(params, ids, token_row, positions, kv_lens, last_idx,
                latent_pages, block_tables, config: LongcatFlashConfig,
                mesh: Optional[Mesh] = None, mp_axis: str = "mp",
                logits_epilogue=None):
    """One forward over a ragged packed token batch: the contract of
    ``models.axk1.ragged_step``, ``latent_pages`` (2 x layers, pages, page,
    entry_dim). Returns ``(logits (C, V), latent_pages', aux)``; ``aux``
    int32 (layers, 5): per layer the experts hit, the largest number of
    assignments one expert received and the assignments made, among the
    experts HELD, then the assignments to zero-compute experts and the
    router's (``ops.moe_ops.grouped_expert_ffn``)."""
    from ..ops import paged_attention as pa

    c = config

    def rms(xv, wv):
        return rms_norm_replicated(xv, wv, c.rms_norm_eps, mesh)

    t = ids.shape[0]
    nh, nope, rope, ckv = (c.num_attention_heads, c.qk_nope_head_dim,
                           c.qk_rope_head_dim, c.kv_lora_rank)
    page = latent_pages.shape[2]
    n_rows, width = block_tables.shape
    pos_c = jnp.minimum(positions.astype(jnp.int32), width * page - 1)
    cos, sin = rope_ops.rope_tables(
        pos_c, rope_ops.rope_inv_freq(rope, c.rope_theta))
    cos, sin = cos[None], sin[None]                         # (1, T, rope)
    scale = (nope + rope) ** -0.5
    s_q, s_kv = lora_scales(c)
    # float32 residual stream, branches in ``dtype`` (models.afmoe)
    f32, dt = jnp.float32, c.dtype
    x = jnp.take(params["embed"], ids.astype(jnp.int32), axis=0).astype(f32)

    valid = token_row >= 0
    row_c = jnp.clip(token_row.astype(jnp.int32), 0, n_rows - 1)
    phys = jnp.take(block_tables.reshape(-1), row_c * width + pos_c // page)
    phys = jnp.where(valid, phys, 0)                        # pads -> page 0
    page_off = pos_c % page

    # flat-pool carry with per-layer page offsets, as in llama.ragged_step
    pool_p = latent_pages.shape[1]
    flat = latent_pages.reshape((-1,) + latent_pages.shape[2:])
    entry_pad = jnp.zeros((t, c.entry_dim - c.latent_dim), flat.dtype)

    def attention(xc, lat, lp, cache_layer):
        a = rms(xc, lp["ln_in"]).astype(dt)
        # the two scales in float32: neither need be a power of two
        c_q = rms(_mm(a, lp["w_qa"]), lp["q_norm"]).astype(f32) * s_q
        q = _mm(c_q.astype(dt), lp["w_qb"]).reshape(t, nh, nope + rope)
        kva = _mm(a, lp["w_kva"])
        c_kv = rms(kva[:, :ckv], lp["kv_norm"]).astype(f32) * s_kv
        q_rope, k_rope = rope_ops.apply_rope_array(
            q[None, :, :, nope:], kva[None, :, None, ckv:], cos, sin)
        entry = jnp.concatenate(
            [c_kv.astype(lat.dtype), k_rope[0, :, 0].astype(lat.dtype),
             entry_pad], axis=-1)
        lat = lat.at[phys + cache_layer * pool_p, page_off].set(entry)
        o_lat = pa.mla_paged_attention(
            absorb_queries(q[:, :, :nope], q_rope[0], lp["w_uk"],
                           c.entry_dim),
            lat, block_tables + cache_layer * pool_p, token_row, pos_c,
            kv_lens, scale=scale, value_dim=ckv)            # (T, nh, ckv)
        o = jnp.einsum("thc,chv->thv", o_lat, lp["w_uv"])
        return xc + _mm(o.reshape(t, -1), lp["wo"]).astype(f32), lat

    def dense(m, lp):
        return _swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"]).astype(f32)

    def layer(carry, lp_l):
        xc, lat = carry
        lp, l = lp_l
        sub0, sub1 = ({k: lax.dynamic_index_in_dim(
            subs[k], 2 * l + i, keepdims=False) for k in _SUB_KEYS}
            for i in (0, 1))
        xc, lat = attention(xc, lat, sub0, 2 * l)
        m = rms(xc, sub0["ln_post"])        # float32: the router's input
        sel, w = route(m, lp["router"], lp["expert_bias"], c)
        m = m.astype(dt)
        # the shortcut: taken here, added after the second feed-forward
        e, stats = grouped_expert_ffn(
            m, sel, w, valid, *(params[k] for k in _EXPERT_KEYS),
            first_expert=c.first_expert, layer=l,
            n_routed=c.n_routed_experts)
        xc = xc + dense(m, sub0)
        xc, lat = attention(xc, lat, sub1, 2 * l + 1)
        xc = xc + dense(rms(xc, sub1["ln_post"]).astype(dt), sub1) \
            + e.astype(f32)
        return (xc, lat), stats

    # the sub-layers' weights as ONE run of 2 x layers (a free reshape),
    # each read where it lies by the product that uses it: scanned as
    # (layers, 2, ...), a layer's pair is sliced out whole first, which
    # copies both sub-layers' weights every layer
    subs = {k: params[k].reshape((-1,) + params[k].shape[2:])
            for k in _SUB_KEYS}
    stack = {k: params[k] for k in _ROUTER_KEYS}
    (x, flat), aux = lax.scan(layer, (x, flat),
                              (stack, jnp.arange(c.num_layers)))
    x = rms(x, params["ln_f"]).astype(dt)
    h_last = jnp.take(x, last_idx.astype(jnp.int32), axis=0)
    logits = jnp.einsum("rh,hv->rv", h_last, params["lm_head"])
    if logits_epilogue is not None:
        logits = logits_epilogue(logits)
    return logits, flat.reshape(latent_pages.shape), aux
