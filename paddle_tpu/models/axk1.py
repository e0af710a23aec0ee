"""A.X-K1 decoder (``model_type: axk1``; DeepSeek-V3 family conventions) on
the serving path: the stacked functional weights and the ragged model step
the continuous-batching engine dispatches, with ONE cache array in place of
``models.llama.ragged_step``'s K and V (``cache_layout``).

The layer, as ``perfbench/reference/axk1.py`` computes it in its expanded
form (x: tokens x hidden; H heads; pre-norm residual blocks):

- attention (multi-head latent attention), ``a = rms(x; ln_in)``:
  ``c_q = rms(a W_qa; q_norm)``; ``[q_nope_h | q_rope_h] = c_q W_qb``;
  ``[c_kv | k_r] = a W_kva``, ``c_kv <- rms(c_kv; kv_norm)``, ``k_r <-
  rope(k_r)`` (ONE per token, shared by the heads), ``q_rope_h <-
  rope(q_rope_h)``; ``[k_nope_h | v_h] = c_kv W_kvb``; ``score_h(t, s) =
  scale (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_r(s))``, causal
  softmax, ``o_h = sum_s p v_h(s)``; ``x = x + concat_h(o_h) W_o``.
  ``scale = (nope + rope)^-0.5 yarn_mscale(factor, mscale_all_dim)^2``; the
  rotary tables are YaRN's (``ops.rope.rope_inv_freq``).
- what the served step computes is the ABSORBED form, for every row:
  ``W_kvb = [W_UK | W_UV]`` a head; ``q~_h = q_nope_h W_UK_h^T`` (kv_lora
  numbers), ``score = scale (q~_h . c_kv(s) + q_rope_h . k_r(s))``, ``o~_h =
  sum_s p c_kv(s)``, ``o_h = o~_h W_UV_h``. A token's cache entry is
  ``[c_kv (normed) | k_r (roped)]`` and has no head axis: every head reads
  the same ``kv_lora + rope`` numbers, and the values are the keys' first
  ``kv_lora`` (``ops.paged_attention.mla_paged_attention``). The entry is
  held in a whole number of 128-lane rows (576 numbers in 640 lanes: the
  device pads an array's minor dimension to the lane width anyway, and a
  page copy needs it aligned).
- feed-forward, ``m = rms(x; ln_post)``: the first ``first_k_dense_replace``
  layers a SwiGLU of ``intermediate_size``; the others ``s = sigmoid(float32
  (m) W_r)``; selection on ``s + b``: a group's score is the sum of its best
  two, the best ``topk_group`` of ``n_group`` groups stay, top-k inside
  them; weights ``s`` at the chosen experts (no ``b``), over their sum
  (``norm_topk_prob``), times ``routed_scaling_factor``; ``x = x + sum_e w_e
  E_e(m) + S(m)``, every expert and the shared one a SwiGLU of
  ``moe_intermediate_size``.

**A chip's share.** The router keeps its ``n_routed_experts`` outputs; this
chip holds experts ``first_expert .. first_expert + experts_held - 1`` and
computes their part of the result (``ops.moe_ops.grouped_expert_ffn`` as it
is); what the others would add is left out, and that partial result goes on
to the next layer. No code stands in for the absent chips.

Dense and expert layers are two scanned stacks (``d_*`` / ``e_*``). The
residual stream and the router are float32 whatever ``dtype`` the model is
served in (``models.afmoe``: the router's near-ties); branches, weights and
the cache are ``dtype``. One chip only: every weight is replicated and a
latent cache has no head axis to split, so the engine refuses a mesh of
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

_LANES = 128


@dataclasses.dataclass
class Axk1Config:
    """The published keys of an ``axk1`` ``config.json`` that set a shape or
    an equation, and the chip's share (``experts_held``, ``first_expert``)."""
    #: the module the serving engine takes this model's step from
    serving_module = "paddle_tpu.models.axk1"

    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192         # the router's outputs
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    #: experts this chip holds, ``first_expert`` onward (None: all)
    experts_held: Optional[int] = None
    first_expert: int = 0
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: a ``rope_scaling`` group of type ``yarn``, or None
    rope_scaling: Optional[Dict[str, Any]] = None
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.n_routed_experts):
            raise ValueError("the experts held lie outside the router's")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace outside the model's depth")

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def latent_dim(self) -> int:
        """Numbers of a token's cache entry that mean something."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def entry_dim(self) -> int:
        """Width of a token's cache entry: ``latent_dim`` in whole lanes."""
        return -(-self.latent_dim // _LANES) * _LANES


def axk1_tiny(**over) -> Axk1Config:
    """A CPU-test size with both feed-forward kinds, grouped routing and a
    YaRN stretch."""
    return Axk1Config(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
        n_group=4, topk_group=2, max_position_embeddings=512,
        rope_scaling=dict(type="yarn", factor=4.0, beta_fast=32, beta_slow=1,
                          mscale=1.0, mscale_all_dim=1.0,
                          original_max_position_embeddings=32)), **over})


def cache_layout(config: Axk1Config) -> CacheLayout:
    """What a token keeps per layer: ONE entry ``[c_kv | k_rope]`` (padded to
    whole lanes), no V, no head axis — so no mesh can split it."""
    return CacheLayout(((config.entry_dim,),), head_axis=None)


def softmax_scale(config: Axk1Config) -> float:
    scale = (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5
    y = config.rope_scaling
    if y is not None:
        scale *= rope_ops.yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _rope_mscale(config: Axk1Config) -> float:
    y = config.rope_scaling
    if y is None:
        return 1.0
    return (rope_ops.yarn_mscale(y["factor"], y["mscale"])
            / rope_ops.yarn_mscale(y["factor"], y["mscale_all_dim"]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
_NORM_KEYS = ("q_norm", "kv_norm", "ln_in", "ln_post")
_ATTN_KEYS = ("w_qa", "w_qb", "w_kva", "w_uk", "w_uv", "wo") + _NORM_KEYS
_DENSE_KEYS = ("w_gate", "w_up", "w_down")
_MOE_KEYS = ("router", "expert_bias", "ws_gate", "ws_up", "ws_down")
#: read in place from the whole stack by the grouped product (a slice handed
#: to a kernel is a copy; ``models.afmoe``)
_EXPERT_KEYS = ("we_gate", "we_up", "we_down")


def _shapes(config: Axk1Config) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """Every weight's (shape, dtype): ``d_*`` over the dense layers, ``e_*``
    over the expert layers. ``W_kvb`` is held as its two halves a head,
    ``w_uk`` and ``w_uv`` (kv_lora, heads, .), which the absorbed form
    multiplies from different sides."""
    c, dt = config, config.dtype
    h, nh, cq, ckv = (c.hidden_size, c.num_attention_heads, c.q_lora_rank,
                      c.kv_lora_rank)
    nope, rope, v = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    mi, ms = c.moe_intermediate_size, \
        c.moe_intermediate_size * c.n_shared_experts
    attn = {"w_qa": (h, cq), "q_norm": (cq,),
            "w_qb": (cq, nh * (nope + rope)), "w_kva": (h, ckv + rope),
            "kv_norm": (ckv,), "w_uk": (ckv, nh, nope),
            "w_uv": (ckv, nh, v), "wo": (nh * v, h), "ln_in": (h,),
            "ln_post": (h,)}
    dense = {"w_gate": (h, c.intermediate_size),
             "w_up": (h, c.intermediate_size),
             "w_down": (c.intermediate_size, h)}
    moe = {"we_gate": (c.experts_held, h, mi),
           "we_up": (c.experts_held, h, mi),
           "we_down": (c.experts_held, mi, h), "ws_gate": (h, ms),
           "ws_up": (h, ms), "ws_down": (ms, h)}
    out = {"embed": ((c.vocab_size, h), dt), "ln_f": ((h,), dt),
           "lm_head": ((h, c.vocab_size), dt)}
    for prefix, n, groups in (("d_", c.first_k_dense_replace, (attn, dense)),
                              ("e_", c.num_expert_layers, (attn, moe))):
        for group in groups:
            for k, shape in group.items():
                out[prefix + k] = ((n,) + shape, dt)
    # the router is float32 whatever the model is served in (module doc)
    out["e_router"] = ((c.num_expert_layers, h, c.n_routed_experts),
                       jnp.float32)
    out["e_expert_bias"] = ((c.num_expert_layers, c.n_routed_experts),
                            jnp.float32)
    return out


def init_stacked_params(config: Axk1Config, seed: int = 0) -> Dict[str, Any]:
    """Seeded weights in the stacked layout: normal, std 0.02; norm weights
    1; ``expert_bias`` drawn too (std 0.02), so that the experts selected
    (by ``s + b``) and their weights (from ``s``) differ."""
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


def param_count(config: Axk1Config) -> int:
    return sum(math.prod(shape) for shape, _ in _shapes(config).values())


def param_nbytes(config: Axk1Config) -> int:
    """Device bytes of ``init_stacked_params(config)``."""
    return sum(math.prod(shape) * jnp.dtype(dt).itemsize
               for shape, dt in _shapes(config).values())


def serving_param_specs(config: Axk1Config) -> Dict[str, P]:
    """All replicated: this model serves on one chip (module doc)."""
    return {k: P() for k in _shapes(config)}


def shard_params_tp(params: Dict[str, Any], mesh: Mesh,
                    config: Axk1Config) -> Dict[str, Any]:
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


def route(m, router, expert_bias, config: Axk1Config):
    """The router's choice for tokens ``m`` (T, h) over ALL its experts:
    (experts (T, k) int32, weights (T, k) float32). Group-limited top-k:
    selection on ``s + b``, weights from ``s``. Float32 at HIGHEST precision
    throughout."""
    c = config
    s = jax.nn.sigmoid(jnp.einsum(
        "th,he->te", m.astype(jnp.float32), router,
        precision=lax.Precision.HIGHEST))
    biased = s + expert_bias
    grouped = biased.reshape(-1, c.n_group, c.n_routed_experts // c.n_group)
    group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)  # (T, groups)
    _, kept = lax.top_k(group_score, c.topk_group)
    keep = jnp.any(kept[:, :, None] == jnp.arange(c.n_group), axis=1)
    masked = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(
        biased.shape)
    _, sel = lax.top_k(masked, c.num_experts_per_tok)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if c.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * c.routed_scaling_factor


def absorb_queries(q_nope, q_rope, w_uk, entry_dim: int):
    """The absorbed queries ``[q_nope W_UK^T | q_rope | 0]``, (T, heads,
    entry_dim): what scores against a token's cache entry as it lies."""
    q_lat = jnp.einsum("thn,chn->thc", q_nope, w_uk)
    t, nh = q_lat.shape[:2]
    pad = entry_dim - q_lat.shape[-1] - q_rope.shape[-1]
    return jnp.concatenate(
        [q_lat, q_rope.astype(q_lat.dtype),
         jnp.zeros((t, nh, pad), q_lat.dtype)], axis=-1)


def ragged_step(params, ids, token_row, positions, kv_lens, last_idx,
                latent_pages, block_tables, config: Axk1Config,
                mesh: Optional[Mesh] = None, mp_axis: str = "mp",
                logits_epilogue=None):
    """One forward over a ragged packed token batch: the contract of
    ``models.llama.ragged_step`` with ONE cache array, ``latent_pages``
    (layers, pages, page, entry_dim), where Llama's has K and V. Returns
    ``(logits (C, V), latent_pages', aux)``; ``aux`` int32 (expert layers,
    3): per expert layer the experts hit, the largest number of assignments
    one expert received and the assignments made, among the experts HELD
    (``ops.moe_ops.grouped_expert_ffn``)."""
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
        pos_c, rope_ops.rope_inv_freq(rope, c.rope_theta, c.rope_scaling),
        mscale=_rope_mscale(c))
    cos, sin = cos[None], sin[None]                         # (1, T, rope)
    scale = softmax_scale(c)
    # float32 residual stream, branches in ``dtype`` (models.afmoe)
    f32, dt = jnp.float32, c.dtype
    x = jnp.take(params["embed"], ids.astype(jnp.int32), axis=0).astype(f32)

    valid = token_row >= 0
    row_c = jnp.clip(token_row.astype(jnp.int32), 0, n_rows - 1)
    phys = jnp.take(block_tables.reshape(-1), row_c * width + pos_c // page)
    phys = jnp.where(valid, phys, 0)                        # pads -> page 0
    page_off = pos_c % page

    # flat-pool carry with per-layer page offsets, as in llama.ragged_step
    n_layers, pool_p = latent_pages.shape[:2]
    flat = latent_pages.reshape((n_layers * pool_p,) + latent_pages.shape[2:])
    entry_pad = jnp.zeros((t, c.entry_dim - c.latent_dim), flat.dtype)

    def attention(xc, lat, lp, l):
        a = rms(xc, lp["ln_in"]).astype(dt)
        c_q = rms(_mm(a, lp["w_qa"]), lp["q_norm"])
        q = _mm(c_q, lp["w_qb"]).reshape(t, nh, nope + rope)
        kva = _mm(a, lp["w_kva"])
        c_kv = rms(kva[:, :ckv], lp["kv_norm"])
        q_rope, k_rope = rope_ops.apply_rope_array(
            q[None, :, :, nope:], kva[None, :, None, ckv:], cos, sin)
        entry = jnp.concatenate(
            [c_kv.astype(lat.dtype), k_rope[0, :, 0].astype(lat.dtype),
             entry_pad], axis=-1)
        lat = lat.at[phys + l * pool_p, page_off].set(entry)
        o_lat = pa.mla_paged_attention(
            absorb_queries(q[:, :, :nope], q_rope[0], lp["w_uk"],
                           c.entry_dim),
            lat, block_tables + l * pool_p, token_row, pos_c, kv_lens,
            scale=scale, value_dim=ckv)                     # (T, nh, ckv)
        o = jnp.einsum("thc,chv->thv", o_lat, lp["w_uv"])
        return xc + _mm(o.reshape(t, -1), lp["wo"]).astype(f32), lat

    def dense_layer(carry, lp_l):
        xc, lat = carry
        lp, l = lp_l
        xo, lat = attention(xc, lat, lp, l)
        m = rms(xo, lp["ln_post"]).astype(dt)
        f = _swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
        return (xo + f.astype(f32), lat), None

    def expert_layer(carry, lp_l):
        xc, lat = carry
        lp, l = lp_l
        xo, lat = attention(xc, lat, lp, l)
        m = rms(xo, lp["ln_post"])          # float32: the router's input
        sel, w = route(m, lp["router"], lp["expert_bias"], c)
        m = m.astype(dt)
        routed, stats = grouped_expert_ffn(
            m, sel, w, valid, *(params["e_" + k] for k in _EXPERT_KEYS),
            first_expert=c.first_expert, layer=l - n_dense)
        f = _swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"]) + routed
        return (xo + f.astype(f32), lat), stats

    n_dense = c.first_k_dense_replace
    carry = (x, flat)
    aux = jnp.zeros((0, 3), jnp.int32)
    for prefix, keys, body, lo, hi in (
            ("d_", _ATTN_KEYS + _DENSE_KEYS, dense_layer, 0, n_dense),
            ("e_", _ATTN_KEYS + _MOE_KEYS, expert_layer, n_dense, n_layers)):
        if hi == lo:
            continue
        stack = {k: params[prefix + k] for k in keys}
        carry, stats = lax.scan(body, carry, (stack, jnp.arange(lo, hi)))
        if stats is not None:
            aux = stats
    x, flat = carry
    x = rms(x, params["ln_f"]).astype(dt)
    h_last = jnp.take(x, last_idx.astype(jnp.int32), axis=0)
    logits = jnp.einsum("rh,hv->rv", h_last, params["lm_head"])
    if logits_epilogue is not None:
        logits = logits_epilogue(logits)
    return logits, flat.reshape(latent_pages.shape), aux
