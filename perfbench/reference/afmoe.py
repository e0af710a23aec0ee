"""Plain reference of an AFMoE decoder (Arcee Trinity-Mini / Trinity-Nano,
``model_type: afmoe``): the logits of a full forward pass in straightforward
``jax.numpy`` and float32, under ``jax.default_matmul_precision("highest")``.
No kernels, no cache, no batching, no sorting of tokens by expert; nothing
here imports the program.

The layer, for x (positions, hidden). Everything that is not a key of the
published ``config.json`` is written from memory of ``transformers``'
``modeling_afmoe.py`` (no network here) and listed under ``assumed`` in the
configuration's file as unconfirmed: the QK norm, the output gate, rope on
sliding layers only, the sandwich norms, the sqrt(hidden) embedding scale,
``expert_bias`` used for selection only.

- ``x0 = embed[ids] * sqrt(hidden_size)`` where ``mup_enabled``; ``logits =
  rms(x_L; norm) @ lm_head`` (untied).
- attention: ``a = rms(x; input_layernorm)``; ``q = a Wq`` (heads x
  head_dim), ``k = a Wk``, ``v = a Wv`` (KV heads x head_dim), ``g = a Wg``
  (heads x head_dim); ``q = rms(q; q_norm)``, ``k = rms(k; k_norm)`` over
  ``head_dim``; rotary embedding (half-rotation layout, ``rope_theta``,
  absolute positions) on ``q, k`` where ``layer_types[l]`` is
  ``sliding_attention``, none on ``full_attention`` layers; softmax attention
  with scale ``1 / sqrt(head_dim)``, each KV head shared by ``heads / KV
  heads`` query heads, mask ``key_pos <= pos`` and on sliding layers also
  ``key_pos > pos - sliding_window``; ``x = x + rms((attn * sigmoid(g)) Wo;
  post_attention_layernorm)``.
- feed-forward: ``m = rms(x; pre_mlp_layernorm)``; layers ``l <
  num_dense_layers``: ``down(silu(gate(m)) * up(m))`` of width
  ``intermediate_size``; the others: ``s = sigmoid(m @ W_router)``, ``sel`` the
  ``num_experts_per_tok`` largest of ``s + expert_bias``, ``w = s[sel]``,
  divided by ``sum(s[sel]) + 1e-20`` where ``route_norm``, times
  ``route_scale``; ``f = shared(m) + sum_e w_e expert_e(m)``, every expert a
  SwiGLU of width ``moe_intermediate_size`` (the shared one of
  ``num_shared_experts`` times that); ``x = x + rms(f;
  post_mlp_layernorm)``. All norms RMS with ``rms_norm_eps``.

Departures from that description, all for memory and none in the
mathematics: weights arrive in whatever dtype they are served in and are
upcast to float32 one matrix (one EXPERT) at a time; attention runs over
blocks of ``QUERY_BLOCK`` queries and one KV group at a time; the routed
experts are a loop over ALL experts, each applied to every position and
weighted by the position's weight for it (zero where the router did not
choose it); every row is padded to one length so that each layer compiles
once. Only ``logits_at`` is offered: with a vocabulary of 200,192 rows the
logits of every position of a padded batch would be gigabytes a request.

Weights are an object with ``embed`` (V, h), ``norm`` (h,), ``lm_head`` (h,
V) and ``layer(i)`` -> dict of ``input_layernorm post_attention_layernorm
pre_mlp_layernorm post_mlp_layernorm`` (h,), ``q_norm k_norm`` (head_dim,),
``q_proj k_proj v_proj gate_proj o_proj`` as (in, out) matrices (``y = x @
W``), and ``mlp``: for a dense layer ``{"gate_proj", "up_proj",
"down_proj"}``, for an expert layer ``{"router": (h, E) float32,
"expert_bias": (E,), "experts": {"gate_proj": (E, h, m), "up_proj": (E, h,
m), "down_proj": (E, m, h)}, "shared": {"gate_proj", "up_proj",
"down_proj"}}``. The model is a dict with the published keys.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope(seq_len: int, head_dim: int, theta: float):
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=F32)
                                / head_dim))
    freqs = jnp.outer(jnp.arange(seq_len, dtype=F32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def _swiglu(x, w):
    gate = jax.nn.silu(x @ w["gate_proj"].astype(F32))
    return (gate * (x @ w["up_proj"].astype(F32))) @ w["down_proj"].astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "d", "eps", "theta", "window"))
def _attention(x, lw, *, n_heads, n_kv, d, eps, theta, window):
    """x + the attention branch, x (S, h) float32; ``window`` None on a
    full-attention layer (which also applies no rotary embedding)."""
    s = x.shape[0]
    a = _rms_norm(x, lw["input_layernorm"], eps)
    q = _rms_norm((a @ lw["q_proj"].astype(F32)).reshape(s, n_heads, d),
                  lw["q_norm"], eps)
    k = _rms_norm((a @ lw["k_proj"].astype(F32)).reshape(s, n_kv, d),
                  lw["k_norm"], eps)
    v = (a @ lw["v_proj"].astype(F32)).reshape(s, n_kv, d)
    gate = jax.nn.sigmoid(a @ lw["gate_proj"].astype(F32))
    if window is not None:
        cos, sin = _rope(s, d, theta)
        cos, sin = cos[:, None, :], sin[:, None, :]
        q = q * cos + _rotate_half(q) * sin
        k = k * cos + _rotate_half(k) * sin
    group = n_heads // n_kv
    key_pos = jnp.arange(s)[None, :]

    def block(start):
        """Attention of QUERY_BLOCK queries from ``start`` on, all heads."""
        q_pos = start + jnp.arange(QUERY_BLOCK)[:, None]
        mask = key_pos <= q_pos
        if window is not None:
            mask = mask & (key_pos > q_pos - window)
        qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK, axis=0)
        outs = []
        for g in range(n_kv):                   # one KV group at a time
            qg = qb[:, g * group:(g + 1) * group]           # (B, group, d)
            scores = jnp.einsum("qhd,kd->hqk", qg, k[:, g]) / math.sqrt(d)
            scores = jnp.where(mask[None], scores, -jnp.inf)
            outs.append(jnp.einsum("hqk,kd->qhd",
                                   jax.nn.softmax(scores, axis=-1), v[:, g]))
        return jnp.concatenate(outs, axis=1)                # (B, heads, d)

    attn = jax.lax.map(block, jnp.arange(0, s, QUERY_BLOCK))
    attn = attn.reshape(s, n_heads * d) * gate
    return x + _rms_norm(attn @ lw["o_proj"].astype(F32),
                         lw["post_attention_layernorm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_mlp(x, lw, *, eps):
    m = _rms_norm(x, lw["pre_mlp_layernorm"], eps)
    return x + _rms_norm(_swiglu(m, lw["mlp"]), lw["post_mlp_layernorm"],
                         eps)


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "route_norm", "route_scale"))
def _expert_mlp(x, lw, *, eps, top_k, route_norm, route_scale):
    m = _rms_norm(x, lw["pre_mlp_layernorm"], eps)
    mlp = lw["mlp"]
    scores = jax.nn.sigmoid(m @ mlp["router"].astype(F32))   # (S, E)
    n_experts = scores.shape[-1]
    # the top_k largest of scores + bias, ties to the lower index
    chosen = jnp.argsort(-(scores + mlp["expert_bias"].astype(F32)), axis=-1,
                         stable=True)[:, :top_k]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(n_experts), axis=1)
    weight = jnp.where(picked, scores, 0.0)
    if route_norm:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * route_scale                            # (S, E)

    def one_expert(total, ew):
        gate, up, down, w_e = ew
        out = _swiglu(m, {"gate_proj": gate, "up_proj": up,
                          "down_proj": down})
        return total + w_e[:, None] * out, None

    experts = mlp["experts"]
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (experts["gate_proj"], experts["up_proj"], experts["down_proj"],
         weight.T))
    f = _swiglu(m, mlp["shared"]) + routed
    return x + _rms_norm(f, lw["post_mlp_layernorm"], eps)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(table, ids, *, scale):
    return jnp.take(table, ids, axis=0).astype(F32) * scale


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    return _rms_norm(x, norm, eps) @ lm_head.astype(F32)


def hidden_states(weights, ids, model: Dict):
    """The last layer's output (S, h) for token ids (S,), S a multiple of
    QUERY_BLOCK (positions past a row's own tokens come after them, so the
    causal mask keeps them out of it)."""
    eps = float(model["rms_norm_eps"])
    scale = math.sqrt(model["hidden_size"]) if model["mup_enabled"] else 1.0
    x = _embed(weights.embed, jnp.asarray(ids, jnp.int32), scale=scale)
    for i in range(model["num_hidden_layers"]):
        lw = weights.layer(i)
        sliding = model["layer_types"][i] == "sliding_attention"
        x = _attention(
            x, lw, n_heads=model["num_attention_heads"],
            n_kv=model["num_key_value_heads"], d=model["head_dim"], eps=eps,
            theta=float(model["rope_theta"]),
            window=int(model["sliding_window"]) if sliding else None)
        if i < model["num_dense_layers"]:
            x = _dense_mlp(x, lw, eps=eps)
        else:
            x = _expert_mlp(x, lw, eps=eps,
                            top_k=model["num_experts_per_tok"],
                            route_norm=bool(model["route_norm"]),
                            route_scale=float(model["route_scale"]))
    return x


def logits_at(weights, ids: Sequence[np.ndarray],
              spans: Sequence[Tuple[int, int]], model: Dict) -> List:
    """For each row of token ids (unpadded, 1-D) the float32 logits at
    positions ``start .. stop - 1`` of its span, ``(stop - start, vocab)``
    (the logits at position p predict token p + 1)."""
    longest = max(len(row) for row in ids)
    padded = -(-longest // QUERY_BLOCK) * QUERY_BLOCK
    out = []
    with jax.default_matmul_precision("highest"):
        for row, (start, stop) in zip(ids, spans):
            full = np.zeros((padded,), np.int32)
            full[:len(row)] = row
            x = hidden_states(weights, full, model)
            out.append(_head(x[start:stop], weights.norm, weights.lm_head,
                             eps=float(model["rms_norm_eps"])))
    return out
