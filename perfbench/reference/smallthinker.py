"""Plain reference of a SmallThinker decoder (PowerInfer's
SmallThinker-21BA3B-Instruct / 4BA0.6B): the logits of a full forward pass in
straightforward ``jax.numpy`` and float32, under
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching, no sorting of tokens by expert; nothing here imports the program.

The layer, for x (positions, hidden), from the published ``config.json``'s
keys and the catalog's ``described_as`` ("router placed before attention",
"sparse ReGLU"). What those leave open is listed under ``assumed`` in the
configuration's file as unconfirmed: the router reads the normed input
``a`` (not the raw stream), the half-rotation rotary pairing, no QK norm, no
bias and no embedding scale, and no "secondary" experts.

- ``x0 = embed[ids]``; ``logits = rms(x_L; norm) @ lm_head`` (untied).
- ``a = rms(x; input_layernorm)``; the router: ``p = softmax(a @ W_r)``
  over ``moe_num_primary_experts``, ``sel`` the
  ``moe_num_active_primary_experts`` largest, ``w = p[sel] / sum(p[sel])``
  where ``norm_topk_prob``.
- attention: ``q = a Wq`` (heads x head_dim), ``k = a Wk``, ``v = a Wv`` (KV
  heads x head_dim); rotary embedding (half rotation, ``rope_theta``,
  absolute positions) on ``q, k`` where ``rope_layout[l]`` is 1, none where
  it is 0; softmax attention with scale ``1 / sqrt(head_dim)``, each KV head
  shared by ``heads / KV heads`` query heads, mask ``key_pos <= pos`` and
  where ``sliding_window_layout[l]`` is 1 also ``key_pos > pos -
  sliding_window_size``; ``x = x + attn Wo``.
- experts: ``m = rms(x; post_attention_layernorm)``; ``x = x + sum_e w_e
  down_e(relu(m gate_e) * (m up_e))``, every expert of width
  ``moe_ffn_hidden_size``. All norms RMS with ``rms_norm_eps``.

Departures from that description, all for memory and none in the
mathematics: weights arrive in whatever dtype they are served in and are
upcast to float32 one matrix (one EXPERT) at a time; attention runs over
blocks of ``QUERY_BLOCK`` queries and one KV group at a time; the routed
experts are a loop over ALL experts, each applied to every position and
weighted by the position's weight for it (zero where the router did not
choose it); every row is padded to one length so that each layer compiles
once. Only ``logits_at`` is offered: with a vocabulary of 151,936 rows the
logits of every position of a 13k-position request would be 8 GB.

Weights are an object with ``embed`` (V, h), ``norm`` (h,), ``lm_head`` (h,
V) and ``layer(i)`` -> dict of ``input_layernorm post_attention_layernorm``
(h,), ``q_proj k_proj v_proj o_proj`` as (in, out) matrices (``y = x @ W``),
``router`` (h, E) and ``experts``: ``{"gate_proj": (E, h, m), "up_proj": (E,
h, m), "down_proj": (E, m, h)}``. The model is a dict with the published
keys.
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


def _reglu(x, gate, up, down):
    return (jax.nn.relu(x @ gate.astype(F32))
            * (x @ up.astype(F32))) @ down.astype(F32)


@functools.partial(jax.jit, static_argnames=("top_k", "norm"))
def router_weights(a, router, *, top_k, norm):
    """(S, E) float32: each position's weight for each expert, zero where
    the router did not choose it (the ``top_k`` largest scores, ties to the
    lower index)."""
    p = jax.nn.softmax(a @ router.astype(F32), axis=-1)
    chosen = jnp.argsort(-p, axis=-1, stable=True)[:, :top_k]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(p.shape[-1]), axis=1)
    weight = jnp.where(picked, p, 0.0)
    if norm:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return weight


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "d", "theta", "rope", "window"))
def attention(a, lw, *, n_heads, n_kv, d, theta, rope, window):
    """The attention branch (S, h) of the normed input ``a`` (S, h) float32;
    ``rope`` whether the layer ropes, ``window`` None on a global layer."""
    s = a.shape[0]
    q = (a @ lw["q_proj"].astype(F32)).reshape(s, n_heads, d)
    k = (a @ lw["k_proj"].astype(F32)).reshape(s, n_kv, d)
    v = (a @ lw["v_proj"].astype(F32)).reshape(s, n_kv, d)
    if rope:
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
    return attn.reshape(s, n_heads * d) @ lw["o_proj"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def experts(x, weight, lw, *, eps):
    """The expert branch (S, h) of the stream ``x`` after attention, with
    the router's (S, E) ``weight``."""
    m = _rms_norm(x, lw["post_attention_layernorm"], eps)

    def one_expert(total, ew):
        gate, up, down, w_e = ew
        return total + w_e[:, None] * _reglu(m, gate, up, down), None

    e = lw["experts"]
    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (e["gate_proj"], e["up_proj"], e["down_proj"], weight.T))
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms_norm(x, w, eps)


def layer(x, lw, l: int, model: Dict):
    """One layer: ``x`` (S, h) float32 -> the next layer's input."""
    eps = float(model["rms_norm_eps"])
    a = _norm(x, lw["input_layernorm"], eps=eps)
    weight = router_weights(a, lw["router"],
                            top_k=int(model["moe_num_active_primary_experts"]),
                            norm=bool(model["norm_topk_prob"]))
    x = x + attention(
        a, lw, n_heads=model["num_attention_heads"],
        n_kv=model["num_key_value_heads"], d=model["head_dim"],
        theta=float(model["rope_theta"]),
        rope=bool(model["rope_layout"][l]),
        window=int(model["sliding_window_size"])
        if model["sliding_window_layout"][l] else None)
    return x + experts(x, weight, lw, eps=eps)


@jax.jit
def _embed(table, ids):
    return jnp.take(table, ids, axis=0).astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    return _rms_norm(x, norm, eps) @ lm_head.astype(F32)


def hidden_states(weights, ids, model: Dict):
    """The last layer's output (S, h) for token ids (S,), S a multiple of
    QUERY_BLOCK (positions past a row's own tokens come after them, so the
    causal mask keeps them out of it)."""
    x = _embed(weights.embed, jnp.asarray(ids, jnp.int32))
    for l in range(model["num_hidden_layers"]):
        x = layer(x, weights.layer(l), l, model)
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
