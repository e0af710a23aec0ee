"""Plain reference of a Jamba decoder (``model_type: jamba``: Mamba layers
beside a few attention layers, dense feed-forwards): the logits of a full
forward pass in straightforward ``jax.numpy`` and float32, under
``jax.default_matmul_precision("highest")``. The recurrence is one
``lax.scan`` over the positions of a row from a zero state; attention is over
the whole row; there is no cache, no state carried between calls, no chunking
and no kernel. Nothing here imports the program or another reference.

For x (positions, hidden), pre-norm residual blocks, all norms RMS with
``rms_norm_eps``, no bias but the two named:

- ``x0 = embed[ids]``; ``logits = rms(x_L; norm) @ embed^T``
  (``tie_word_embeddings``).
- every layer: ``x = x + mixer(rms(x; input_layernorm))``; ``x = x +
  down_proj(silu(gate_proj(h)) * up_proj(h))``, ``h = rms(x;
  pre_ff_layernorm)`` (``num_experts`` 1: every feed-forward is dense).
- layer i's mixer is attention where ``i % attn_layer_period ==
  attn_layer_offset``, else Mamba.
- attention: ``q = a q_proj`` as (H, d), ``k = a k_proj``, ``v = a v_proj`` as
  (KV, d), d = ``hidden_size / num_attention_heads``, a query head on KV head
  ``h // (H / KV)``; softmax attention, mask ``key_pos <= pos``, scale
  ``d^-0.5``, NO rotary or other positional term; ``o_proj``.
- Mamba, D = ``mamba_expand`` x hidden, N = ``mamba_d_state``, R =
  ``mamba_dt_rank``, K = ``mamba_d_conv``: ``[u | z] = a in_proj``; ``u_t =
  silu(conv1d_bias + sum_{j<K} conv1d_weight[:, j] * u_{t-(K-1)+j})`` per
  channel, zeros before the row's first position; ``[dt | B | C] = u
  x_proj`` (R, N, N), each through its own weighted RMS norm
  (``dt_layernorm``, ``b_layernorm``, ``c_layernorm``); ``delta =
  softplus(dt dt_proj + dt_proj_bias)``; ``A = -exp(A_log)`` (D, N); ``S_t =
  exp(delta_t[:, None] A) S_{t-1} + (delta_t u_t)[:, None] B_t[None, :]``,
  ``S_{-1} = 0``; ``y_t = S_t C_t + D_skip u_t``; ``out = (y * silu(z))
  out_proj``.

Departures for memory, none in the mathematics: weights arrive in whatever
dtype they are served in and are upcast to float32 one matrix at a time;
attention runs over blocks of ``QUERY_BLOCK`` queries; every row is padded to
one length so that each layer compiles once (positions past a row's own
tokens come after them: neither the causal mask nor the recurrence lets them
reach back). Only ``logits_at`` is offered (65,536 x ~2k positions of float32
logits a request would be 0.5 GB).

Weights are an object with ``embed`` (V, h), ``norm`` (h,) and ``layer(i)``
-> dict of ``input_layernorm pre_ff_layernorm`` (h,), ``gate_proj up_proj
down_proj`` as (in, out) matrices (``y = x @ W``) and, for an attention
layer, ``q_proj k_proj v_proj o_proj``; for a Mamba layer ``in_proj`` (h,
2D), ``conv1d_weight`` (D, K), ``conv1d_bias`` (D,), ``x_proj`` (D, R + 2N),
``dt_layernorm`` (R,), ``b_layernorm c_layernorm`` (N,), ``dt_proj`` (R, D),
``dt_proj_bias`` (D,), ``A_log`` (D, N), ``D`` (D,), ``out_proj`` (D, h).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512


def is_attention(i: int, model: Dict) -> bool:
    return i % model["attn_layer_period"] == model["attn_layer_offset"]


def _rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _feed_forward(x, lw, eps):
    h = _rms_norm(x, lw["pre_ff_layernorm"], eps)
    gate = jax.nn.silu(h @ lw["gate_proj"].astype(F32))
    return x + (gate * (h @ lw["up_proj"].astype(F32))) \
        @ lw["down_proj"].astype(F32)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps"))
def _attention_layer(x, lw, *, n_heads, n_kv, eps):
    s, hidden = x.shape
    d = hidden // n_heads
    a = _rms_norm(x, lw["input_layernorm"], eps)
    q = (a @ lw["q_proj"].astype(F32)).reshape(s, n_heads, d)
    k = (a @ lw["k_proj"].astype(F32)).reshape(s, n_kv, d)
    v = (a @ lw["v_proj"].astype(F32)).reshape(s, n_kv, d)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    key_pos = jnp.arange(s)

    def block(q_blk, first):
        pos = first + jnp.arange(q_blk.shape[0])
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k) * d ** -0.5
        scores = jnp.where(key_pos[None, None, :] <= pos[None, :, None],
                           scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    blocks = [block(q[i:i + QUERY_BLOCK], i) for i in range(0, s, QUERY_BLOCK)]
    o = jnp.concatenate(blocks, axis=0).reshape(s, hidden)
    return _feed_forward(x + o @ lw["o_proj"].astype(F32), lw, eps)


def _conv(u, weight, bias):
    """Causal depthwise conv: out_t = bias + sum_j weight[:, j] u_{t-(K-1)+j},
    zeros before position 0. u (S, D), weight (D, K)."""
    k = weight.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), F32), u], axis=0)
    out = bias.astype(F32)[None, :]
    for j in range(k):
        out = out + weight[:, j].astype(F32)[None, :] \
            * padded[j:j + u.shape[0]]
    return out


def _scan(u, delta, b, c, a, d_skip):
    """The recurrence over the positions of one row, from a zero state.
    u, delta (S, D); b, c (S, N); a (D, N); d_skip (D,)."""

    def step(state, xs):
        u_t, delta_t, b_t, c_t = xs
        state = jnp.exp(delta_t[:, None] * a) * state \
            + (delta_t * u_t)[:, None] * b_t[None, :]
        return state, state @ c_t + d_skip * u_t

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, F32), (u, delta, b, c))
    return y


@functools.partial(jax.jit, static_argnames=("n_state", "dt_rank", "eps"))
def _mamba_layer(x, lw, *, n_state, dt_rank, eps):
    a = _rms_norm(x, lw["input_layernorm"], eps)
    uz = a @ lw["in_proj"].astype(F32)
    d_inner = uz.shape[1] // 2
    u = jax.nn.silu(_conv(uz[:, :d_inner], lw["conv1d_weight"],
                          lw["conv1d_bias"]))
    z = uz[:, d_inner:]
    dbc = u @ lw["x_proj"].astype(F32)
    dt = _rms_norm(dbc[:, :dt_rank], lw["dt_layernorm"], eps)
    b = _rms_norm(dbc[:, dt_rank:dt_rank + n_state], lw["b_layernorm"], eps)
    c = _rms_norm(dbc[:, dt_rank + n_state:], lw["c_layernorm"], eps)
    delta = jax.nn.softplus(dt @ lw["dt_proj"].astype(F32)
                            + lw["dt_proj_bias"].astype(F32))
    y = _scan(u, delta, b, c, -jnp.exp(lw["A_log"].astype(F32)),
              lw["D"].astype(F32))
    out = (y * jax.nn.silu(z)) @ lw["out_proj"].astype(F32)
    return _feed_forward(x + out, lw, eps)


@jax.jit
def _embed(table, ids):
    return jnp.take(table, ids, axis=0).astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, embed, *, eps):
    return _rms_norm(x, norm, eps) @ embed.astype(F32).T


def hidden_states(weights, ids, model: Dict):
    """The last layer's output (S, h) for token ids (S,)."""
    eps = float(model["rms_norm_eps"])
    x = _embed(weights.embed, jnp.asarray(ids, jnp.int32))
    for i in range(model["num_hidden_layers"]):
        lw = weights.layer(i)
        if is_attention(i, model):
            x = _attention_layer(x, lw, n_heads=model["num_attention_heads"],
                                 n_kv=model["num_key_value_heads"], eps=eps)
        else:
            x = _mamba_layer(x, lw, n_state=model["mamba_d_state"],
                             dt_rank=model["mamba_dt_rank"], eps=eps)
    return x


def logits_at(weights, ids: Sequence[np.ndarray],
              spans: Sequence[Tuple[int, int]], model: Dict) -> List:
    """For each row of token ids (unpadded, 1-D) the float32 logits at
    positions ``start .. stop - 1`` of its span, ``(stop - start, vocab)``
    (the logits at position p predict token p + 1)."""
    if not model.get("tie_word_embeddings", True):
        raise ValueError("this reference ties the head to the embedding")
    longest = max(len(row) for row in ids)
    padded = -(-longest // QUERY_BLOCK) * QUERY_BLOCK
    out = []
    with jax.default_matmul_precision("highest"):
        for row, (start, stop) in zip(ids, spans):
            full = np.zeros((padded,), np.int32)
            full[:len(row)] = row
            x = hidden_states(weights, full, model)
            out.append(_head(x[start:stop], weights.norm, weights.embed,
                             eps=float(model["rms_norm_eps"])))
    return out
