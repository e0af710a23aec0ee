"""Plain reference of a Llama-like decoder (Mistral-7B-v0.3, DeepSeek-LLM-7B,
...): forward pass and loss in straightforward ``jax.numpy`` and float32,
under ``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching tricks; nothing here imports the program.

It follows the published model as ``transformers`` implements it
(``modeling_mistral.py``): RMSNorm -> q/k/v projections without bias ->
rotary embedding in the half-rotation layout (``rotate_half``; frequencies
``theta^(-2i/d)``, cos/sin of ``concat(freqs, freqs)``) -> causal softmax
attention with each KV head shared by ``n_heads / n_kv_heads`` query heads
-> o_proj -> residual; RMSNorm -> ``down(silu(gate(x)) * up(x))`` ->
residual; final RMSNorm; untied ``lm_head``. No sliding window (v0.3 sets
``sliding_window`` null). The program's RoPE uses the same half-rotation
layout, so no weight permutation lies between the two; Mistral's own
``mistral-inference`` uses the interleaved layout, which differs from this
one by a fixed permutation of the q/k projection columns only.

Departures from the published description, all for memory and none in the
mathematics: weights arrive in whatever dtype they are served in and are
upcast to float32 ONE MATRIX AT A TIME (a 7B model in float32 does not fit
beside the system under test); attention runs over ``head_block`` query
heads at a time; the layers are a Python loop over ``weights.layer(i)``.

Weights are an object with ``embed`` (V, h), ``layer(i)`` -> dict of
``q_proj k_proj v_proj o_proj gate_proj up_proj down_proj`` as (in, out)
matrices (``transformers`` stores (out, in); ``y = x @ W`` here) and
``input_layernorm post_attention_layernorm`` (h,), ``norm`` (h,) and
``lm_head`` (h, V). The model is a dict with the published keys.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


def head_dim(model: Dict) -> int:
    return model.get("head_dim") or (model["hidden_size"]
                                     // model["num_attention_heads"])


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "d", "eps",
                                             "theta", "head_block"))
def _layer(x, lw, *, n_heads, n_kv, d, eps, theta, head_block):
    """One decoder layer on x (B, S, h) in float32."""
    b, s, _ = x.shape
    cos, sin = _rope(s, d, theta)
    xn = _rms_norm(x, lw["input_layernorm"], eps)
    q = (xn @ lw["q_proj"].astype(F32)).reshape(b, s, n_heads, d)
    k = (xn @ lw["k_proj"].astype(F32)).reshape(b, s, n_kv, d)
    v = (xn @ lw["v_proj"].astype(F32)).reshape(b, s, n_kv, d)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    q = q * cos + _rotate_half(q) * sin
    k = k * cos + _rotate_half(k) * sin
    group = n_heads // n_kv
    causal = jnp.tril(jnp.ones((s, s), bool))
    outs = []
    for h0 in range(0, n_heads, head_block):
        heads = range(h0, min(h0 + head_block, n_heads))
        kv = jnp.array([h // group for h in heads])
        qb = q[:, :, h0:h0 + len(heads)]                 # (B, S, hb, d)
        kb, vb = k[:, :, kv], v[:, :, kv]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, kb) / jnp.sqrt(F32(d))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(scores, axis=-1), vb))
    attn = jnp.concatenate(outs, axis=2).reshape(b, s, n_heads * d)
    x = x + attn @ lw["o_proj"].astype(F32)
    xn = _rms_norm(x, lw["post_attention_layernorm"], eps)
    gate = jax.nn.silu(xn @ lw["gate_proj"].astype(F32))
    up = xn @ lw["up_proj"].astype(F32)
    return x + (gate * up) @ lw["down_proj"].astype(F32)


@jax.jit
def _embed(table, ids):
    return jnp.take(table, ids, axis=0).astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    return _rms_norm(x, norm, eps) @ lm_head.astype(F32)


def forward(weights, ids, model: Dict, head_block: int = 8):
    """Logits (B, S, V) in float32 for token ids (B, S)."""
    with jax.default_matmul_precision("highest"):
        x = _embed(weights.embed, jnp.asarray(ids, jnp.int32))
        for i in range(model["num_hidden_layers"]):
            x = _layer(x, weights.layer(i),
                       n_heads=model["num_attention_heads"],
                       n_kv=model["num_key_value_heads"], d=head_dim(model),
                       eps=float(model["rms_norm_eps"]),
                       theta=float(model["rope_theta"]),
                       head_block=head_block)
        return _head(x, weights.norm, weights.lm_head,
                     eps=float(model["rms_norm_eps"]))


def loss(weights, ids, labels, model: Dict, head_block: int = 8) -> float:
    """Mean next-token cross-entropy (natural log) of ``labels`` (B, S)."""
    logits = forward(weights, ids, model, head_block)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.asarray(labels, jnp.int32)[..., None], axis=-1)[..., 0]
    return float(-jnp.mean(picked))
