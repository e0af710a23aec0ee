"""Plain reference of a LongCat-Flash decoder (shortcut-connected MoE with
zero-compute experts): the logits of a full forward pass in straightforward
``jax.numpy`` and float32, under ``jax.default_matmul_precision("highest")``.
The EXPANDED form of multi-head latent attention: keys and values of every
head are built through ``kv_b_proj`` for every position; nothing is absorbed
into the queries, there is no cache and no kernel; nothing here imports the
program or another reference.

For x (positions, hidden), every norm a weighted RMS with ``rms_norm_eps``,
no bias anywhere, ``x0 = embed[ids]``, ``logits = rms(x_L; norm) @ lm_head``
(untied). Layer l has two sub-layers i = 0, 1 (own norms, attention, dense
feed-forward) and ONE router and ONE set of experts (``layer``)::

    x  = x + MLA_0(rms(x; input_layernorm_0))
    m  = rms(x; post_attention_layernorm_0)
    e  = MoE(m)                              # the shortcut: taken here ...
    x  = x + FFN_0(m)
    x  = x + MLA_1(rms(x; input_layernorm_1))
    x  = x + FFN_1(rms(x; post_attention_layernorm_1)) + e    # ... added here

- ``MLA_i(a)`` (``attention``): ``c_q = rms(a q_a_proj; q_a_layernorm)``
  (``q_lora_rank``); ``q = s_q c_q q_b_proj`` as (H, ``qk_nope_head_dim`` +
  ``qk_rope_head_dim``), ``s_q = (hidden_size / q_lora_rank)^0.5`` where
  ``mla_scale_q_lora``; ``[c | k_rope] = a kv_a_proj``, ``c_kv = s_kv rms(c;
  kv_a_layernorm)``, ``s_kv = (hidden_size / kv_lora_rank)^0.5`` where
  ``mla_scale_kv_lora`` (the latent, not ``k_rope``); ``[k_nope_h | v_h] =
  c_kv kv_b_proj`` as (H, ``qk_nope_head_dim`` + ``v_head_dim``); the rotary
  embedding (``rope_theta``, no scaling; ASSUMED the half rotation, pair i =
  dimensions i and i + d/2) on ``q``'s last ``qk_rope_head_dim`` numbers of
  each head and on ``k_rope`` (one a position, shared by the heads); ``k_h =
  [k_nope_h | k_rope]``; softmax attention, mask ``key_pos <= pos``, scale
  ``(qk_nope_head_dim + qk_rope_head_dim)^-0.5``; out ``concat_h(o_h)
  o_proj``.
- ``FFN_i(m) = down(silu(gate(m)) * up(m))``.
- ``MoE(m)`` (``router_weights``, ``expert_branch``): ``s = softmax(m @
  router)`` over the router's outputs, its routed experts then
  ``zero_expert_num`` zero-compute ones; the ``moe_topk`` largest of ``s +
  expert_bias`` are chosen; ``w = routed_scaling_factor s`` at the chosen
  (no bias, not renormalised); ``e = sum_e w_e E_e(m)`` with ``E_e`` a SwiGLU
  for a routed expert and ``E_e(m) = m`` for a zero-compute one. Of the
  routed experts only those HELD are computed: ``experts`` has the weights
  of experts ``first_expert .. first_expert + E - 1`` (a chip's share; with
  every routed expert held it is the uncut layer), and what the others would
  add is left out; the identities are all computed.

Departures for memory, none in the mathematics: weights arrive in whatever
dtype they are served in and are upcast to float32 one matrix (one expert) at
a time; attention runs over blocks of ``QUERY_BLOCK`` queries and
``HEAD_BLOCK`` heads; the routed experts are a loop over the held experts,
each applied to every position and weighted by the position's weight for it
(zero where the router did not choose it); every row is padded to one length
so that each part compiles once. Only ``logits_at`` is offered.

Weights are an object with ``embed`` (V, h), ``norm`` (h,), ``lm_head`` (h,
V) and ``layer(l)`` -> ``{"sub": [two of {"input_layernorm",
"post_attention_layernorm" (h,), "q_a_layernorm" (q_lora_rank,),
"kv_a_layernorm" (kv_lora_rank,), "q_a_proj q_b_proj kv_a_proj kv_b_proj
o_proj" as (in, out) matrices (y = x @ W; kv_b_proj's columns head-major, a
head's qk_nope_head_dim then its v_head_dim), "mlp": {"gate_proj", "up_proj",
"down_proj"}}], "router": (h, E_all) float32, "expert_bias": (E_all,),
"experts": {"gate_proj": (E, h, m), "up_proj": (E, h, m), "down_proj": (E, m,
h)}}``. The model is a dict with the published keys and ``first_expert``.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256
HEAD_BLOCK = 16


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope_tables(seq_len: int, dim: int, theta: float):
    """cos, sin (seq_len, dim) of the plain rotary embedding."""
    inv_freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = jnp.outer(jnp.arange(seq_len, dtype=F32),
                      jnp.asarray(inv_freq, F32))
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def lora_scales(model: Dict) -> Tuple[float, float]:
    """(s_q, s_kv) of the module doc."""
    h = model["hidden_size"]
    return ((h / model["q_lora_rank"]) ** 0.5
            if model["mla_scale_q_lora"] else 1.0,
            (h / model["kv_lora_rank"]) ** 0.5
            if model["mla_scale_kv_lora"] else 1.0)


def _swiglu(x, w):
    gate = jax.nn.silu(x @ w["gate_proj"].astype(F32))
    return (gate * (x @ w["up_proj"].astype(F32))) @ w["down_proj"].astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "nope", "rope", "v_dim", "kv_rank", "eps", "theta", "s_q",
    "s_kv"))
def attention(x, lw, *, n_heads, nope, rope, v_dim, kv_rank, eps, theta, s_q,
              s_kv):
    """``MLA(rms(x; input_layernorm))``, x (S, h) float32, in the expanded
    form; ``lw`` one sub-layer's weights."""
    s = x.shape[0]
    a = _rms_norm(x, lw["input_layernorm"], eps)
    c_q = _rms_norm(a @ lw["q_a_proj"].astype(F32), lw["q_a_layernorm"], eps)
    q = (s_q * c_q @ lw["q_b_proj"].astype(F32)).reshape(
        s, n_heads, nope + rope)
    kv_a = a @ lw["kv_a_proj"].astype(F32)
    c_kv = s_kv * _rms_norm(kv_a[:, :kv_rank], lw["kv_a_layernorm"], eps)
    kv = (c_kv @ lw["kv_b_proj"].astype(F32)).reshape(s, n_heads,
                                                      nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    cos, sin = rope_tables(s, rope, theta)
    k_rope = kv_a[:, kv_rank:]
    k_rope = k_rope * cos + _rotate_half(k_rope) * sin           # (S, rope)
    q_rope = q[..., nope:]
    q_rope = q_rope * cos[:, None] + _rotate_half(q_rope) * sin[:, None]
    q_nope = q[..., :nope]
    key_pos = jnp.arange(s)[None, :]
    scale = (nope + rope) ** -0.5

    def block(start):
        """Attention of QUERY_BLOCK queries from ``start`` on, all heads."""
        mask = key_pos <= start + jnp.arange(QUERY_BLOCK)[:, None]
        cut = lambda z: jax.lax.dynamic_slice_in_dim(z, start, QUERY_BLOCK, 0)
        qn, qr = cut(q_nope), cut(q_rope)
        outs = []
        for h0 in range(0, n_heads, HEAD_BLOCK):
            hs = slice(h0, h0 + HEAD_BLOCK)
            scores = (jnp.einsum("qhd,khd->hqk", qn[:, hs], k_nope[:, hs])
                      + jnp.einsum("qhd,kd->hqk", qr[:, hs], k_rope)) * scale
            scores = jnp.where(mask[None], scores, -jnp.inf)
            outs.append(jnp.einsum("hqk,khd->qhd",
                                   jax.nn.softmax(scores, axis=-1), v[:, hs]))
        return jnp.concatenate(outs, axis=1)                # (B, heads, v)

    attn = jax.lax.map(block, jnp.arange(0, s, QUERY_BLOCK))
    return attn.reshape(s, n_heads * v_dim) @ lw["o_proj"].astype(F32)


def router_weights(m, router, expert_bias, *, top_k, scaling_factor):
    """(S, E_all) float32: each position's weight for each of the router's
    outputs, zero where it was not chosen (module doc)."""
    scores = jax.nn.softmax(m @ router.astype(F32), axis=-1)
    n_out = scores.shape[-1]
    chosen = jnp.argsort(-(scores + expert_bias.astype(F32)), axis=-1,
                         stable=True)[:, :top_k]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(n_out), axis=1)
    return jnp.where(picked, scores, 0.0) * scaling_factor


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scaling_factor", "zero_experts", "first_expert"))
def expert_branch(m, router, expert_bias, experts, *, top_k, scaling_factor,
                  zero_experts, first_expert):
    """``MoE(m)`` as its two parts, (S, h) each: what the HELD routed experts
    add, and what the zero-compute experts add."""
    weight = router_weights(m, router, expert_bias, top_k=top_k,
                            scaling_factor=scaling_factor)
    n_routed = weight.shape[-1] - zero_experts
    held = experts["gate_proj"].shape[0]
    held_weight = weight[:, first_expert:first_expert + held]

    def one_expert(total, ew):
        gate, up, down, w_e = ew
        out = _swiglu(m, {"gate_proj": gate, "up_proj": up,
                          "down_proj": down})
        return total + w_e[:, None] * out, None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (experts["gate_proj"], experts["up_proj"], experts["down_proj"],
         held_weight.T))
    identity = jnp.sum(weight[:, n_routed:], axis=-1, keepdims=True) * m
    return routed, identity


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, w, *, eps):
    return _rms_norm(x, w, eps)


@jax.jit
def _dense(m, mlp):
    return _swiglu(m, mlp)


def layer(x, lw: Dict, model: Dict):
    """One layer of the module doc, x (S, h) float32."""
    eps = float(model["rms_norm_eps"])
    s_q, s_kv = lora_scales(model)
    attend = functools.partial(
        attention, n_heads=model["num_attention_heads"],
        nope=model["qk_nope_head_dim"], rope=model["qk_rope_head_dim"],
        v_dim=model["v_head_dim"], kv_rank=model["kv_lora_rank"], eps=eps,
        theta=float(model["rope_theta"]), s_q=s_q, s_kv=s_kv)
    sub0, sub1 = lw["sub"]
    x = x + attend(x, sub0)
    m = _normed(x, sub0["post_attention_layernorm"], eps=eps)
    routed, identity = expert_branch(
        m, lw["router"], lw["expert_bias"], lw["experts"],
        top_k=int(model["moe_topk"]),
        scaling_factor=float(model["routed_scaling_factor"]),
        zero_experts=int(model["zero_expert_num"]),
        first_expert=int(model.get("first_expert", 0)))
    x = x + _dense(m, sub0["mlp"])
    x = x + attend(x, sub1)
    return x + _dense(_normed(x, sub1["post_attention_layernorm"], eps=eps),
                      sub1["mlp"]) + routed + identity


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
    for i in range(model["num_layers"]):
        x = layer(x, weights.layer(i), model)
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
