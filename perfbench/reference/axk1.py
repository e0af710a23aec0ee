"""Plain reference of an A.X-K1 decoder (``model_type: axk1``, DeepSeek-V3
family conventions): the logits of a full forward pass in straightforward
``jax.numpy`` and float32, under ``jax.default_matmul_precision("highest")``.
The EXPANDED form of multi-head latent attention: keys and values of every
head are built through ``kv_b_proj`` for every position; nothing is absorbed
into the queries, there is no cache and no kernel; nothing here imports the
program.

The layer, for x (positions, hidden), H heads, pre-norm residual blocks, all
norms RMS with ``rms_norm_eps``:

- ``x0 = embed[ids]``; ``logits = rms(x_L; norm) @ lm_head`` (untied).
- attention: ``a = rms(x; input_layernorm)``; ``c_q = rms(a q_a_proj;
  q_a_layernorm)`` (``q_lora_rank``); ``q = c_q q_b_proj`` as (H,
  ``qk_nope_head_dim`` + ``qk_rope_head_dim``); ``[c_kv | k_rope] = a
  kv_a_proj`` (``kv_lora_rank`` | ``qk_rope_head_dim``), ``c_kv = rms(c_kv;
  kv_a_layernorm)``; ``[k_nope_h | v_h] = c_kv kv_b_proj`` as (H,
  ``qk_nope_head_dim`` + ``v_head_dim``); the rotary embedding on ``q``'s
  last ``qk_rope_head_dim`` numbers of each head and on ``k_rope`` (one a
  position, shared by the heads); ``k_h = [k_nope_h | k_rope]``; softmax
  attention, mask ``key_pos <= pos``, scale ``(qk_nope_head_dim +
  qk_rope_head_dim)^-0.5 * m^2`` with ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``; ``x = x + concat_h(o_h) o_proj``.
- the rotary embedding is YaRN's (``rope_scaling``): pair i of the
  ``qk_rope_head_dim`` / 2 turns at ``theta^(-2i/d)`` blended with that over
  ``factor`` by a linear ramp between the pairs that make ``beta_fast`` and
  ``beta_slow`` turns over ``original_max_position_embeddings``; cos and sin
  are multiplied by ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)``. ASSUMED (the configuration's ``assumed``): the pairing
  is the half rotation, pair i = dimensions i and i + d/2 of the rotary
  part as the projections give it.
- feed-forward: ``m = rms(x; post_attention_layernorm)``; layers ``l <
  first_k_dense_replace``: ``down(silu(gate(m)) * up(m))`` of width
  ``intermediate_size``; the others: ``s = sigmoid(m @ router)`` over the
  router's outputs (all of them, as published); on ``s + expert_bias`` a
  group (``n_group`` equal consecutive groups) scores the sum of its two
  best, the best ``topk_group`` groups stay, and the ``num_experts_per_tok``
  largest inside them are chosen; ``w = s`` at the chosen (no bias), over
  their sum + 1e-20 where ``norm_topk_prob``, times
  ``routed_scaling_factor``; ``f = shared(m) + sum_e w_e expert_e(m)`` over
  the experts HELD: ``experts`` has the weights of experts ``first_expert
  .. first_expert + E - 1`` only (a chip's share; ``first_expert`` 0 and
  every expert is the whole layer), and what the others would add is left
  out. ASSUMED: ``topk_method: "none"`` read as this group-limited top-k.

Departures for memory, none in the mathematics: weights arrive in whatever
dtype they are served in and are upcast to float32 one matrix (one expert)
at a time; attention runs over blocks of ``QUERY_BLOCK`` queries and
``HEAD_BLOCK`` heads; the routed experts are a loop over the held experts,
each applied to every position and weighted by the position's weight for it
(zero where the router did not choose it); every row is padded to one
length so that each layer compiles once. Only ``logits_at`` is offered.

Weights are an object with ``embed`` (V, h), ``norm`` (h,), ``lm_head`` (h,
V) and ``layer(i)`` -> dict of ``input_layernorm post_attention_layernorm``
(h,), ``q_a_layernorm`` (q_lora_rank,), ``kv_a_layernorm`` (kv_lora_rank,),
``q_a_proj q_b_proj kv_a_proj kv_b_proj o_proj`` as (in, out) matrices (``y
= x @ W``; ``kv_b_proj``'s columns head-major, a head's ``qk_nope_head_dim``
then its ``v_head_dim``), and ``mlp``: for a dense layer ``{"gate_proj",
"up_proj", "down_proj"}``, for an expert layer ``{"router": (h, E_all)
float32, "expert_bias": (E_all,), "experts": {"gate_proj": (E, h, m),
"up_proj": (E, h, m), "down_proj": (E, m, h)}, "shared": {"gate_proj",
"up_proj", "down_proj"}}``. The model is a dict with the published keys and
``first_expert``.
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
HEAD_BLOCK = 16


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_tables(seq_len: int, dim: int, theta: float, scaling):
    """cos, sin (seq_len, dim) of the rotary embedding; ``scaling`` a YaRN
    ``rope_scaling`` group (as a hashable tuple of items) or None."""
    pairs = np.arange(0, dim, 2, dtype=np.float64) / dim
    inv_freq = 1.0 / theta ** pairs
    table_scale = 1.0
    if scaling is not None:
        y = dict(scaling)
        factor, orig = y["factor"], y["original_max_position_embeddings"]

        def pair_with_turns(turns):
            return dim * math.log(orig / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))
        low = max(math.floor(pair_with_turns(y["beta_fast"])), 0)
        high = min(math.ceil(pair_with_turns(y["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        inv_freq = inv_freq / factor * (1.0 - keep) + inv_freq * keep
        table_scale = _mscale(factor, y["mscale"]) \
            / _mscale(factor, y["mscale_all_dim"])
    freqs = jnp.outer(jnp.arange(seq_len, dtype=F32),
                      jnp.asarray(inv_freq, F32))
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb) * table_scale, jnp.sin(emb) * table_scale


def attention_scale(model: Dict) -> float:
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    y = model.get("rope_scaling")
    if y:
        scale *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _swiglu(x, w):
    gate = jax.nn.silu(x @ w["gate_proj"].astype(F32))
    return (gate * (x @ w["up_proj"].astype(F32))) @ w["down_proj"].astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "nope", "rope", "v_dim", "kv_rank", "eps", "theta", "scaling",
    "scale"))
def _attention(x, lw, *, n_heads, nope, rope, v_dim, kv_rank, eps, theta,
               scaling, scale):
    """x + the attention branch, x (S, h) float32, in the expanded form."""
    s = x.shape[0]
    a = _rms_norm(x, lw["input_layernorm"], eps)
    c_q = _rms_norm(a @ lw["q_a_proj"].astype(F32), lw["q_a_layernorm"], eps)
    q = (c_q @ lw["q_b_proj"].astype(F32)).reshape(s, n_heads, nope + rope)
    kv_a = a @ lw["kv_a_proj"].astype(F32)
    c_kv = _rms_norm(kv_a[:, :kv_rank], lw["kv_a_layernorm"], eps)
    kv = (c_kv @ lw["kv_b_proj"].astype(F32)).reshape(s, n_heads,
                                                      nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    cos, sin = yarn_tables(s, rope, theta, scaling)
    k_rope = kv_a[:, kv_rank:]
    k_rope = k_rope * cos + _rotate_half(k_rope) * sin           # (S, rope)
    q_rope = q[..., nope:]
    q_rope = q_rope * cos[:, None] + _rotate_half(q_rope) * sin[:, None]
    q_nope = q[..., :nope]
    key_pos = jnp.arange(s)[None, :]

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
    return x + attn.reshape(s, n_heads * v_dim) @ lw["o_proj"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_mlp(x, lw, *, eps):
    return x + _swiglu(_rms_norm(x, lw["post_attention_layernorm"], eps),
                       lw["mlp"])


def router_weights(m, router, expert_bias, *, n_group, topk_group, top_k,
                   norm_topk_prob, scaling_factor):
    """(S, E_all) float32: each position's weight for each of the router's
    experts, zero where it was not chosen (module doc)."""
    scores = jax.nn.sigmoid(m @ router.astype(F32))
    n_experts = scores.shape[-1]
    choice = scores + expert_bias.astype(F32)
    by_group = choice.reshape(-1, n_group, n_experts // n_group)
    best_two = -jnp.sort(-by_group, axis=-1)[..., :2]
    group_rank = jnp.argsort(-jnp.sum(best_two, axis=-1), axis=-1,
                             stable=True)[:, :topk_group]
    group_kept = jnp.any(group_rank[:, :, None] == jnp.arange(n_group), axis=1)
    expert_kept = jnp.repeat(group_kept, n_experts // n_group, axis=-1)
    chosen = jnp.argsort(-jnp.where(expert_kept, choice, -jnp.inf), axis=-1,
                         stable=True)[:, :top_k]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(n_experts), axis=1)
    weight = jnp.where(picked, scores, 0.0)
    if norm_topk_prob:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return weight * scaling_factor


@functools.partial(jax.jit, static_argnames=(
    "eps", "n_group", "topk_group", "top_k", "norm_topk_prob",
    "scaling_factor", "first_expert"))
def _expert_mlp(x, lw, *, eps, n_group, topk_group, top_k, norm_topk_prob,
                scaling_factor, first_expert):
    m = _rms_norm(x, lw["post_attention_layernorm"], eps)
    mlp = lw["mlp"]
    weight = router_weights(
        m, mlp["router"], mlp["expert_bias"], n_group=n_group,
        topk_group=topk_group, top_k=top_k, norm_topk_prob=norm_topk_prob,
        scaling_factor=scaling_factor)
    experts = mlp["experts"]
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
    return x + _swiglu(m, mlp["shared"]) + routed


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
    eps = float(model["rms_norm_eps"])
    y = model.get("rope_scaling")
    scaling = tuple(sorted(y.items())) if y else None
    x = _embed(weights.embed, jnp.asarray(ids, jnp.int32))
    for i in range(model["num_hidden_layers"]):
        lw = weights.layer(i)
        x = _attention(
            x, lw, n_heads=model["num_attention_heads"],
            nope=model["qk_nope_head_dim"], rope=model["qk_rope_head_dim"],
            v_dim=model["v_head_dim"], kv_rank=model["kv_lora_rank"],
            eps=eps, theta=float(model["rope_theta"]), scaling=scaling,
            scale=attention_scale(model))
        if i < model["first_k_dense_replace"]:
            x = _dense_mlp(x, lw, eps=eps)
        else:
            x = _expert_mlp(
                x, lw, eps=eps, n_group=model["n_group"],
                topk_group=model["topk_group"],
                top_k=model["num_experts_per_tok"],
                norm_topk_prob=bool(model["norm_topk_prob"]),
                scaling_factor=float(model["routed_scaling_factor"]),
                first_expert=int(model.get("first_expert", 0)))
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
