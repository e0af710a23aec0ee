"""The SmallThinker reference (``smallthinker.py`` beside this file) computed
in float8 e4m3, the nearest precision below the bfloat16 the configuration
serves in: the control that the configuration's ``correct`` limits must
refuse. Its logits stand for a program that computed in float8.

What is rounded, each at a scale that puts the largest magnitude along the
named axis at 448 (e4m3's largest): every matrix the program serves in
bfloat16 a column (the embedding a row: one token's vector), and the
activations entering a matrix product a row (the attention's normed input,
the experts' normed input and their hidden units before ``down``, and the
final norm's output before the head). The router stays in float32 on the
unrounded input, as the configuration states it; norms are not rounded.
Everything else is the reference's own code: its router, its attention, its
padding to ``QUERY_BLOCK`` and ``logits_at``'s contract, so a configuration
whose ``reference`` names this file has ``perfbench/checks.py`` hold the
served tokens against float8 logits.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import harness

reference = harness.load_module("perfbench/reference/smallthinker.py")
F32 = jnp.float32
E4M3_MAX = 448.0


@functools.partial(jax.jit, static_argnames=("axis",))
def round_float8(x, axis: int):
    """``x`` rounded to float8 e4m3 at a scale a slice along ``axis``, back
    in float32."""
    x = jnp.asarray(x, F32)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


class Float8Weights:
    """The reference's weights object with every served matrix rounded a
    column (the embedding a row); norms and the float32 router as given."""

    def __init__(self, weights):
        self._w = weights
        self.embed = round_float8(weights.embed, -1)
        self.norm = weights.norm
        self.lm_head = round_float8(weights.lm_head, -2)

    def layer(self, l: int) -> Dict:
        lw = dict(self._w.layer(l))
        for k in ("q_proj", "k_proj", "v_proj", "o_proj"):
            lw[k] = round_float8(lw[k], -2)
        lw["experts"] = {k: round_float8(v, -2)
                         for k, v in lw["experts"].items()}
        return lw


@functools.partial(jax.jit, static_argnames=("eps",))
def experts(x, weight, lw, *, eps):
    """The reference's expert branch with the experts' input and hidden
    units rounded a row."""
    m = round_float8(reference._rms_norm(x, lw["post_attention_layernorm"],
                                         eps), -1)

    def one_expert(total, ew):
        gate, up, down, w_e = ew
        h = jax.nn.relu(m @ gate) * (m @ up)
        return total + w_e[:, None] * (round_float8(h, -1) @ down), None

    e = lw["experts"]
    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (e["gate_proj"], e["up_proj"], e["down_proj"], weight.T))
    return out


def layer(x, lw, l: int, model: Dict):
    """The reference's layer, the attention fed its input rounded a row."""
    eps = float(model["rms_norm_eps"])
    a = reference._norm(x, lw["input_layernorm"], eps=eps)
    weight = reference.router_weights(
        a, lw["router"], top_k=int(model["moe_num_active_primary_experts"]),
        norm=bool(model["norm_topk_prob"]))
    x = x + reference.attention(
        round_float8(a, -1), lw, n_heads=model["num_attention_heads"],
        n_kv=model["num_key_value_heads"], d=model["head_dim"],
        theta=float(model["rope_theta"]),
        rope=bool(model["rope_layout"][l]),
        window=int(model["sliding_window_size"])
        if model["sliding_window_layout"][l] else None)
    return x + experts(x, weight, lw, eps=eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    return round_float8(reference._rms_norm(x, norm, eps), -1) @ lm_head


def logits_at(weights, ids: Sequence[np.ndarray],
              spans: Sequence[Tuple[int, int]], model: Dict) -> List:
    """``reference.logits_at`` computed in float8 (module doc); ``weights``
    the reference's weights object, rounded here."""
    weights = Float8Weights(weights)
    block = reference.QUERY_BLOCK
    longest = max(len(row) for row in ids)
    padded = -(-longest // block) * block
    out = []
    with jax.default_matmul_precision("highest"):
        for row, (start, stop) in zip(ids, spans):
            full = np.zeros((padded,), np.int32)
            full[:len(row)] = row
            x = reference._embed(weights.embed, jnp.asarray(full))
            for l in range(model["num_hidden_layers"]):
                x = layer(x, weights.layer(l), l, model)
            out.append(_head(x[start:stop], weights.norm, weights.lm_head,
                             eps=float(model["rms_norm_eps"])))
    return out
