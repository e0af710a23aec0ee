"""Rotary position embedding (fused_rope equivalent).

Reference: fused_rope CUDA kernel family under paddle/fluid/operators/fused/
(SURVEY.md §2.2 "other fused family"). On TPU, rope is a cheap elementwise op
that XLA fuses into the surrounding attention projections, so the XLA form IS
the fused form; a Pallas variant adds nothing measurable.

Convention: NeoX/Llama half-rotation. Layout (B, S, H, D).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from ..core.dispatch import apply
from ..core.tensor import Tensor


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times: ``0.1 * mscale * ln(factor) + 1`` (1 where nothing is
    stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(beta_fast: float, beta_slow: float, head_dim: int,
                          base: float, original_max_position: int):
    """The rotary pairs YaRN's ramp runs between: below ``low`` a pair turns
    more than ``beta_fast`` times over the original context and keeps its
    frequency, above ``high`` it turns less than ``beta_slow`` times and is
    interpolated."""
    def pair_of(rotations):
        return (head_dim * math.log(original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    return (max(math.floor(pair_of(beta_fast)), 0),
            min(math.ceil(pair_of(beta_slow)), head_dim - 1))


def rope_inv_freq(head_dim: int, base: float = 10000.0, yarn=None):
    """(head_dim / 2,) float32 inverse frequencies ``base^(-2i / head_dim)``.
    ``yarn``: a ``rope_scaling`` group of type ``yarn`` (``factor``,
    ``beta_fast``, ``beta_slow``, ``original_max_position_embeddings``):
    each pair's frequency is then a blend of itself and itself over
    ``factor``, by a linear ramp over :func:`yarn_correction_range`."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                               / head_dim))
    if yarn is None:
        return inv_freq
    low, high = yarn_correction_range(
        yarn["beta_fast"], yarn["beta_slow"], head_dim, base,
        yarn["original_max_position_embeddings"])
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (max(high, low + 0.001) - low), 0.0, 1.0)
    return inv_freq / yarn["factor"] * ramp + inv_freq * (1.0 - ramp)


def rope_tables(positions, inv_freq, dtype=jnp.float32, mscale: float = 1.0):
    """cos and sin, (len(positions), head_dim) each, at ``positions`` (half
    rotation: pair i is dimensions i and i + head_dim / 2), times
    ``mscale`` (YaRN: ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)``)."""
    freqs = jnp.outer(positions.astype(jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return ((jnp.cos(emb) * mscale).astype(dtype),
            (jnp.sin(emb) * mscale).astype(dtype))


def build_rope_cache(seq_len: int, head_dim: int, base: float = 10000.0,
                     dtype=jnp.float32, position_offset: int = 0):
    t = jnp.arange(position_offset, position_offset + seq_len, dtype=jnp.float32)
    return rope_tables(t, rope_inv_freq(head_dim, base), dtype)


def apply_rope_array(q, k, cos, sin):
    """q, k: (B, S, H, D); cos/sin: (S, D) or (B, S, D)."""
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    q_out = qf * cos + _rotate_half(qf) * sin
    k_out = kf * cos + _rotate_half(kf) * sin
    return q_out.astype(q.dtype), k_out.astype(k.dtype)


def fused_rotary_position_embedding(q: Tensor, k: Tensor, cos, sin):
    """Parity with paddle.incubate.nn.functional.fused_rotary_position_embedding."""
    cos_v = cos._value if isinstance(cos, Tensor) else cos
    sin_v = sin._value if isinstance(sin, Tensor) else sin
    return apply(lambda a, b: apply_rope_array(a, b, cos_v, sin_v), q, k,
                 op_name="fused_rope")
