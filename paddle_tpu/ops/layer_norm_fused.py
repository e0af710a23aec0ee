"""Fused LayerNorm (forward + backward) — Pallas TPU kernel with XLA
fallback, mirroring ops/rms_norm.py's structure.

Rebuild target: the reference's fused LayerNorm CUDA kernels
(paddle/phi/kernels/gpu/layer_norm_kernel.cu — SURVEY.md §2.2). Motivation:
the round-4 ViT-L profile showed the encoder's 49 LayerNorm instances compiling to multiply_reduce +
convert_reduce chains worth 19.2 ms/step — a single-pass kernel holds the
row block in VMEM across mean, variance, normalize, and the backward's
three reductions.

Math (fp32 accumulation):
    mu = mean(x); var = mean((x-mu)^2); inv = rsqrt(var+eps)
    xhat = (x-mu)*inv;  y = xhat*w + b
    dx = inv * (wg - mean(wg) - xhat * mean(wg*xhat)),  wg = w*g
    dw = sum_rows(g*xhat);  db = sum_rows(g)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import use_pallas
from .rms_norm import _pick_block_rows


def _use_pallas_ln() -> bool:
    from ..flags import flag_value
    return use_pallas() and flag_value("use_pallas_layer_norm")


def _ln_ref(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    xhat = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (xhat * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    xhat = (x - mu) * jax.lax.rsqrt(var + eps)
    y_ref[...] = (xhat * w_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(y_ref.dtype)


def _bwd_kernel(x_ref, w_ref, g_ref, dx_ref, dw_ref, db_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * inv
    wg = w * g
    m1 = jnp.mean(wg, axis=-1, keepdims=True)
    m2 = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (inv * (wg - m1 - xhat * m2)).astype(dx_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dw_ref[...] += jnp.sum(g * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(g, axis=0, keepdims=True)


def _pallas_fwd(x2, w, b, eps, interpret=False):
    rows, h = x2.shape
    br = _pick_block_rows(rows, h)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(rows // br,),
        interpret=interpret,
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, h), x2.dtype),
    )(x2, w.reshape(1, h), b.reshape(1, h))


def _pallas_bwd(x2, w, g2, eps, interpret=False):
    rows, h = x2.shape
    br = _pick_block_rows(rows, h)
    dx, dw, db = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=(rows // br,),
        interpret=interpret,
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((br, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, h), x2.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
    )(x2, w.reshape(1, h), g2)
    return dx, dw.reshape(h), db.reshape(h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm_fused(x, w, b, eps=1e-5):
    y, _ = _ln_fwd(x, w, b, eps)
    return y


def _rows(x):
    r = 1
    for s in x.shape[:-1]:
        r *= s
    return r


def _ln_fwd(x, w, b, eps):
    h = x.shape[-1]
    rows = _rows(x)
    if _use_pallas_ln() and h % 128 == 0 and _pick_block_rows(rows, h):
        y = _pallas_fwd(x.reshape(rows, h), w, b, eps)
        return y.reshape(x.shape), (x, w, b)
    return _ln_ref(x, w, b, eps), (x, w, b)


def _ln_bwd(eps, res, g):
    x, w, b = res
    h = x.shape[-1]
    rows = _rows(x)
    if _use_pallas_ln() and h % 128 == 0 and _pick_block_rows(rows, h):
        dx, dw, db = _pallas_bwd(x.reshape(rows, h), w,
                                 g.reshape(rows, h), eps)
        return dx.reshape(x.shape), dw.astype(w.dtype), db.astype(b.dtype)
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (xf - mu) * inv
    wg = wf * gf
    m1 = jnp.mean(wg, axis=-1, keepdims=True)
    m2 = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    dx = (inv * (wg - m1 - xhat * m2)).astype(x.dtype)
    red = tuple(range(x.ndim - 1))
    dw = jnp.sum(gf * xhat, axis=red).astype(w.dtype)
    db = jnp.sum(gf, axis=red).astype(b.dtype)
    return dx, dw, db


layer_norm_fused.defvjp(_ln_fwd, _ln_bwd)
