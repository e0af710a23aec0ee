"""Flash attention (forward + backward) — Pallas TPU kernels + XLA fallback.

Rebuild of the reference's ``flash_attn`` path: CUDA glue
paddle/phi/kernels/gpu/flash_attn_kernel.cu + vendored libflashattn, Python
surface python/paddle/nn/functional/flash_attention.py (SURVEY.md §2.2).
Here the kernel itself is written in Pallas (online-softmax tiling over KV
blocks; fp32 accumulators in VMEM; LSE saved for the backward pass), which is
the TPU-native equivalent of FlashAttention-2.

Internal layout: (BH, S, D) with batch*heads flattened into the leading grid
dimension. Public entry points accept the paddle layout (B, S, H, D).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import use_pallas
from ..core.dispatch import apply, unwrap
from ..core.tensor import Tensor
from .. import random as _random

_NEG_INF = -1e30


def _mult(a: int, b: int) -> bool:
    return a % b == 0


# ===========================================================================
# Forward kernel
# ===========================================================================
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, bq, bk, nkv,
                has_seg=False, kv_valid=None, causal_offset=0):
    if has_seg:
        segq_ref, segk_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (j * bk < (i + 1) * bq + causal_offset) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (BQ, BK)
        if causal or kv_valid is not None or has_seg:
            row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = jnp.ones((bq, bk), dtype=bool)
            if causal:
                keep &= row + causal_offset >= col
            if kv_valid is not None:
                # static bound: keys beyond the unpadded length are masked
                keep &= col < kv_valid
            if has_seg:
                keep &= (segq_ref[0, 0][:, None] == segk_ref[0, 0][None, :])
            s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if causal or kv_valid is not None or has_seg:
            # exp(s - m) degenerates to 1 when EVERY entry of the block is
            # masked (m == s == -inf); zero masked probabilities explicitly
            # so fully-masked rows (in-row padding) produce 0, not mean(v)
            p = jnp.where(keep, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nkv - 1)
    def _finalize():
        l = l_ref[:, :1]
        m = m_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        # lse is carried as (BH, 1, S): a lane-major row per bh so the block
        # shape (1, 1, bq) satisfies Mosaic's (sublane, lane) tiling rule.
        lse_ref[0, 0] = (m + jnp.log(safe_l))[:, 0]


def _seg3(seg, bh):
    """Normalize segment ids for the kernels: (S,) -> shared (1, 1, S);
    (R, S) -> per-row (R, 1, S) with bh = R * rep heads per row. Returns
    (array, row_of_bh) where row_of_bh maps grid index b -> seg row."""
    if seg.ndim == 1:
        return seg[None, None, :], (lambda b: 0)
    rep = bh // seg.shape[0]
    return seg[:, None, :], (lambda b: b // rep)


def _flash_fwd_pallas(q, k, v, scale, causal, bq, bk, seg_q=None, seg_k=None,
                      kv_valid=None, causal_offset=0, interpret=False,
                      kv_rep=1):
    """``kv_rep`` implements GQA without materializing repeated KV: q has
    B*Hq rows, k/v have B*Hk rows (Hq = Hk*kv_rep, heads consecutive per
    batch entry), and the k/v BlockSpec index map shares each KV row across
    its kv_rep query heads."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nkv = sq // bq, sk // bk
    grid = (bh, nq, nkv)
    has_seg = seg_q is not None
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b // kv_rep, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b // kv_rep, j, 0)),
    ]
    args = [q, k, v]
    if has_seg:
        # segment ids travel lane-major as (R, 1, S): one row shared by
        # every bh (packed varlen) or one per batch row (packed batches)
        sq3, rowq = _seg3(seg_q, bh)
        sk3, rowk = _seg3(seg_k, bh)
        in_specs += [
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (rowq(b), 0, i)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (rowk(b), 0, j))]
        args += [sq3, sk3]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nkv=nkv, has_seg=has_seg,
                          kv_valid=kv_valid, causal_offset=causal_offset),
        name="flash_attention_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out, lse[:, 0]


# ===========================================================================
# Backward kernels
# ===========================================================================
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, bq, bk, nkv, has_seg=False, kv_valid=None,
                   causal_offset=0):
    if has_seg:
        segq_ref, segk_ref, dq_ref, acc_ref = rest
    else:
        dq_ref, acc_ref = rest
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (j * bk < (i + 1) * bq + causal_offset) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        p = jnp.exp(s - lse)
        if causal or kv_valid is not None or has_seg:
            row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = jnp.ones((bq, bk), dtype=bool)
            if causal:
                keep &= row + causal_offset >= col
            if kv_valid is not None:
                keep &= col < kv_valid
            if has_seg:
                keep &= (segq_ref[0, 0][:, None] == segk_ref[0, 0][None, :])
            p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(j == nkv - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, bq, bk, nq, has_seg=False, kv_valid=None,
                    causal_offset=0):
    if has_seg:
        segq_ref, segk_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    j = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = ((i + 1) * bq + causal_offset > j * bk) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        p = jnp.exp(s - lse)
        if causal or kv_valid is not None or has_seg:
            row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = jnp.ones((bq, bk), dtype=bool)
            if causal:
                keep &= row + causal_offset >= col
            if kv_valid is not None:
                keep &= col < kv_valid
            if has_seg:
                keep &= (segq_ref[0, 0][:, None] == segk_ref[0, 0][None, :])
            p = jnp.where(keep, p, 0.0)
        pt = p.astype(do.dtype)
        dv_acc[...] += jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32) * scale

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, g, scale, causal, bq, bk,
                      seg_q=None, seg_k=None, kv_valid=None, causal_offset=0,
                      interpret=False, kv_rep=1):
    """With ``kv_rep`` > 1 (GQA), k/v carry B*Hk rows shared across query
    heads via index maps; dk/dv are reduced over each KV row's kv_rep query
    heads before returning, so the caller always gets KV-shaped grads."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nkv = sq // bq, sk // bk
    has_seg = seg_q is not None
    # lse/delta travel as (BH, 1, S) — see _fwd_kernel note on Mosaic tiling.
    lse3 = lse[:, None, :]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]

    dq_in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b // kv_rep, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b // kv_rep, j, 0)),
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
    ]
    dq_args = [q, k, v, g, lse3, delta]
    if has_seg:
        sq3, rowq = _seg3(seg_q, bh)
        sk3, rowk = _seg3(seg_k, bh)
        dq_in_specs += [
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (rowq(b), 0, i)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (rowk(b), 0, j))]
        dq_args += [sq3, sk3]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nkv=nkv, has_seg=has_seg,
                          kv_valid=kv_valid, causal_offset=causal_offset),
        name="flash_attention_bwd_dq",
        grid=(bh, nq, nkv),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(*dq_args)

    dkv_in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b // kv_rep, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b // kv_rep, j, 0)),
        pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i)),
        pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i)),
    ]
    dkv_args = [q, k, v, g, lse3, delta]
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, 1, bq), lambda b, j, i: (rowq(b), 0, i)),
            pl.BlockSpec((1, 1, bk), lambda b, j, i: (rowk(b), 0, j))]
        dkv_args += [sq3, sk3]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, has_seg=has_seg,
                          kv_valid=kv_valid, causal_offset=causal_offset),
        name="flash_attention_bwd_dkv",
        grid=(bh, nkv, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(*dkv_args)
    if kv_rep != 1:
        # per-query-head dk/dv partials -> reduce over each KV row's group
        # (consecutive q heads share a KV head)
        dk = dk.reshape(bh // kv_rep, kv_rep, sk, d).sum(axis=1)
        dv = dv.reshape(bh // kv_rep, kv_rep, sk, d).sum(axis=1)
    return dq, dk, dv


# ===========================================================================
# XLA reference path (oracle + fallback), layout (BH, S, D)
# ===========================================================================
def _attn_ref(q, k, v, scale, causal):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ===========================================================================
# custom_vjp dispatcher
# ===========================================================================
def _pick_blocks(sq, sk):
    def pick(s):
        for b in (512, 256, 128):
            if s % b == 0:
                return b
        return None
    return pick(sq), pick(sk)


def _pad_to(s: int, mult: int = 128) -> int:
    return -(-s // mult) * mult


# checkpoint names of the Pallas forward's ``out`` and ``lse`` residuals
SAVED_RESIDUALS = ("flash_attention_out", "flash_attention_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bhsd_inner(q, k, v, scale, causal, kv_valid, causal_offset):
    out, _ = _fa_fwd(q, k, v, scale, causal, kv_valid, causal_offset)
    return out


def _pallas_ok(q, k):
    bq, bk = _pick_blocks(q.shape[1], k.shape[1])
    # d=64 compiles cleanly under Mosaic (verified on chip: fwd+bwd parity
    # 4e-3 bf16) — required for the encoder family, whose hd = 1024/16 =
    # 64. Other non-128 multiples (192, 320, ...) stay on the fallback
    # until verified. Sequence threshold is measured: at S<=256 the XLA
    # einsum path wins (ViT-L S=197->256: 222 vs 215 img/s end-to-end);
    # from S=512 up the kernel wins (BERT S=512 d=64: 6.75 vs 10.8 ms;
    # llama S=2048 d=128: 1.7x) and the score materialization the kernel
    # avoids grows quadratically.
    d = q.shape[2]
    return use_pallas() and bq is not None and bk is not None and \
        (_mult(d, 128) or d == 64) and \
        max(q.shape[1], k.shape[1]) >= 512


def _fa_fwd(q, k, v, scale, causal, kv_valid, causal_offset):
    if _pallas_ok(q, k):
        bq, bk = _pick_blocks(q.shape[1], k.shape[1])
        out, lse = _flash_fwd_pallas(q, k, v, scale, causal, bq, bk,
                                     kv_valid=kv_valid,
                                     causal_offset=causal_offset)
        # the two residuals only this S^2 kernel can give back: a
        # jax.checkpoint whose policy saves SAVED_RESIDUALS keeps them and
        # its backward does not run the kernel again (q, k, v are still
        # recomputed); anywhere else a name is the identity
        out = checkpoint_name(out, SAVED_RESIDUALS[0])
        lse = checkpoint_name(lse, SAVED_RESIDUALS[1])
        return out, (q, k, v, out, lse)
    out = _attn_ref_kv(q, k, v, scale, causal, kv_valid, causal_offset)
    return out, (q, k, v, out, None)


def _fa_bwd(scale, causal, kv_valid, causal_offset, res, g):
    q, k, v, out, lse = res
    if lse is not None and _pallas_ok(q, k):
        bq, bk = _pick_blocks(q.shape[1], k.shape[1])
        return _flash_bwd_pallas(q, k, v, out, lse, g, scale, causal, bq, bk,
                                 kv_valid=kv_valid,
                                 causal_offset=causal_offset)
    _, vjp = jax.vjp(
        lambda a, b, c: _attn_ref_kv(a, b, c, scale, causal, kv_valid,
                                     causal_offset),
        q, k, v)
    return vjp(g)


_flash_bhsd_inner.defvjp(_fa_fwd, _fa_bwd)


def _attn_ref_kv(q, k, v, scale, causal, kv_valid, causal_offset=0):
    """Reference path with the kernel's mask semantics: causal keeps
    row + causal_offset >= col (causal_offset = sk - sq of the ORIGINAL
    shapes — the end-aligned decode convention, 0 for self-attention) and
    cols >= kv_valid are masked. Slicing k instead would shift _attn_ref's
    end-aligned convention under padding."""
    if kv_valid is None and causal_offset == 0:
        return _attn_ref(q, k, v, scale, causal)
    sq, sk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    keep = (jnp.arange(sk) < (kv_valid if kv_valid is not None else sk)
            )[None, :]
    if causal:
        keep = keep & (jnp.arange(sq)[:, None] + causal_offset
                       >= jnp.arange(sk)[None, :])
    s = jnp.where(keep[None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def flash_attention_bhsd(q, k, v, scale, causal):
    """(BH, S, D) flash attention; differentiable; pallas on TPU.

    Ragged lengths (S % 128 != 0) no longer silently fall back to XLA:
    q/k/v are zero-padded to the next 128 multiple, padded KEYS are masked
    in-kernel via the static ``kv_valid`` bound, and the output is sliced
    back (padded-query rows carry zero cotangents, so gradients are exact).
    """
    sq, sk = q.shape[1], k.shape[1]
    # end-aligned causal for sq != sk (decode over a KV prefix): real row i
    # attends cols <= i + (sk - sq), matching _attn_ref / flash-attn
    offset = (sk - sq) if causal and sq != sk else 0
    psq, psk = _pad_to(sq), _pad_to(sk)
    if psq == sq and psk == sk:
        return _flash_bhsd_inner(q, k, v, scale, causal, None, offset)
    qp = jnp.pad(q, ((0, 0), (0, psq - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, psk - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, psk - sk), (0, 0)))
    out = _flash_bhsd_inner(qp, kp, vp, scale, causal,
                            sk if psk != sk else None, offset)
    return out[:, :sq]


# ===========================================================================
# Varlen (unpadded / packed) attention
# ===========================================================================
def _segments_from_cu(cu, total):
    """cu_seqlens (B+1,) -> per-token segment ids (total,), int32."""
    cu = jnp.asarray(cu, jnp.int32)
    return jnp.searchsorted(cu[1:], jnp.arange(total, dtype=jnp.int32),
                            side="right").astype(jnp.int32)


def _varlen_ref(q, k, v, seg_q, seg_k, scale, causal):
    """(H, T, D) packed reference path with segment + causal mask."""
    s = jnp.einsum("htd,hsd->hts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    keep = seg_q[:, None] == seg_k[None, :]
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        keep &= (jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :])
    s = jnp.where(keep[None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no visible key (padding segments) are fully masked; their
    # softmax is a uniform garbage row — zero it
    any_keep = jnp.any(keep, axis=-1)
    p = jnp.where(any_keep[None, :, None], p, 0.0)
    return jnp.einsum("hts,hsd->htd", p, v.astype(jnp.float32)).astype(q.dtype)


def flash_attention_varlen(q, k, v, cu_seqlens_q, cu_seqlens_k,
                           scale: Optional[float] = None,
                           causal: bool = True):
    """Unpadded (packed) flash attention — the reference's
    ``flash_attn_unpadded`` (python/paddle/nn/functional/flash_attention.py
    :§0, SURVEY.md §2.2).

    q/k/v: (total_tokens, H, D) with every sequence's tokens CONTIGUOUS;
    cu_seqlens_*: (B+1,) int cumulative lengths. TPU-native formulation: the
    packed stream runs as ONE dense kernel invocation with per-token
    segment ids masked in-kernel (cross-sequence attention blocked; causal
    within each sequence falls out of global positions because packing is
    order-preserving) — no per-sequence padding, no wasted MXU tiles
    beyond the final 128-alignment pad.
    """
    tq, h, d = q.shape
    tk = k.shape[0]
    if causal:
        # causal in packed coordinates is only defined when both sides
        # share the packing (self-attention); a drifting q/k offset would
        # silently zero-mask real rows
        if tq != tk or jnp.shape(cu_seqlens_q) != jnp.shape(cu_seqlens_k):
            raise ValueError(
                "flash_attention_varlen: causal=True requires "
                "cu_seqlens_q == cu_seqlens_k (self-attention packing)")
        try:
            same = bool(jnp.all(jnp.asarray(cu_seqlens_q)
                                == jnp.asarray(cu_seqlens_k)))
            if not same:
                raise ValueError(
                    "flash_attention_varlen: causal=True requires "
                    "cu_seqlens_q == cu_seqlens_k (self-attention packing)")
        except jax.errors.TracerBoolConversionError:
            pass  # traced lengths: requirement is documented
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    seg_q = _segments_from_cu(cu_seqlens_q, tq)
    seg_k = _segments_from_cu(cu_seqlens_k, tk)
    ptq, ptk = _pad_to(tq), _pad_to(tk)
    qp = jnp.pad(q, ((0, ptq - tq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, ptk - tk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, ptk - tk), (0, 0), (0, 0)))
    # distinct pad ids per side so padded q never matches padded k
    seg_qp = jnp.pad(seg_q, (0, ptq - tq), constant_values=-1)
    seg_kp = jnp.pad(seg_k, (0, ptk - tk), constant_values=-2)
    qt = jnp.moveaxis(qp, 1, 0)                      # (H, T, D)
    kt = jnp.moveaxis(kp, 1, 0)
    vt = jnp.moveaxis(vp, 1, 0)

    use_kernel = _pallas_ok(qt, kt)

    # seg ids are explicit custom_vjp arguments (NOT closure captures) so
    # grad(jax.jit(fn)) works when cu_seqlens is traced — a closure-captured
    # tracer escapes its trace and fails with "No constant handler for type
    # DynamicJaxprTracer" (ADVICE r3 #3). Their cotangents are float0
    # (integer-typed primals).
    @jax.custom_vjp
    def run(qq, kk, vv, sq_ids, sk_ids):
        out, _ = run_fwd(qq, kk, vv, sq_ids, sk_ids)
        return out

    def run_fwd(qq, kk, vv, sq_ids, sk_ids):
        if use_kernel:
            bq, bk = _pick_blocks(qq.shape[1], kk.shape[1])
            out, lse = _flash_fwd_pallas(qq, kk, vv, sc, causal, bq, bk,
                                         seg_q=sq_ids, seg_k=sk_ids)
            return out, (qq, kk, vv, sq_ids, sk_ids, out, lse)
        return _varlen_ref(qq, kk, vv, sq_ids, sk_ids, sc, causal), \
            (qq, kk, vv, sq_ids, sk_ids, None, None)

    def run_bwd(res, g):
        qq, kk, vv, sq_ids, sk_ids, out, lse = res
        zq = np.zeros(sq_ids.shape, jax.dtypes.float0)
        zk = np.zeros(sk_ids.shape, jax.dtypes.float0)
        if lse is not None:
            bq, bk = _pick_blocks(qq.shape[1], kk.shape[1])
            dq, dk, dv = _flash_bwd_pallas(qq, kk, vv, out, lse, g, sc,
                                           causal, bq, bk, seg_q=sq_ids,
                                           seg_k=sk_ids)
            return dq, dk, dv, zq, zk
        _, vjp = jax.vjp(
            lambda a, b, c: _varlen_ref(a, b, c, sq_ids, sk_ids, sc, causal),
            qq, kk, vv)
        dq, dk, dv = vjp(g)
        return dq, dk, dv, zq, zk

    run.defvjp(run_fwd, run_bwd)
    out = run(qt, kt, vt, seg_qp, seg_kp)             # (H, Tq_pad, D)
    return jnp.moveaxis(out, 0, 1)[:tq]


def _seg_ref_batched(q, k, v, seg, scale, causal):
    """(B, nh, S, D) reference path with per-row segment mask."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    keep = seg[:, None, :, None] == seg[:, None, None, :]
    keep &= (seg >= 0)[:, None, :, None]     # pads attend to nothing
    if causal:
        sq = q.shape[2]
        keep &= (jnp.arange(sq)[:, None] >= jnp.arange(sq)[None, :]
                 )[None, None]
    s = jnp.where(keep, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    any_keep = jnp.any(keep, axis=-1)
    p = jnp.where(any_keep[..., None], p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def flash_attention_segmented(q, k, v, seg_ids, scale=None, causal=False):
    """Sequence-packed batched attention: (B, nh, S, D) q/k/v with per-row
    segment ids (B, S) — tokens attend only within their own segment
    (negative ids = padding, attend to nothing). The TPU-native encoder
    packing path (reference: varlen glue in
    paddle/phi/kernels/gpu/flash_attn_kernel.cu:§0 feeding
    fused_multi_transformer's packed ERNIE pretraining batches): one
    Pallas flash invocation over the whole batch, segment mask applied
    in-kernel — no (B, H, S, S) score materialization, no per-sequence
    padding beyond the row length.
    """
    b, nh, s, d = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    ps = _pad_to(s)
    seg = jnp.asarray(seg_ids, jnp.int32)
    if ps != s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, ps - s), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, ps - s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, ps - s), (0, 0)))
        seg = jnp.pad(seg, ((0, 0), (0, ps - s)), constant_values=-1)
    # padded-query rows must never match padded keys: distinct ids per side
    seg_q = jnp.where(seg < 0, -1, seg)
    seg_k = jnp.where(seg < 0, -2, seg)
    qf = q.reshape(b * nh, ps, d)
    kf = k.reshape(b * nh, ps, d)
    vf = v.reshape(b * nh, ps, d)
    use_kernel = _pallas_ok(qf, kf)

    @jax.custom_vjp
    def run(qq, kk, vv, sq_ids, sk_ids):
        out, _ = run_fwd(qq, kk, vv, sq_ids, sk_ids)
        return out

    def run_fwd(qq, kk, vv, sq_ids, sk_ids):
        if use_kernel:
            bq, bk = _pick_blocks(qq.shape[1], kk.shape[1])
            out, lse = _flash_fwd_pallas(qq, kk, vv, sc, causal, bq, bk,
                                         seg_q=sq_ids, seg_k=sk_ids)
            return out, (qq, kk, vv, sq_ids, sk_ids, out, lse)
        ref = _seg_ref_batched(qq.reshape(b, nh, ps, d),
                               kk.reshape(b, nh, ps, d),
                               vv.reshape(b, nh, ps, d),
                               jnp.where(sq_ids < 0, -1, sq_ids), sc, causal)
        return ref.reshape(b * nh, ps, d), \
            (qq, kk, vv, sq_ids, sk_ids, None, None)

    def run_bwd(res, g):
        qq, kk, vv, sq_ids, sk_ids, out, lse = res
        zq = np.zeros(sq_ids.shape, jax.dtypes.float0)
        zk = np.zeros(sk_ids.shape, jax.dtypes.float0)
        if lse is not None:
            bq, bk = _pick_blocks(qq.shape[1], kk.shape[1])
            dq, dk, dv = _flash_bwd_pallas(qq, kk, vv, out, lse, g, sc,
                                           causal, bq, bk, seg_q=sq_ids,
                                           seg_k=sk_ids)
            return dq, dk, dv, zq, zk

        def ref_flat(a, bb, c):
            r = _seg_ref_batched(a.reshape(b, nh, ps, d),
                                 bb.reshape(b, nh, ps, d),
                                 c.reshape(b, nh, ps, d),
                                 jnp.where(sq_ids < 0, -1, sq_ids), sc,
                                 causal)
            return r.reshape(b * nh, ps, d)

        _, vjp = jax.vjp(ref_flat, qq, kk, vv)
        dq, dk, dv = vjp(g)
        return dq, dk, dv, zq, zk

    run.defvjp(run_fwd, run_bwd)
    out = run(qf, kf, vf, seg_q, seg_k)
    return out.reshape(b, nh, ps, d)[:, :, :s]


# ===========================================================================
# Public paddle-layout entry points
# ===========================================================================
def _sdpa_array(q, k, v, *, scale, causal):
    """(B, S, H, D) in/out; handles GQA by repeating KV heads."""
    b, sq, hq, d = q.shape
    hk = k.shape[2]
    if hk != hq:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hq, k.shape[1], d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hq, v.shape[1], d)
    out = flash_attention_bhsd(qt, kt, vt, scale, causal)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)


def _sdpa_masked(q, k, v, mask, *, scale, dropout_p, dropout_key, causal):
    """XLA path with arbitrary mask / dropout. (B, S, H, D)."""
    hq, hk = q.shape[2], k.shape[2]
    if hk != hq:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(cm, s, _NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            s = jnp.where(mask, s, _NEG_INF)
        else:
            s = s + mask.astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0.0:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def scaled_dot_product_attention(query: Tensor, key: Tensor, value: Tensor,
                                 attn_mask=None, dropout_p=0.0, is_causal=False,
                                 training=True, scale=None):
    """Paddle-layout (B, S, H, D) attention. Reference surface:
    python/paddle/nn/functional/flash_attention.py (SURVEY.md §2.2)."""
    d = query.shape[-1]
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    drop = dropout_p if training else 0.0
    if attn_mask is None and drop == 0.0:
        return apply(lambda a, b, c: _sdpa_array(a, b, c, scale=sc, causal=is_causal),
                     query, key, value, op_name="flash_attention")
    dkey = _random.next_key()
    if attn_mask is not None:
        return apply(
            lambda a, b, c, m: _sdpa_masked(a, b, c, m, scale=sc, dropout_p=drop,
                                            dropout_key=dkey, causal=is_causal),
            query, key, value, attn_mask if isinstance(attn_mask, Tensor) else Tensor(attn_mask),
            op_name="attention_masked")
    return apply(
        lambda a, b, c: _sdpa_masked(a, b, c, None, scale=sc, dropout_p=drop,
                                     dropout_key=dkey, causal=is_causal),
        query, key, value, op_name="attention_dropout")
