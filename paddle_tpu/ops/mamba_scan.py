"""Selective state-space scan over the serving engine's packed token axis.

A micro-round of the engine (``inference/decoding.py``) packs the tokens of
up to ``rows`` slots into one flat axis: a decoding row one token, a
prefilling row a span of its prompt, each row's tokens CONTIGUOUS and in
order, pad slots ``token_row`` -1. A Mamba layer's recurrence runs per row,
from the state ITS slot keeps, and writes the state back::

    S_t = exp(delta_t[None, :] * A) * S_{t-1} + B_t[:, None] * (delta_t * u_t)[None, :]
    y_t = sum_n S_t[n, :] * C_t[n] + D * u_t

with ``S`` (d_state, d_inner), d_inner along the lanes (a minor dimension of
``d_state`` = 16 would be padded to 128 on the device and hold 8x). A row
whose first token of the round is at position 0 starts from ``S = 0``: a
re-used slot is reset inside the program, and admission uploads nothing.

``mamba_ragged_scan`` is the dispatcher: the Pallas kernel of that name on
TPU, its XLA twin (``mamba_ragged_scan_array``, same signature) elsewhere.
Both take the state of EVERY recurrent layer, ``(layers, rows, d_state,
d_inner)``, and the layer's index: the kernel reads and writes its layer's
rows in place (a slice of the stack handed to a kernel would be a copy of 42
MB a layer at 128 rows; ``models.afmoe``'s expert weights, PR 27).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class ScanPlan(NamedTuple):
    """What the scan needs to know of a micro-round's packing, per row
    (``rows``,) int32; the same for every layer of the round."""
    tok_start: jax.Array    # index of the row's first token in the packed axis
    tok_count: jax.Array    # tokens the row has this round (0: no work)
    reset: jax.Array        # 1: the row starts from a zero state
    work: jax.Array         # the rows that have work, first; then the rest
    n_live: jax.Array       # () how many rows have work


def scan_plan(token_row, positions, n_rows: int) -> ScanPlan:
    mine = token_row[None, :] == jnp.arange(n_rows, dtype=jnp.int32)[:, None]
    count = jnp.sum(mine, axis=1, dtype=jnp.int32)
    start = jnp.argmax(mine, axis=1).astype(jnp.int32)
    has = count > 0
    reset = has & (jnp.take(positions.astype(jnp.int32), start) == 0)
    work = jnp.argsort(~has, stable=True).astype(jnp.int32)
    return ScanPlan(start, count, reset.astype(jnp.int32), work,
                    jnp.sum(has, dtype=jnp.int32))


def mamba_ragged_scan_array(u, delta, b, c, a, d, state, layer,
                            token_row, plan: ScanPlan):
    """XLA twin: one ``lax.scan`` over the packed tokens in order, each
    reading and writing its row's state.

    u, delta:  (T, d_inner)  — the scan's input and its step size, float32
    b, c:      (T, d_state)  — float32
    a:         (d_state, d_inner) — ``-exp(A_log)``; d: (d_inner,)
    state:     (layers, rows, d_state, d_inner) float32
    layer:     () int32
    Returns (y (T, d_inner) float32, pad slots 0; state')."""
    n_rows = state.shape[1]
    s_l = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s_l = jnp.where(plan.reset[:, None, None] != 0, 0.0, s_l)

    def token(s_l, xs):
        u_t, dt_t, b_t, c_t, r = xs
        row = jnp.clip(r, 0, n_rows - 1)
        s = lax.dynamic_index_in_dim(s_l, row, 0, keepdims=False)
        s_new = (jnp.exp(dt_t[None, :] * a) * s
                 + b_t[:, None] * (dt_t * u_t)[None, :])
        y = jnp.sum(s_new * c_t[:, None], axis=0) + d * u_t
        live = r >= 0
        s_l = lax.dynamic_update_index_in_dim(
            s_l, jnp.where(live, s_new, s), row, 0)
        return s_l, jnp.where(live, y, 0.0)

    s_l, y = lax.scan(token, s_l, (u, delta, b, c, token_row))
    return y, lax.dynamic_update_index_in_dim(state, s_l, layer, 0)


def _scan_kernel(layer_ref, work_ref, n_live_ref, start_ref, count_ref,
                 reset_ref, u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                 s_in_ref, y_ref, s_out_ref):
    i = pl.program_id(1)
    n_live = n_live_ref[0]

    @pl.when(i == 0)
    def _zero_out():
        # pad slots belong to no row and are never written
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(i < n_live)
    def _row():
        r = work_ref[i]
        t0, n = start_ref[r], count_ref[r]
        a = a_ref[...]                                  # (N, D)
        d = d_ref[...]                                  # (1, D)
        s0 = jnp.where(reset_ref[r] != 0, 0.0, s_in_ref[0, 0])

        def token(k, s):
            at = pl.ds(t0 + k, 1)
            u = u_ref[at, :]                            # (1, D)
            dt = dt_ref[at, :]
            # B and C of the token: (N, 1) columns, along every channel
            s = jnp.exp(dt * a) * s + b_ref[t0 + k] * (dt * u)
            y_ref[at, :] = (jnp.sum(s * c_ref[t0 + k], axis=0, keepdims=True)
                            + d * u)
            return s

        s_out_ref[0, 0] = lax.fori_loop(0, n, token, s0)

    @pl.when((n_live == 0) & (i == 0))
    def _untouched():
        # a call with no work still owns its one step's output block
        s_out_ref[...] = s_in_ref[...]


def mamba_ragged_scan_pallas(u, delta, b, c, a, d, state, layer, token_row,
                             plan: ScanPlan, block_d: int = 0,
                             interpret: bool = False):
    """Pallas kernel: same contract as :func:`mamba_ragged_scan_array`.

    The grid is (blocks of d_inner, the rows that have work): a step loads
    ONE row's state block (d_state, block_d) from the layer's slab of the
    stack (the layer and the row through scalar prefetch: nothing is sliced
    outside), steps through the row's 1..T tokens with the state in
    registers, and writes the block back in place (the state is aliased to
    the output). The tokens' ``u``, ``delta``, ``B``, ``C`` and ``y`` are
    whole blocks that stay in VMEM across the rows. Rows without work are
    not visited: their state is not read, and the grid's extent is the
    number of rows that have work (a traced scalar)."""
    t, di = u.shape
    n = b.shape[1]
    block_d = block_d or di
    if di % block_d:
        raise ValueError(f"block_d {block_d} must divide d_inner {di}")
    tokens = lambda j, i, *_: (0, j)
    per_token = lambda j, i, *_: (0, 0, 0)
    row_block = lambda j, i, layer, work, *_: (layer[0], work[i], 0, j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer, rows with work first, their number, each row's first token,
        # token count and reset
        num_scalar_prefetch=6,
        grid=(di // block_d, jnp.maximum(plan.n_live, 1)),
        in_specs=[
            pl.BlockSpec((t, block_d), tokens),         # u
            pl.BlockSpec((t, block_d), tokens),         # delta
            pl.BlockSpec((t, n, 1), per_token),         # B, a column a token
            pl.BlockSpec((t, n, 1), per_token),         # C
            pl.BlockSpec((n, block_d), tokens),         # A
            pl.BlockSpec((1, block_d), tokens),         # D
            pl.BlockSpec((1, 1, n, block_d), row_block),
        ],
        out_specs=[pl.BlockSpec((t, block_d), tokens),
                   pl.BlockSpec((1, 1, n, block_d), row_block)],
    )
    # u, delta and y blocks (double-buffered), B and C a 128-lane column a
    # token, the state block in and out, A
    vmem = 4 * (6 * t * block_d + 4 * t * n * 128 + 6 * n * block_d)
    y, state = pl.pallas_call(
        _scan_kernel,
        name="mamba_ragged_scan",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, di), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={12: 1},                   # the state, in place
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(vmem) + (16 << 20)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), plan.work,
      plan.n_live.reshape(1), plan.tok_start, plan.tok_count, plan.reset,
      u.astype(jnp.float32), delta.astype(jnp.float32),
      b.astype(jnp.float32)[:, :, None], c.astype(jnp.float32)[:, :, None],
      a.astype(jnp.float32), d.astype(jnp.float32).reshape(1, di), state)
    return y, state


def mamba_ragged_scan(u, delta, b, c, a, d, state, layer, token_row,
                      plan: ScanPlan):
    """Dispatcher: the Pallas kernel on TPU (FLAGS_use_pallas_kernels), its
    XLA twin elsewhere; same contract (:func:`mamba_ragged_scan_array`)."""
    from ._common import use_pallas
    impl = mamba_ragged_scan_pallas if use_pallas() \
        else mamba_ragged_scan_array
    return impl(u, delta, b, c, a, d, state, layer, token_row, plan)
