"""MoE dispatch/capacity ops.

Rebuild of the reference's CUDA capacity kernels and collective dispatch ops
(SURVEY.md §2.4 EP row): ``number_count``, ``limit_by_capacity``,
``prune_gate_by_capacity``, ``random_routing``
(paddle/fluid/operators/collective/global_scatter_op.* and phi capacity
kernels, file:§0) — here as pure-jnp ops XLA fuses, plus the dense
GShard-style dispatch/combine einsums that replace global_scatter /
global_gather. On an ``expert``-sharded mesh the einsum's expert dim IS the
alltoall: GSPMD lowers the (N,E,C)×(N,d) contraction to an ICI all_to_all.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from ..core.compat import axis_size


def number_count(gate_idx, upper_range: int):
    """Histogram of expert assignments: out[e] = #tokens routed to e
    (reference number_count op)."""
    return jnp.bincount(gate_idx.reshape(-1).astype(jnp.int32),
                        length=upper_range)


def position_in_expert(gate_idx, num_experts: int):
    """For each token, its arrival position within its expert's queue
    (cumulative count of earlier tokens with the same expert)."""
    one_hot = jax.nn.one_hot(gate_idx, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(one_hot, axis=0) * one_hot  # (N, E)
    return pos.sum(axis=-1) - 1  # (N,) zero-based


def limit_by_capacity(expert_count, capacity, n_worker: int = 1):
    """Clamp per-expert counts at capacity (reference limit_by_capacity):
    returns the admitted counts."""
    cap = jnp.asarray(capacity)
    if cap.ndim == 0:
        cap = jnp.full(expert_count.shape, cap)
    return jnp.minimum(expert_count, cap)


def prune_gate_by_capacity(gate_idx, expert_count, n_expert: int,
                           n_worker: int = 1):
    """Set gate_idx to -1 for tokens beyond their expert's capacity
    (reference prune_gate_by_capacity)."""
    pos = position_in_expert(gate_idx, n_expert)
    cap = expert_count[gate_idx]
    return jnp.where(pos < cap, gate_idx, -1)


def random_routing(topk_idx, topk_value, prob, topk: int = 2):
    """GShard 2nd-expert random drop: keep expert #2 only when
    2*value > prob (reference random_routing op). prob ~ U[0,1) per token."""
    if topk != 2:
        raise ValueError("random_routing supports topk=2 only")
    keep = (2.0 * topk_value[:, 1]) > prob
    second = jnp.where(keep, topk_idx[:, 1], -1)
    return jnp.stack([topk_idx[:, 0], second], axis=1)


def dispatch_combine_masks(gate_idx, gate_prob, num_experts: int,
                           capacity: int):
    """Dense GShard dispatch: returns
      dispatch (N,E,C) bool — token n goes to slot c of expert e
      combine  (N,E,C) f32  — same mask scaled by the gate prob.
    Tokens with gate_idx -1 (pruned) or beyond capacity drop out.
    """
    valid = gate_idx >= 0
    safe_idx = jnp.where(valid, gate_idx, 0)
    oh_e = jax.nn.one_hot(safe_idx, num_experts, dtype=jnp.int32)
    oh_e = oh_e * valid[:, None].astype(jnp.int32)
    pos = jnp.cumsum(oh_e, axis=0) * oh_e  # 1-based where routed
    pos = pos.sum(axis=-1) - 1  # (N,), -1 where unrouted
    in_cap = (pos >= 0) & (pos < capacity)
    keep = (valid & in_cap).astype(jnp.float32)
    oh_c = jax.nn.one_hot(jnp.where(in_cap, pos, 0), capacity,
                          dtype=jnp.float32)
    disp = jnp.einsum("ne,nc->nec", oh_e.astype(jnp.float32), oh_c)
    disp = disp * keep[:, None, None]
    combine = disp * gate_prob[:, None, None]
    return disp, combine


def dispatch_masks_topk(gate_idx, num_experts: int, capacity: int):
    """Per-choice dispatch masks with joint capacity ordering (GShard:
    choice k's tokens queue after admitted tokens of choices < k). Returns a
    list of K raw (N,E,C) float32 masks — index-only, no gradient path, so
    callers can treat them as constants and keep probs differentiable."""
    n, K = gate_idx.shape
    masks = []
    admitted = jnp.zeros((num_experts,), jnp.int32)
    for k in range(K):
        idx = gate_idx[:, k]
        valid = idx >= 0
        safe = jnp.where(valid, idx, 0)
        oh = jax.nn.one_hot(safe, num_experts, dtype=jnp.int32) * \
            valid[:, None].astype(jnp.int32)
        pos = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1 + admitted[safe]
        in_cap = valid & (pos >= 0) & (pos < capacity)
        keep = in_cap.astype(jnp.float32)
        oh_c = jax.nn.one_hot(jnp.where(in_cap, pos, 0), capacity,
                              dtype=jnp.float32)
        disp = jnp.einsum("ne,nc->nec", oh.astype(jnp.float32), oh_c) * \
            keep[:, None, None]
        masks.append(disp)
        admitted = admitted + (oh * in_cap[:, None].astype(jnp.int32)
                               ).sum(axis=0)
    return masks


def dispatch_combine_topk(gate_idx, gate_prob, num_experts: int,
                          capacity: int):
    """Joint top-K dispatch (GShard ordering: choice k's tokens queue after
    the admitted tokens of choices < k), so (token, k) pairs never collide
    in an expert's capacity slots. Returns summed (N,E,C) dispatch and
    combine masks."""
    masks = dispatch_masks_topk(gate_idx, num_experts, capacity)
    disp_sum = sum(masks)
    comb_sum = sum(m * gate_prob[:, k][:, None, None]
                   for k, m in enumerate(masks))
    return disp_sum, comb_sum


def moe_dispatch(x, dispatch_mask):
    """(N,d),(N,E,C) -> (E,C,d): the global_scatter equivalent — under an
    expert-sharded mesh XLA turns this contraction into the alltoall."""
    return jnp.einsum("nec,nd->ecd", dispatch_mask, x)


def moe_combine(expert_out, combine_mask):
    """(E,C,d),(N,E,C) -> (N,d): global_gather equivalent."""
    return jnp.einsum("nec,ecd->nd", combine_mask, expert_out)


# ---------------------------------------------------------------------------
# Expert-parallel execution inside shard_map (the ragged alltoall of
# global_scatter/global_gather over an ICI 'expert' axis — SURVEY §2.4 EP)
# ---------------------------------------------------------------------------
def expert_parallel_apply(x_local, gate_idx_local, gate_prob_local,
                          w1_local, w2_local, axis_name: str,
                          num_experts: int, capacity: int, act=None,
                          b1_local=None, b2_local=None):
    """Expert-parallel MoE FFN with PRE-COMPUTED gating (any gate works:
    naive/GShard/Switch indices with -1 = pruned token drop out of the
    dispatch masks). Call inside shard_map; see :func:`expert_parallel_ffn`
    for the data-path description.
    """
    from jax import lax

    n = axis_size(axis_name)
    if num_experts % n:
        raise ValueError(f"num_experts {num_experts} must be divisible by "
                         f"'{axis_name}' axis size {n}")
    e_local = num_experts // n
    if act is None:
        act = jax.nn.gelu

    # round 4: gather-based dispatch builds the same dense (E, C, d) slot
    # layout the all_to_all needs; all float movement is gathers (see
    # dispatch_plan)
    routes = dispatch_indices_topk(gate_idx_local, num_experts, capacity)
    in_dtype = x_local.dtype
    tfs, cfs, flats, oks = dispatch_plan(routes, num_experts, capacity,
                                         x_local.shape[0])
    slots = moe_dispatch_gather(x_local.astype(jnp.float32), tfs, flats,
                                oks, num_experts, capacity)   # (E, C, d)

    d_model = x_local.shape[-1]
    z = slots.reshape(n, e_local, capacity, d_model)
    # chunk i (this device's dispatch FOR expert-group i) goes to device i;
    # received leading dim then indexes the SOURCE device
    z = lax.all_to_all(z, axis_name, split_axis=0, concat_axis=0)
    z = jnp.swapaxes(z, 0, 1).reshape(e_local, n * capacity, d_model)

    h = jnp.einsum("ecd,edf->ecf", z.astype(in_dtype), w1_local)
    if b1_local is not None:
        h = h + b1_local[:, None, :]
    h = act(h)
    y = jnp.einsum("ecf,efd->ecd", h, w2_local)              # (E_local, nC, d)
    if b2_local is not None:
        y = y + b2_local[:, None, :]

    y = jnp.swapaxes(y.reshape(e_local, n, capacity, d_model), 0, 1)
    y = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0)
    y = y.reshape(num_experts, capacity, d_model)
    return moe_combine_gather(y.astype(jnp.float32), gate_prob_local,
                              flats, oks, tfs, cfs).astype(in_dtype)


def expert_parallel_ffn(x_local, gate_logits_local, w1_local, w2_local,
                        axis_name: str, num_experts: int, capacity: int,
                        topk: int = 1, act=None):
    """Run a MoE FFN with experts sharded over ``axis_name``.

    Call inside shard_map. Per device: T_local tokens, E_local =
    num_experts/n experts (w1_local (E_local, d, ff), w2_local
    (E_local, ff, d)); gating is over ALL experts (gate weights
    replicated → gate_logits_local (T_local, num_experts)).

    Data path (the reference's global_scatter → expert → global_gather,
    SURVEY §3.2 MoE):
      local dispatch (T_local, E, C) → (E, C, d)
      all_to_all over the expert axis → (E_local, n·C, d) per device
      local expert FFN
      inverse all_to_all → local combine back to (T_local, d)
    """
    from jax import lax

    probs = jax.nn.softmax(gate_logits_local.astype(jnp.float32), axis=-1)
    if topk == 1:
        gate_idx = jnp.argmax(probs, axis=-1)[:, None]       # (T, 1)
        gate_prob = jnp.take_along_axis(probs, gate_idx, axis=-1)
    else:
        gate_prob, gate_idx = lax.top_k(probs, topk)
    return expert_parallel_apply(x_local, gate_idx, gate_prob, w1_local,
                                 w2_local, axis_name, num_experts, capacity,
                                 act=act)


# ---------------------------------------------------------------------------
# Index-based dispatch (round 3): the (N,E,C) one-hot einsum dispatch costs
# O(N·E·C·d) FLOPs — at training scale orders of magnitude more than the
# expert matmuls it feeds. The same routing expressed as scatter/gather by
# slot index is O(N·d); the masks remain for the expert-parallel all_to_all
# layout, which needs the dense (E,C) slot structure anyway.
# ---------------------------------------------------------------------------
def dispatch_indices_topk(gate_idx, num_experts: int, capacity: int):
    """Index form of :func:`dispatch_masks_topk` with the SAME joint
    capacity ordering. Returns a list of K routes
    ``(flat_slot (N,), admitted (N,) bool)`` where flat_slot indexes the
    flattened (E*C) expert-slot space."""
    n, K = gate_idx.shape
    routes = []
    admitted = jnp.zeros((num_experts,), jnp.int32)
    for k in range(K):
        idx = gate_idx[:, k]
        valid = idx >= 0
        safe = jnp.where(valid, idx, 0)
        oh = jax.nn.one_hot(safe, num_experts, dtype=jnp.int32) * \
            valid[:, None].astype(jnp.int32)
        pos = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1 + admitted[safe]
        in_cap = valid & (pos >= 0) & (pos < capacity)
        flat = safe * capacity + jnp.where(in_cap, pos, 0)
        routes.append((flat.astype(jnp.int32), in_cap))
        admitted = admitted + (oh * in_cap[:, None].astype(jnp.int32)
                               ).sum(axis=0)
    return routes


def moe_dispatch_indices(x, routes, num_experts: int, capacity: int):
    """(N,d) + routes -> (E,C,d) by scatter-add (slots are collision-free
    by construction, so add == set with exact gradients)."""
    out = jnp.zeros((num_experts * capacity, x.shape[-1]), x.dtype)
    for flat, ok in routes:
        out = out.at[jnp.where(ok, flat, 0)].add(
            jnp.where(ok[:, None], x, jnp.zeros_like(x)))
    return out.reshape(num_experts, capacity, x.shape[-1])


# ---------------------------------------------------------------------------
# Gather-based dispatch (round 4): the round-3 index dispatch scatters the
# full (N, d) activations into slots — TPU scatter of d-wide rows is the
# measured +8% step-time regression (BASELINE.md moe row). Both dispatch
# AND its gradient are expressible as gathers once the inverse slot->token
# map exists, and that map costs one N-element int32 scatter. custom_vjp
# keeps every float movement a gather (the fast path on TPU), mirroring
# what the reference's global_scatter CUDA kernel achieves with direct
# addressed writes (global_scatter_op:§0).
# ---------------------------------------------------------------------------
def dispatch_plan(routes, num_experts: int, capacity: int, n_tokens: int):
    """Invert routes into the full gather plan. Returns
    token_for_slot (E*C,) int32 (-1 = empty slot),
    choice_for_slot (E*C,) int32 (which top-k choice filled it),
    flats (N, K) int32 and oks (N, K) bool (the routes, stacked)."""
    ec = num_experts * capacity
    tfs = jnp.full((ec + 1,), -1, jnp.int32)     # +1 sentinel dump slot
    cfs = jnp.zeros((ec + 1,), jnp.int32)
    tok = jnp.arange(n_tokens, dtype=jnp.int32)
    for k, (flat, ok) in enumerate(routes):
        idx = jnp.where(ok, flat, ec)
        tfs = tfs.at[idx].set(jnp.where(ok, tok, -1))
        cfs = cfs.at[idx].set(k)
    flats = jnp.stack([f for f, _ in routes], axis=1)
    oks = jnp.stack([o for _, o in routes], axis=1)
    return tfs[:ec], cfs[:ec], flats, oks


def moe_dispatch_gather(x, token_for_slot, flats, oks, num_experts: int,
                        capacity: int):
    """(N,d) -> (E,C,d) where slot s holds x[token_for_slot[s]] (0 when
    empty). flats/oks: (N,K) flat slot per (token, choice) + admitted
    flags — used only by the backward gather."""
    d = x.shape[-1]

    @jax.custom_vjp
    def run(xv, tfs, fl, ok):
        valid = tfs >= 0
        slots = jnp.take(xv, jnp.clip(tfs, 0, None), axis=0)
        slots = jnp.where(valid[:, None], slots, 0)
        return slots.reshape(num_experts, capacity, d)

    def run_fwd(xv, tfs, fl, ok):
        return run(xv, tfs, fl, ok), (tfs, fl, ok)

    def run_bwd(res, g):
        tfs, fl, ok = res
        gf = g.reshape(num_experts * capacity, d)
        dx = 0.0
        for k in range(fl.shape[1]):
            rows = jnp.take(gf, fl[:, k], axis=0)
            dx = dx + jnp.where(ok[:, k][:, None], rows, 0)
        return (dx, np.zeros(tfs.shape, jax.dtypes.float0),
                np.zeros(fl.shape, jax.dtypes.float0),
                np.zeros(ok.shape, jax.dtypes.float0))

    run.defvjp(run_fwd, run_bwd)
    return run(x, token_for_slot, flats, oks)


def moe_combine_gather(expert_out, probs, flats, oks, token_for_slot,
                       choice_for_slot):
    """(E,C,d) + (N,K) probs -> (N,d): out[n] = sum_k ok*p_k*eo[slot(n,k)].
    Backward for expert_out/probs is gather-only via the slot->token maps."""
    e, c, d = expert_out.shape
    n, K = flats.shape

    @jax.custom_vjp
    def run(eo, pv, fl, ok, tfs, cfs):
        flat = eo.reshape(e * c, d)
        out = 0.0
        for k in range(K):
            vals = jnp.take(flat, fl[:, k], axis=0)
            w = pv[:, k] * ok[:, k].astype(pv.dtype)
            out = out + vals * w[:, None].astype(vals.dtype)
        return out

    def run_fwd(eo, pv, fl, ok, tfs, cfs):
        return run(eo, pv, fl, ok, tfs, cfs), (eo, pv, fl, ok, tfs, cfs)

    def run_bwd(res, g):
        eo, pv, fl, ok, tfs, cfs = res
        flat = eo.reshape(e * c, d)
        valid = tfs >= 0
        tok = jnp.clip(tfs, 0, None)
        # d_eo[s] = valid * g[token(s)] * p[token(s), choice(s)]
        g_rows = jnp.take(g, tok, axis=0)
        p_slot = jnp.take_along_axis(
            jnp.take(pv, tok, axis=0), cfs[:, None], axis=1)[:, 0]
        ok_slot = jnp.take_along_axis(
            jnp.take(ok, tok, axis=0), cfs[:, None], axis=1)[:, 0]
        w = p_slot * ok_slot.astype(p_slot.dtype)
        d_eo = jnp.where(valid[:, None],
                         g_rows * w[:, None].astype(g_rows.dtype), 0)
        # d_p[n,k] = ok * <g[n], eo[slot(n,k)]>
        dps = []
        for k in range(K):
            vals = jnp.take(flat, fl[:, k], axis=0)
            dp = jnp.sum(g.astype(jnp.float32) * vals.astype(jnp.float32),
                         axis=-1) * ok[:, k].astype(jnp.float32)
            dps.append(dp)
        d_pv = jnp.stack(dps, axis=1).astype(pv.dtype)
        return (d_eo.reshape(e, c, d).astype(eo.dtype), d_pv,
                np.zeros(fl.shape, jax.dtypes.float0),
                np.zeros(ok.shape, jax.dtypes.float0),
                np.zeros(tfs.shape, jax.dtypes.float0),
                np.zeros(cfs.shape, jax.dtypes.float0))

    run.defvjp(run_fwd, run_bwd)
    return run(expert_out, probs, flats, oks, token_for_slot,
               choice_for_slot)


def moe_combine_indices(expert_out, routes, gate_prob):
    """(E,C,d) + routes + (N,K) probs -> (N,d) by gather."""
    e, c, d = expert_out.shape
    flat = expert_out.reshape(e * c, d)
    out = None
    for k, (fs, ok) in enumerate(routes):
        vals = flat[jnp.where(ok, fs, 0)]
        w = (gate_prob[:, k] * ok.astype(gate_prob.dtype))[:, None]
        term = vals * w.astype(vals.dtype)
        out = term if out is None else out + term
    return out


# ---------------------------------------------------------------------------
# Dropless expert layer (serving): assignments sorted by expert, grouped
# matrix products over the rows each expert received — no capacity, no drop
# ---------------------------------------------------------------------------

#: rows of a grouped product's row tile. An expert's rows are contiguous
#: after the sort, so a tile meets few experts; 32 keeps bf16's (16, 128)
#: tiling whole and the masked-out share of a step's MXU work small
_GMM_TILE_ROWS = 32
#: bytes of one expert's weight block a grid step streams (double-buffered
#: in VMEM): large enough that the DMA, not the step's fixed cost, sets the
#: pace; small enough for the default scoped VMEM beside lhs and out
_GMM_RHS_BLOCK_BYTES = 2 * 1024 * 1024


def _gmm_row_tile(m: int) -> int:
    """Rows of a row tile: ``_GMM_TILE_ROWS``, or all ``m`` where that does
    not divide them (small test sizes)."""
    return _GMM_TILE_ROWS if m % _GMM_TILE_ROWS == 0 else m


def _gmm_col_tile(k: int, n: int, itemsize: int) -> int:
    """Output columns a grid step computes: all ``n`` if one expert's (k, n)
    block fits ``_GMM_RHS_BLOCK_BYTES``, else the largest multiple of 128
    that divides ``n`` and fits."""
    if k * n * itemsize <= _GMM_RHS_BLOCK_BYTES or n % 128:
        return n
    best = 128
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and k * tn * itemsize <= _GMM_RHS_BLOCK_BYTES:
            best = tn
    return best


def _gmm_work_list(group_sizes, m: int, tm: int):
    """The grouped product's grid as data: the (row tile, expert) pairs in
    which the expert owns a row of the tile, tile-major, one packed int32
    each ``(tile << 17) | (expert << 1) | is the tile's first item``.
    Returns (items (m // tm + E - 1,), n_items, starts (E,), ends (E,)).
    An expert nobody chose is in no pair: it costs no step and no weight
    read. A tile no expert reaches (rows past the last group: pad slots,
    and the assignments to experts that are not held) is in no pair either:
    the kernel's output starts as zeros and stays so there. With a chip's
    share of the experts and k choices a token most of the ``T x k`` rows
    are such, and at one step a tile and column tile they were three
    quarters of the kernel's steps. Where nobody chose anything the list is
    one item, tile 0 with expert 0 (a grid has at least one step)."""
    n_exp = group_sizes.shape[0]
    n_tiles = m // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    lo = jnp.arange(n_tiles, dtype=jnp.int32)[:, None] * tm
    hit = ((sizes > 0)[None, :] & (starts[None, :] < lo + tm)
           & (ends[None, :] > lo))                          # (tiles, E)
    # never an empty grid: tile 0 owns nothing and is zeroed once more
    hit = hit.at[0, 0].set(hit[0, 0] | ~jnp.any(hit))
    flat = hit.reshape(-1)
    # every tile's experts are contiguous and neighbouring tiles share at
    # most one, so tiles + E - 1 items always suffice
    idx = jnp.nonzero(flat, size=n_tiles + n_exp - 1, fill_value=0)[0]
    idx = idx.astype(jnp.int32)
    tile, expert = idx // n_exp, idx % n_exp
    first = jnp.concatenate([jnp.ones((1,), bool), tile[1:] != tile[:-1]])
    items = (tile << 17) | (expert << 1) | first.astype(jnp.int32)
    return items, jnp.sum(flat, dtype=jnp.int32), starts, ends


def _gmm_kernel(work_ref, starts_ref, ends_ref, base_ref, lhs_ref, rhs_ref,
                zeros_ref, out_ref, *, tm: int):
    del base_ref                            # read by rhs's index map
    del zeros_ref                           # the output as it starts
    item = work_ref[pl.program_id(1)]
    tile, expert, first = item >> 17, (item >> 1) & 0xFFFF, (item & 1) == 1
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (rows >= starts_ref[expert]) & (rows < ends_ref[expert])
    prod = jnp.dot(lhs_ref[...], rhs_ref[0],
                   preferred_element_type=jnp.float32).astype(out_ref.dtype)

    # a row belongs to one expert: "accumulating" is a select, exact in
    # any dtype; the tile's first step also zeroes the rows of no expert
    @pl.when(first)
    def _start():
        out_ref[...] = jnp.where(mine, prod, jnp.zeros_like(prod))

    @pl.when(jnp.logical_not(first))
    def _merge():
        out_ref[...] = jnp.where(mine, prod, out_ref[...])


def moe_grouped_matmul_pallas(lhs, rhs, group_sizes, work=None,
                              interpret: bool = False, layer=None):
    """Pallas grouped matrix product, same contract as
    :func:`moe_grouped_matmul_array`. The grid is (column tiles, work list
    of (row tile, expert) pairs), the second extent a traced scalar: each
    step streams one expert's (k, tn) weight block and multiplies it with
    one tile of rows, keeping the rows that expert owns — so the call reads
    the weights of the experts hit, once (twice for an expert whose rows
    straddle two tiles), and nothing of the others. The output starts as
    zeros (an aliased operand that is never copied), so a row tile that is
    in no pair costs no step and reads as zeros. ``work``: the list from
    :func:`_gmm_work_list`, for callers that make several products over one
    grouping. ``layer`` (a traced index): ``rhs`` is (layers, E, k, n) and
    the blocks are read IN PLACE from that layer — sliced out first, a
    layer's experts would be copied whole on every call (XLA cannot fuse a
    slice into a kernel's operand)."""
    m, k = lhs.shape
    n_exp, n = rhs.shape[-3], rhs.shape[-1]
    tm = _gmm_row_tile(m)
    tn = _gmm_col_tile(k, n, rhs.dtype.itemsize)
    items, n_items, starts, ends = (
        work if work is not None else _gmm_work_list(group_sizes, m, tm))
    # the stack of layers as one run of experts (a free reshape); the
    # layer's first expert rides in as a scalar
    base = jnp.asarray(0 if layer is None else layer * n_exp,
                       jnp.int32).reshape(1)
    rhs = rhs.reshape((-1, k, n))

    def tile_of(i, work):
        return work[i] >> 17

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # work list, group starts, ends, base
        grid=(n // tn, n_items),
        in_specs=[
            pl.BlockSpec((tm, k),
                         lambda c, i, w, s, e, b: (tile_of(i, w), 0)),
            pl.BlockSpec((1, k, tn), lambda c, i, w, s, e, b: (
                b[0] + ((w[i] >> 1) & 0xFFFF), 0, c)),
            pl.BlockSpec(memory_space=pl.ANY),      # zeros: never copied
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda c, i, w, s, e, b: (tile_of(i, w), c)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        name="moe_grouped_matmul",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        # the tiles no step visits keep what the output starts as
        input_output_aliases={6: 0},
        interpret=interpret,
    )(items, starts, ends, base, lhs, rhs, jnp.zeros((m, n), lhs.dtype))


def moe_grouped_matmul_array(lhs, rhs, group_sizes):
    """Plain ``jax.numpy`` grouped product, the kernel's parity reference
    and the path off the chip: row i of ``lhs`` (m, k), which lies in group
    g (``group_sizes`` (E,), the rows sorted by group), times ``rhs[g]``
    (E, k, n); rows past the last group give zeros. It gathers a weight
    matrix a row, which only small sizes afford."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    rows = jnp.arange(lhs.shape[0], dtype=jnp.int32)
    group = jnp.sum(ends[None, :] <= rows[:, None], axis=1, dtype=jnp.int32)
    owned = group < rhs.shape[0]
    w = jnp.take(rhs, jnp.minimum(group, rhs.shape[0] - 1), axis=0)
    out = jnp.einsum("mk,mkn->mn", lhs, w,
                     preferred_element_type=jnp.float32).astype(lhs.dtype)
    return jnp.where(owned[:, None], out, jnp.zeros_like(out))


#: the expert activations ``grouped_expert_ffn`` offers: an expert is
#: ``down(act(x W_gate) * (x W_up))``, SwiGLU with ``silu``, ReGLU with
#: ``relu``
_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def expert_stat_names(n_routed: Optional[int] = None,
                      activation: str = "silu") -> Tuple[str, ...]:
    """The names of the columns of ``grouped_expert_ffn``'s stats, in
    order, for a call with these options: what a model's routing record
    names its columns by."""
    names = ("experts_hit", "max_expert_load", "expert_assignments")
    if n_routed is not None:
        names += ("zero_expert_assignments", "router_assignments")
    if activation == "relu":
        names += ("expert_active_units", "expert_units")
    return names


def grouped_expert_ffn(x, expert_idx, expert_weight, token_valid,
                       w_gate, w_up, w_down, first_expert: int = 0,
                       layer=None, n_routed: Optional[int] = None,
                       activation: str = "silu"):
    """Dropless routed experts, each ``down(act(x W_gate) * (x W_up))``
    with ``act`` named by ``activation`` (``silu``: SwiGLU, ``relu``:
    ReGLU): ``sum_j expert_weight[t, j] * expert_{expert_idx[t, j]}(x[t])``
    over the experts HELD here.

    x: (T, h); expert_idx / expert_weight: (T, k), the router's choice over
    ALL its experts and the weights it gave them; token_valid: (T,) bool, a
    pad slot routes nowhere and counts nowhere. w_gate / w_up: (E, h, m),
    w_down: (E, m, h): the weights of experts ``first_expert ..
    first_expert + E - 1`` (a chip's share is a slice; an assignment to an
    expert outside it adds nothing here). Static shapes, T * k rows: every
    assignment is computed whatever the skew, none is dropped, and there is
    no capacity. On the chip the three products are the Pallas kernel
    ``moe_grouped_matmul``, whose cost follows the experts hit. ``layer``
    (a traced index, inside a scan over layers): the weights are a whole
    stack, (layers, E, ...), and that layer's are used where they lie.

    ``n_routed``: the router's width in experts that HAVE weights, for a
    router with zero-compute experts beyond them: an id at or beyond it
    names an identity, ``E_e(x) = x``, which needs no weights and no other
    chip, so it is computed here for every valid token, ``w x`` in float32.
    (None: every id is a routed expert, and one outside the held slice adds
    nothing.)

    Returns (out (T, h), stats int32 (3,): experts hit, the largest number
    of assignments one expert received, assignments made, all three among
    the experts HELD; with ``n_routed`` two more: the assignments to
    zero-compute experts, and the router's, valid tokens x k; with
    ``relu`` two more after those: the intermediate units ReLU left
    non-zero over the assignments made (``expert_active_units``) and the
    units computed for them (``expert_units``, assignments x the expert
    width), pad slots and rows of no held expert in neither).
    ``expert_stat_names`` names the columns."""
    from ._common import use_pallas
    t, k = expert_idx.shape
    held = w_gate.shape[-3]
    local = expert_idx.astype(jnp.int32) - first_expert
    routed = token_valid[:, None] & (local >= 0) & (local < held)
    # ``held`` sorts after every expert: the rows of no expert come last
    group = jnp.where(routed, local, held).reshape(-1)
    order = jnp.argsort(group)                      # stable
    token_of = order // k
    sizes = jnp.sum(group[:, None] == jnp.arange(held, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    rows = jnp.take(x, token_of, axis=0)            # (T * k, h)
    if use_pallas():
        work = _gmm_work_list(sizes, t * k, _gmm_row_tile(t * k))
        gmm = functools.partial(moe_grouped_matmul_pallas, work=work,
                                layer=layer)
    else:
        def gmm(lhs, rhs, group_sizes):
            return moe_grouped_matmul_array(
                lhs, rhs if layer is None else rhs[layer], group_sizes)
    gate = gmm(rows, w_gate, sizes)
    act = _ACTIVATIONS[activation](gate) * gmm(rows, w_up, sizes)
    y = gmm(act.astype(x.dtype), w_down, sizes)     # (T * k, h), sorted
    y = jnp.take(y, jnp.argsort(order), axis=0).reshape(t, k, -1)
    weight = jnp.where(routed, expert_weight, 0).astype(jnp.float32)
    out = jnp.einsum("tkh,tk->th", y.astype(jnp.float32), weight)
    stats = [jnp.sum(sizes > 0, dtype=jnp.int32), jnp.max(sizes),
             jnp.sum(sizes, dtype=jnp.int32)]
    if n_routed is not None:
        with jax.named_scope("moe.zero_experts"):
            zero = token_valid[:, None] & (expert_idx >= n_routed)
            kept = jnp.sum(jnp.where(zero, expert_weight, 0).astype(
                jnp.float32), axis=1, keepdims=True)
            out = out + kept * x.astype(jnp.float32)
        stats += [jnp.sum(zero, dtype=jnp.int32),
                  jnp.sum(token_valid, dtype=jnp.int32) * k]
    if activation == "relu":
        # the assignments made are the first ``stats[2]`` sorted rows: pad
        # slots and rows of no held expert lie past them
        made = stats[2]
        made_rows = jnp.arange(t * k, dtype=jnp.int32)[:, None] < made
        stats += [jnp.sum((gate > 0) & made_rows, dtype=jnp.int32),
                  made * w_gate.shape[-1]]
    return out.astype(x.dtype), jnp.stack(stats)
