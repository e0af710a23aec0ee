"""Jamba decoder (``model_type: jamba``: Mamba layers beside a few attention
layers) on the serving path: the stacked functional weights and the ragged
model step the continuous-batching engine dispatches. Beside the pages of
its attention layers (``cache_layout``) a row keeps a FIXED-SIZE STATE in
every Mamba layer (``state_layout``): the step takes and returns both.

The layers, as ``perfbench/reference/jamba.py`` computes them (x: tokens x
hidden; pre-norm residual blocks; no bias but the two named)::

    x = x + mixer(rms(x; ln_in));  x = x + down(silu(gate(h)) * up(h)),  h = rms(x; ln_ff)

Layer i's mixer is attention where ``i % attn_layer_period ==
attn_layer_offset``, else Mamba. A final RMS norm; the head is the embedding
transposed where ``tie_word_embeddings``. Every feed-forward is the dense
SwiGLU (``num_experts`` 1).

- attention: q ``hidden -> heads x head_dim``, k and v ``hidden ->
  kv_heads x head_dim``, causal softmax at ``head_dim^-0.5``, NO rotary or
  other positional term (the Mamba layers carry the order), o. Served as
  multi-query attention: ``num_key_value_heads`` must be 1, a token keeps K
  and V of ``head_dim`` WITHOUT a head axis (a head axis of one would be
  padded to a sublane tile on the device), through
  ``ops.paged_attention.ragged_paged_attention``.
- Mamba, d_inner = ``mamba_expand`` x hidden, N = ``mamba_d_state``, R =
  ``mamba_dt_rank``, K = ``mamba_d_conv``: ``[u | z] = in_proj(h)``; ``u_t =
  silu(b_conv + sum_{j<K} W_conv[j] * u_{t-(K-1)+j})`` per channel, causal,
  zeros before a row's first token; ``[dt | B | C] = x_proj(u)``, each
  through its own weighted RMS norm; ``delta = softplus(dt_proj(dt) +
  dt_bias)``; ``A = -exp(A_log)``; ``S_t = exp(delta_t A) S_{t-1} + B_t
  (delta_t u_t)``; ``y_t = S_t C_t + D u_t``; ``out = out_proj(y *
  silu(z))``. A row keeps ``S`` (N, d_inner; float32, ``state_dtype``: a
  running sum over thousands of tokens) and the last K-1 pre-activation
  ``u`` a layer, whatever its length. The recurrence is
  ``ops.mamba_scan.mamba_ragged_scan``; the conv and the projections around
  it run under ``jax.named_scope("mamba.conv")`` / ``("mamba.proj")``.

A row whose first token of a micro-round is at position 0 starts from a zero
state and a zero conv window: a re-used slot is reset inside the program.

Mamba and attention layers are two stacks (``m_*`` / ``a_*``); runs of
consecutive Mamba layers go under one ``lax.scan`` each. The residual stream
is float32, branches and weights ``dtype`` (``models.afmoe``). One chip only:
every weight is replicated, so the engine refuses a mesh of degree > 1.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kvcache.state import StateArray, StateLayout
from ..ops import mamba_scan
from ..ops.paged_attention import CacheLayout
from ..ops.rms_norm import rms_norm_replicated


@dataclasses.dataclass
class JambaConfig:
    """The published keys of a ``jamba`` ``config.json`` that set a shape or
    an equation, and the user's two precisions."""
    #: the module the serving engine takes this model's step from
    serving_module = "paddle_tpu.models.jamba"

    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    num_experts: int = 1
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    dtype: Any = jnp.float32
    #: what the recurrence's running sum is kept in
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_key_value_heads != 1:
            raise ValueError(
                "models.jamba serves multi-query attention: "
                f"num_key_value_heads={self.num_key_value_heads}, not 1")
        if self.num_experts != 1:
            raise ValueError("models.jamba has dense feed-forwards only: "
                             f"num_experts={self.num_experts}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("num_attention_heads must divide hidden_size")
        if not self.tie_word_embeddings:
            raise ValueError("models.jamba ties the head to the embedding")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Per layer ``"attention"`` or ``"mamba"``."""
        return tuple(
            "attention" if i % self.attn_layer_period == self.attn_layer_offset
            else "mamba" for i in range(self.num_hidden_layers))

    @property
    def num_attention_layers(self) -> int:
        return self.layer_kinds.count("attention")

    @property
    def num_mamba_layers(self) -> int:
        return self.layer_kinds.count("mamba")


def jamba_tiny(**over) -> JambaConfig:
    """A CPU-test size: two attention layers among Mamba layers, runs of
    several Mamba layers before, between and after them."""
    return JambaConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=7, num_attention_heads=4, attn_layer_period=3,
        attn_layer_offset=2, mamba_d_state=8, mamba_dt_rank=8,
        max_position_embeddings=512), **over})


def cache_layout(config: JambaConfig) -> CacheLayout:
    """What a token keeps in each ATTENTION layer: K and V of ``head_dim``,
    no head axis (one KV head), so no mesh can split it."""
    entry = (config.head_dim,)
    return CacheLayout((entry, entry), head_axis=None,
                       layers=config.num_attention_layers)


def state_layout(config: JambaConfig) -> StateLayout:
    """What a row keeps in each MAMBA layer: ``ssm`` (d_state, d_inner) in
    ``state_dtype`` and ``conv``, the last d_conv - 1 pre-activation inputs,
    (d_conv - 1, rows, d_inner) in ``dtype``: d_inner along the lanes, rows
    before the short dimension (``kvcache.state.StateArray``)."""
    return StateLayout(config.num_mamba_layers, (
        StateArray("ssm", (), (config.mamba_d_state, config.d_inner),
                   config.state_dtype),
        StateArray("conv", (config.mamba_d_conv - 1,), (config.d_inner,),
                   config.dtype)))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
_FF_KEYS = ("ln_ff", "w_gate", "w_up", "w_down")
_ATTN_KEYS = ("ln_in", "wq", "wk", "wv", "wo") + _FF_KEYS
_MAMBA_KEYS = ("ln_in", "in_proj", "conv_w", "conv_b", "x_proj", "dt_norm",
               "b_norm", "c_norm", "dt_proj", "dt_bias", "a_log", "d_skip",
               "out_proj") + _FF_KEYS
_NORM_KEYS = ("ln_in", "ln_ff", "dt_norm", "b_norm", "c_norm")
#: kept in float32 whatever the model is served in: they set the state's
#: decay and step size (5,120 x 18 numbers a layer)
_F32_KEYS = ("dt_bias", "a_log", "d_skip")


def _shapes(config: JambaConfig) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """Every weight's (shape, dtype): ``m_*`` over the Mamba layers, ``a_*``
    over the attention layers, ``x @ W`` orientation. ``a_log`` is (d_state,
    d_inner): d_inner along the lanes, as the state."""
    c, dt = config, config.dtype
    h, di, n, r, k = (c.hidden_size, c.d_inner, c.mamba_d_state,
                      c.mamba_dt_rank, c.mamba_d_conv)
    ff = {"ln_ff": (h,), "w_gate": (h, c.intermediate_size),
          "w_up": (h, c.intermediate_size), "w_down": (c.intermediate_size, h)}
    attn = {"ln_in": (h,), "wq": (h, h), "wk": (h, c.head_dim),
            "wv": (h, c.head_dim), "wo": (h, h), **ff}
    mamba = {"ln_in": (h,), "in_proj": (h, 2 * di), "conv_w": (k, di),
             "conv_b": (di,), "x_proj": (di, r + 2 * n), "dt_norm": (r,),
             "b_norm": (n,), "c_norm": (n,), "dt_proj": (r, di),
             "dt_bias": (di,), "a_log": (n, di), "d_skip": (di,),
             "out_proj": (di, h), **ff}
    out = {"embed": ((c.vocab_size, h), dt), "ln_f": ((h,), dt)}
    for prefix, count, group in (("m_", c.num_mamba_layers, mamba),
                                 ("a_", c.num_attention_layers, attn)):
        for name, shape in group.items():
            out[prefix + name] = (
                (count,) + shape, jnp.float32 if name in _F32_KEYS else dt)
    return out


def init_stacked_params(config: JambaConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded weights in the stacked layout: normal, std 0.02; norm weights
    1; and the Mamba family's own initialisation where zeros or noise would
    switch the mechanism off: ``a_log = log(1..d_state)`` a channel, ``D =
    1``, ``dt_bias`` the inverse softplus of a log-uniform draw in [1e-3,
    1e-1], so that a state carries over hundreds of positions."""
    shapes = _shapes(config)
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    out = {}
    for key, (name, (shape, dt)) in zip(keys, sorted(shapes.items())):
        base = name[2:]                                 # past "m_" / "a_"
        if name == "ln_f" or base in _NORM_KEYS:
            out[name] = jnp.ones(shape, dt)
        elif name == "m_a_log":
            out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=jnp.float32))[None, :, None], shape)
        elif name == "m_d_skip":
            out[name] = jnp.ones(shape, dt)
        elif name == "m_dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            out[name] = step + jnp.log(-jnp.expm1(-step))
        else:
            out[name] = (jax.random.normal(key, shape, jnp.float32)
                         * 0.02).astype(dt)
    return out


def param_count(config: JambaConfig) -> int:
    return sum(math.prod(shape) for shape, _ in _shapes(config).values())


def param_nbytes(config: JambaConfig) -> int:
    """Device bytes of ``init_stacked_params(config)``."""
    return sum(math.prod(shape) * jnp.dtype(dt).itemsize
               for shape, dt in _shapes(config).values())


def serving_param_specs(config: JambaConfig) -> Dict[str, P]:
    """All replicated: this model serves on one chip (module doc)."""
    return {k: P() for k in _shapes(config)}


def shard_params_tp(params: Dict[str, Any], mesh: Mesh,
                    config: JambaConfig) -> Dict[str, Any]:
    """Place the weights on a (degree-1) serving mesh, replicated."""
    return {k: jax.device_put(v, NamedSharding(mesh, P()))
            for k, v in params.items()}


# ---------------------------------------------------------------------------
# the ragged step
# ---------------------------------------------------------------------------
def _mm(x, w):
    return jnp.einsum("...h,hd->...d", x, w)


def _rms_small(x, w, eps):
    """RMS norm over a short last dimension (dt_rank, d_state), float32."""
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _runs(kinds: Tuple[str, ...]) -> List[Tuple[str, int, int]]:
    """Consecutive layers of one kind: (kind, first index IN ITS STACK,
    count), in layer order."""
    out: List[Tuple[str, int, int]] = []
    seen = {"mamba": 0, "attention": 0}
    for kind in kinds:
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, seen[kind], 1))
        seen[kind] += 1
    return out


def conv_window(u_pre, window, token_row, plan, conv_w, conv_b):
    """The causal depthwise conv over the packed axis, each row's tokens
    after ITS window. ``u_pre`` (T, d_inner) pre-activation inputs;
    ``window`` (K - 1, rows, d_inner) the row's last K - 1 of them, oldest
    first. Returns (silu(conv) (T, d_inner), window')."""
    t = u_pre.shape[0]
    lags, n_rows = window.shape[:2]
    fresh = plan.reset != 0                                 # (rows,)
    window = jnp.where(fresh[None, :, None], 0, window)
    row = jnp.clip(token_row, 0, n_rows - 1)
    off = jnp.arange(t, dtype=jnp.int32) - jnp.take(plan.tok_start, row)
    flat = window.reshape(lags * n_rows, -1)
    acc = conv_b.astype(jnp.float32) + conv_w[lags].astype(jnp.float32) \
        * u_pre.astype(jnp.float32)
    for lag in range(1, lags + 1):
        # the input ``lag`` tokens back: in the packed axis where the row has
        # that many tokens before this one this round, else in its window
        packed = jnp.roll(u_pre, lag, axis=0)
        kept = jnp.take(flat, jnp.clip(lags + off - lag, 0, lags - 1)
                        * n_rows + row, axis=0)
        tap = jnp.where((off >= lag)[:, None], packed, kept)
        acc = acc + conv_w[lags - lag].astype(jnp.float32) \
            * tap.astype(jnp.float32)
    # the window after the round: the row's last K - 1 inputs, from the
    # packed axis where it has them this round, else shifted down
    v = plan.tok_count[None, :] - lags + jnp.arange(lags)[:, None]  # (K-1, R)
    new = jnp.take(u_pre, jnp.clip(plan.tok_start[None, :] + v, 0, t - 1),
                   axis=0)                                  # (K-1, R, d)
    old = jnp.take_along_axis(
        window, jnp.clip(lags + v, 0, lags - 1)[:, :, None], axis=0)
    window = jnp.where((v >= 0)[:, :, None], new.astype(window.dtype), old)
    return jax.nn.silu(acc).astype(u_pre.dtype), window


def ragged_step(params, ids, token_row, positions, kv_lens, last_idx,
                k_pages, v_pages, ssm, conv, block_tables,
                config: JambaConfig, mesh: Optional[Mesh] = None,
                mp_axis: str = "mp", logits_epilogue=None):
    """One forward over a ragged packed token batch: the contract of
    ``models.llama.ragged_step`` with the cache's arrays ``k_pages, v_pages``
    (attention layers, pages, page, head_dim) AND the rows' state ``ssm``
    (mamba layers, rows, d_state, d_inner), ``conv`` (mamba layers, d_conv -
    1, rows, d_inner). Each row's tokens are contiguous and in order in the
    packed axis (the engine's plan packs them so). Returns ``(logits (C, V),
    k_pages', v_pages', ssm', conv')``."""
    from ..ops import paged_attention as pa

    c = config

    def rms(xv, wv):
        return rms_norm_replicated(xv, wv, c.rms_norm_eps, mesh)

    t = ids.shape[0]
    nh, hd, n, r = (c.num_attention_heads, c.head_dim, c.mamba_d_state,
                    c.mamba_dt_rank)
    page = k_pages.shape[2]
    n_rows, width = block_tables.shape
    pos_c = jnp.minimum(positions.astype(jnp.int32), width * page - 1)
    token_row = token_row.astype(jnp.int32)
    f32, dt = jnp.float32, c.dtype
    x = jnp.take(params["embed"], ids.astype(jnp.int32), axis=0).astype(f32)

    valid = token_row >= 0
    row_c = jnp.clip(token_row, 0, n_rows - 1)
    phys = jnp.take(block_tables.reshape(-1), row_c * width + pos_c // page)
    phys = jnp.where(valid, phys, 0)                        # pads -> page 0
    page_off = pos_c % page
    # flat-pool carry with per-layer page offsets, as in llama.ragged_step
    pool_p = k_pages.shape[1]
    k_flat = k_pages.reshape((-1,) + k_pages.shape[2:])
    v_flat = v_pages.reshape((-1,) + v_pages.shape[2:])
    # the packing, once for every Mamba layer of the round
    plan = mamba_scan.scan_plan(token_row, positions, n_rows)

    def feed_forward(xo, lp):
        m = rms(xo, lp["ln_ff"]).astype(dt)
        f = _mm(jax.nn.silu(_mm(m, lp["w_gate"])) * _mm(m, lp["w_up"]),
                lp["w_down"])
        return xo + f.astype(f32)

    def attention_layer(xc, k_flat, v_flat, lp, l: int):
        a = rms(xc, lp["ln_in"]).astype(dt)
        q = _mm(a, lp["wq"]).reshape(t, nh, hd)
        at = (phys + l * pool_p, page_off)
        k_flat = k_flat.at[at].set(_mm(a, lp["wk"]).astype(k_flat.dtype))
        v_flat = v_flat.at[at].set(_mm(a, lp["wv"]).astype(v_flat.dtype))
        o = pa.ragged_paged_attention(
            q, k_flat, v_flat, block_tables + l * pool_p, token_row, pos_c,
            kv_lens, scale=hd ** -0.5)                      # (T, nh, hd)
        xo = xc + _mm(o.reshape(t, -1).astype(dt), lp["wo"]).astype(f32)
        return feed_forward(xo, lp), k_flat, v_flat

    def mamba_layer(carry, l):
        xc, ssm, conv = carry
        lp = {k: lax.dynamic_index_in_dim(params["m_" + k], l, 0,
                                          keepdims=False)
              for k in _MAMBA_KEYS}
        a = rms(xc, lp["ln_in"]).astype(dt)
        with jax.named_scope("mamba.proj"):
            uz = _mm(a, lp["in_proj"])
            u_pre, z = uz[:, :c.d_inner], uz[:, c.d_inner:]
        with jax.named_scope("mamba.conv"):
            u, window = conv_window(
                u_pre, lax.dynamic_index_in_dim(conv, l, 0, keepdims=False),
                token_row, plan, lp["conv_w"], lp["conv_b"])
            conv = lax.dynamic_update_index_in_dim(conv, window, l, 0)
        with jax.named_scope("mamba.proj"):
            dbc = _mm(u, lp["x_proj"])
            eps = c.rms_norm_eps
            low = _rms_small(dbc[:, :r], lp["dt_norm"], eps).astype(dt)
            b = _rms_small(dbc[:, r:r + n], lp["b_norm"], eps)
            cc = _rms_small(dbc[:, r + n:], lp["c_norm"], eps)
            delta = jax.nn.softplus(
                _mm(low, lp["dt_proj"]).astype(f32) + lp["dt_bias"])
            a_neg = -jnp.exp(lp["a_log"])
        y, ssm = mamba_scan.mamba_ragged_scan(
            u.astype(f32), delta, b, cc, a_neg, lp["d_skip"], ssm, l,
            token_row, plan)
        with jax.named_scope("mamba.proj"):
            g = (y * jax.nn.silu(z.astype(f32))).astype(dt)
            xo = xc + _mm(g, lp["out_proj"]).astype(f32)
        return (feed_forward(xo, lp), ssm, conv), None

    for kind, first, count in _runs(c.layer_kinds):
        if kind == "mamba":
            (x, ssm, conv), _ = lax.scan(
                mamba_layer, (x, ssm, conv),
                jnp.arange(first, first + count, dtype=jnp.int32))
        else:
            for l in range(first, first + count):
                x, k_flat, v_flat = attention_layer(
                    x, k_flat, v_flat,
                    {k: params["a_" + k][l] for k in _ATTN_KEYS}, l)
    x = rms(x, params["ln_f"]).astype(dt)
    h_last = jnp.take(x, last_idx.astype(jnp.int32), axis=0)
    logits = jnp.einsum("rh,vh->rv", h_last, params["embed"])
    if logits_epilogue is not None:
        logits = logits_epilogue(logits)
    return (logits, k_flat.reshape(k_pages.shape),
            v_flat.reshape(v_pages.shape), ssm, conv)
