"""Distribution-faithful decoding: the in-program sampling epilogue.

ISSUE 16's tentpole. The unified ragged step already produces per-row
logits in ONE compiled program; this module is the epilogue that turns
them into tokens for every workload class at once — greedy, sampled
(temperature / top-k / top-p), speculative and grammar-constrained —
without forking the program:

* **Per-request runtime parameters.** :class:`SamplerConfig`
  (temperature, top_k, top_p, per-request seed) rides on each request
  and lands in per-row HOST numpy mirrors the engine writes at admission
  (like its position and block-table mirrors); the device copies the
  step program takes are refreshed with the plan's uploads, and only on
  a step whose admission changed a value. A mixed greedy/sampled/
  constrained batch is one dispatch and the request mix never
  recompiles anything.
* **Counter-based PRNG.** The key for the token at sequence position
  ``P`` of a request with seed ``s`` is
  ``fold_in(fold_in(PRNGKey(s), P), salt)`` — derived in-program from
  plain int inputs, no key state threads across steps and no global
  stream couples rows. Streams are therefore seeded-replayable
  (same seed => same tokens) regardless of batch composition, chunk
  size, TP degree, or a mid-stream failover resume (the position IS
  the counter).
* **Greedy is temperature == 0**, computed as ``argmax`` over the same
  (grammar-masked) logits — for unconstrained rows the mask is a no-op
  and the argmax is bit-identical to the pre-sampling engine.
* **Lossless rejection-sampling speculation**
  (:func:`spec_sample_rows`). The shipped drafters are deterministic,
  so the draft distribution is a point mass and the accept probability
  ``min(1, p/q)`` reduces to ``p_target(draft)``; on rejection the
  residual ``max(p - q, 0)`` renormalized is exactly the target with
  the draft token excluded — one categorical over the processed logits
  with that token masked. The committed-token marginal equals the
  non-speculative sampler's distribution EXACTLY (property-tested in
  ``tests/test_sampling.py``); greedy rows keep the verify-by-argmax
  prefix match and stay byte-identical.
* Salt discipline: ``DRAW`` keys ordinary categorical draws (shared by
  the non-spec epilogue and the spec bonus/undrafted draws — a row
  with an empty draft commits byte-identically to the non-spec
  sampler), ``ACCEPT`` keys the per-candidate accept coin,
  ``RESAMPLE`` keys the residual draw. Keys at positions a rejected
  round discarded are re-derived next round — the accept prefix is a
  function of the coins at earlier positions only, so reuse is
  independence-safe.

Grammar masking/advance live in ``inference/constrain.py``; the
engine applies the mask via the model's ``logits_epilogue`` hook BEFORE
this module's temperature/top-k/top-p processing, so constrained rows
renormalize over legal tokens only.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.registry import get_registry
from . import constrain as _constrain

#: PRNG salts (see module docstring)
SALT_DRAW = 0
SALT_ACCEPT = 1
SALT_RESAMPLE = 2

_reg = get_registry()
_c_requests = _reg.counter(
    "paddle_sampling_requests_total",
    "requests admitted with a non-greedy epilogue, by mode "
    "(sampled | constrained)",
    labels=("mode",))
_c_tokens = _reg.counter(
    "paddle_sampling_tokens_total",
    "tokens committed through the sampling epilogue, by mode",
    labels=("mode",))
_c_violations = _reg.counter(
    "paddle_sampling_violations_total",
    "tokens the host grammar mirror rejected (device/host automaton "
    "disagreement — never expected; each also emits a "
    "constraint_violation event)")
_g_states = _reg.gauge(
    "paddle_sampling_grammar_states",
    "grammar-arena rows in use across registered token DFAs")


def note_request(mode: str) -> None:
    _c_requests.inc(mode=mode)


def note_tokens(mode: str, n: int) -> None:
    if n:
        _c_tokens.inc(n, mode=mode)


def note_violation() -> None:
    _c_violations.inc()


def set_grammar_states(n: int) -> None:
    _g_states.set(float(n))


@dataclass(frozen=True)
class SamplerConfig:
    """Per-request sampling parameters. ``temperature == 0`` is greedy
    (the byte-identical argmax path); ``top_k == 0`` and
    ``top_p == 1.0`` disable their filters. ``seed=None`` asks the
    engine to derive a deterministic per-request seed (config seed +
    rid) — pass an explicit seed for streams that must replay across
    engines (e.g. router failover resume)."""

    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None

    def resolved(self, default_seed: int) -> "SamplerConfig":
        if self.seed is not None:
            return self
        return replace(self, seed=int(default_seed) & 0x7FFFFFFF)


def greedy_config() -> SamplerConfig:
    return SamplerConfig(temperature=0.0, seed=0)


#: the per-row sampler parameters one engine slot owns, as HOST numpy
#: mirrors in the step program's tuple order:
#: (seeds uint32, temperatures f32, top_k int32, top_p f32)
def init_row_state(num_rows: int) -> Tuple:
    return (np.zeros((num_rows,), np.uint32),
            np.zeros((num_rows,), np.float32),
            np.zeros((num_rows,), np.int32),
            np.ones((num_rows,), np.float32))


def set_row(samp: Tuple, s: int, cfg: Optional[SamplerConfig]) -> bool:
    """Write one slot's sampler parameters into the host mirrors at
    admission, in place. ``None`` resets the row to greedy defaults —
    slot reuse must never inherit a previous request's temperature.
    Returns whether a value changed: only then do the device copies
    need a refresh (greedy after greedy writes what the row holds)."""
    if cfg is None:
        cfg = greedy_config()
    changed = False
    for rows, value in zip(samp, ((cfg.seed or 0) & 0xFFFFFFFF,
                                  cfg.temperature, cfg.top_k, cfg.top_p)):
        value = rows.dtype.type(value)
        if rows[s] != value:
            rows[s] = value
            changed = True
    return changed


# ---------------------------------------------------------------------------
# In-program pieces
# ---------------------------------------------------------------------------
def _keys(seeds, pos, salt):
    """(N,) uint32 seeds x (N,) int32 positions -> N independent keys:
    ``fold_in(fold_in(PRNGKey(seed), pos), salt)``. Counter-based — no
    key threads across calls, so the draw at a given (seed, position,
    salt) is one fixed value wherever/whenever it is computed."""
    base = jax.vmap(jax.random.PRNGKey)(seeds)
    keyed = jax.vmap(jax.random.fold_in)(base, pos)
    return jax.vmap(lambda k: jax.random.fold_in(k, salt))(keyed)


def process_logits(logits, temps, top_k, top_p):
    """Temperature scale -> top-k -> top-p, all with PER-ROW runtime
    parameters — the vectorized form of the scalar-parameter filter
    chain tests/test_sampling.py holds it to (same kth-value rule, same
    keep-ties-at-cutoff top-p rule), with
    ``top_k == 0`` / ``top_p == 1`` rows passing through untouched.
    ``logits`` must already be f32 (and grammar-masked for constrained
    rows)."""
    V = logits.shape[-1]
    x = logits / jnp.maximum(temps, 1e-6)[:, None]
    k_on = (top_k > 0)[:, None]
    sorted_desc = jnp.sort(x, axis=-1)[..., ::-1]
    k_idx = jnp.clip(top_k - 1, 0, V - 1)[:, None]
    kth = jnp.take_along_axis(sorted_desc, k_idx, axis=-1)
    x = jnp.where(k_on & (x < kth), -jnp.inf, x)
    p_on = (top_p < 1.0)[:, None]
    sorted_desc = jnp.sort(x, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(sorted_desc,
                                 jnp.clip(cutoff_idx, 0, V - 1), axis=-1)
    return jnp.where(p_on & (x < cutoff), -jnp.inf, x)


def sample_rows(logits, pos_next, samp, gstate, gtable):
    """The unified-step epilogue: per-row logits -> (token, grammar
    state). ``logits (rows, V)`` must already carry the grammar mask
    (the model's ``logits_epilogue`` hook applies
    :func:`constrain.mask_logits` first); ``pos_next (rows,)`` is the
    sequence position the sampled token will occupy (= the row's
    attended length this micro-round) — it is the PRNG counter.
    Greedy rows (``temperature <= 0``) take the bit-exact argmax."""
    seeds, temps, top_k, top_p = samp
    lg = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    proc = process_logits(lg, temps, top_k, top_p)
    keys = _keys(seeds, pos_next, SALT_DRAW)
    drawn = jax.vmap(jax.random.categorical)(keys, proc).astype(jnp.int32)
    tok = jnp.where(temps <= 0.0, greedy_tok, drawn)
    return tok, _constrain.advance_states(gstate, tok, gtable)


def greedy_rows(logits, pos_next, samp, gstate, gtable):
    """Argmax-only twin of :func:`sample_rows` for engines whose
    request mix has never seen a sampler or a grammar: the engine
    compiles this tail until the first ``sampler=``/``grammar=``
    submit flips it to the full epilogue (ONE counted recompile, then
    sticky). Tracing no sort/cumsum/PRNG keeps the greedy program's
    compile cost at the pre-sampling baseline — on single-core CI
    boxes compile time is the tier-1 budget. The f32 cast is
    value-exact for bf16/f16 logits, so the argmax is bit-identical
    to ``sample_rows``'s greedy path."""
    del pos_next, samp, gtable
    tok = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    return tok, gstate


def spec_greedy_rows(logits, drafts, draft_len, pos_base, samp, gstate,
                     gtable):
    """Greedy-only twin of :func:`spec_sample_rows` (same signature,
    same ``(tokens, accepted, gstate)`` fence): per-candidate argmax +
    drafted-prefix match, no rejection sampling, no grammar advance —
    the pre-sampling speculative verifier. Swapped in by the engine
    while the epilogue is off (see :func:`greedy_rows`)."""
    del pos_base, samp, gtable
    R, k1, V = logits.shape
    k = k1 - 1
    g = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    if k > 0:
        lane = jnp.arange(k, dtype=jnp.int32)[None, :]
        valid = lane < draft_len[:, None]
        d = jnp.clip(drafts[:, :k], 0, V - 1)
        match = (d == g[:, :k]) & valid
        accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                           axis=1).astype(jnp.int32)
    else:
        accepted = jnp.zeros((R,), jnp.int32)
    return g, accepted, gstate


def spec_sample_rows(logits, drafts, draft_len, pos_base, samp, gstate,
                     gtable):
    """The speculative-step epilogue: per-candidate logits
    ``(rows, k+1, V)`` -> ``(tokens (rows, k+1), accepted (rows,),
    grammar state)``; the host commits ``draft[:a] + [tokens[a]]``
    where ``a = accepted``.

    Greedy rows: exact argmax + drafted-prefix match (byte-identical to
    the pre-sampling verifier). Sampled rows: lossless rejection
    sampling against the deterministic (point-mass) draft — candidate
    ``j`` accepts with probability ``p_j(draft_j)`` (the ``min(1, p/q)``
    rule with ``q`` a point mass), and ``tokens[j]`` holds the residual
    resample for drafted lanes / the plain ``DRAW``-salt categorical
    past the draft (so an undrafted row commits byte-identically to the
    non-speculative sampler at the same position). Constrained rows
    never draft (``draft_len == 0``); candidate 0 is grammar-masked and
    the row's DFA state advances on its committed token."""
    seeds, temps, top_k, top_p = samp
    R, k1, V = logits.shape
    k = k1 - 1
    lg = logits.astype(jnp.float32)
    lg0 = _constrain.mask_logits(lg[:, 0], gstate, gtable)
    lg = lg.at[:, 0].set(lg0)
    g = jnp.argmax(lg, axis=-1).astype(jnp.int32)          # (R, k1)
    flat = lg.reshape(R * k1, V)
    rep = lambda a: jnp.repeat(a, k1)  # noqa: E731 - row -> candidates
    proc = process_logits(flat, rep(temps), rep(top_k),
                          rep(top_p)).reshape(R, k1, V)
    pos_gen = (pos_base[:, None] + 1
               + jnp.arange(k1, dtype=jnp.int32)[None, :])  # (R, k1)
    seeds_c = jnp.repeat(seeds, k1)
    plain = jax.vmap(jax.random.categorical)(
        _keys(seeds_c, pos_gen.reshape(-1), SALT_DRAW),
        proc.reshape(R * k1, V)).reshape(R, k1).astype(jnp.int32)
    if k > 0:
        lane = jnp.arange(k, dtype=jnp.int32)[None, :]
        valid = lane < draft_len[:, None]                   # (R, k)
        d = jnp.clip(drafts[:, :k], 0, V - 1)
        match = (d == g[:, :k]) & valid
        acc_greedy = jnp.sum(jnp.cumprod(match.astype(jnp.int32),
                                         axis=1), axis=1)
        probs = jax.nn.softmax(proc[:, :k, :], axis=-1)
        p_d = jnp.take_along_axis(probs, d[..., None], axis=-1)[..., 0]
        u = jax.vmap(jax.random.uniform)(
            _keys(seeds_c.reshape(R, k1)[:, :k].reshape(-1),
                  pos_gen[:, :k].reshape(-1),
                  SALT_ACCEPT)).reshape(R, k)
        accept = (u < p_d) & valid
        acc_sampled = jnp.sum(jnp.cumprod(accept.astype(jnp.int32),
                                          axis=1), axis=1)
        resid = jnp.where(jax.nn.one_hot(d, V, dtype=bool),
                          -jnp.inf, proc[:, :k, :])
        r = jax.vmap(jax.random.categorical)(
            _keys(seeds_c.reshape(R, k1)[:, :k].reshape(-1),
                  pos_gen[:, :k].reshape(-1), SALT_RESAMPLE),
            resid.reshape(R * k, V)).reshape(R, k).astype(jnp.int32)
        toks_s = jnp.concatenate(
            [jnp.where(valid, r, plain[:, :k]), plain[:, k:]], axis=1)
    else:
        acc_greedy = jnp.zeros((R,), jnp.int32)
        acc_sampled = jnp.zeros((R,), jnp.int32)
        toks_s = plain
    greedy = temps <= 0.0
    toks = jnp.where(greedy[:, None], g, toks_s)
    accepted = jnp.where(greedy, acc_greedy, acc_sampled).astype(jnp.int32)
    gst = _constrain.advance_states(gstate, toks[:, 0], gtable)
    return toks, accepted, gst
