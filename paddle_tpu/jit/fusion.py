"""Profile-guided fusion pass: megakernel-ize profiled hot chains.

ROADMAP item 1, closing the loop the observability plane opened:
``DispatchChainProfiler`` (observability/profiling.py) exports a ranked
producer→consumer hot-chain artifact (``paddle_tpu.hot_chains``) whose
ops resolve to ``ProjectIndex`` symbols — and this module is the
consumer. :class:`FusionPass` reads the artifact, maps ranked chains to
declared **fusable regions**, and rewrites them into single jitted
megaregions (PAPERS.md: MPK "Mega-Kernelizing Tensor Programs", Neptune
operator fusion). The one declared region is the eager grad-transform →
optimizer-update chain of the training side.

Admission discipline (the hard gates, enforced by
``benchmarks/bench_fusion.py`` + ``tests/test_fusion.py``):

* **byte-identical** outputs fused vs. unfused — the optimizer
  megaregion replays the optimizer's own ``_update``/grad-clip code
  through the **eager-granularity stager** below, so fusing never
  changes a single bit of training state;
* **recompile-count-neutral** — the fused program's signature depends
  only on parameter shapes, never on the step or the learning rate;
* **measured ABBA win** recorded in BASELINE.md before a fusion ships
  enabled.

Degradation contract: a stale artifact (symbols renamed/moved since the
capture, or an incompatible schema) or a chain no region declares
produces structured
``fusion_skipped`` events — one deduped event per chain per process —
and ``paddle_fusion_skipped_total{reason}`` counts, never an exception.

Eager-granularity staging (the bit-exactness mechanism)
-------------------------------------------------------

Fusing an eager op chain into one XLA program normally changes numerics:
inside a fused loop LLVM contracts ``a*b + c`` into an FMA, and the XLA
algebraic simplifier rewrites chained divisions — bit drift the eager
per-op execution never sees. :func:`stage_eager` re-emits a traced
function's jaxpr with a **contraction fence** after every floating-point
equation: ``min(x, lim)`` where ``lim`` is a *runtime* input valued
``+inf`` (a constant bound would be folded away). Every intermediate is
forced to its eagerly-rounded value, NaN/±inf/-0.0 pass through
untouched, and the megaregion stays one dispatch — the win is the
eliminated per-op host overhead, which is exactly what the profiler's
hot chains measure.

Layering: this module consumes *symbols*, never the serving/inference
stack — tpu-lint's ``layer-deps`` STRICT contract bans those imports at
any scope.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.events import emit_event
from ..observability.profiling import (PROFILE_VERSION, chain_armed,
                                       dispatch_sites, note_chain)
from ..observability.registry import get_registry
from ..observability.runtime import recompiles

from jax.extend.core import Literal as _JaxprLiteral

#: the artifact this pass consumes (DispatchChainProfiler.export)
ARTIFACT_KIND = "paddle_tpu.hot_chains"

_reg = get_registry()
_admitted_total = _reg.counter(
    "paddle_fusion_admitted_total",
    "hot chains admitted and installed as fused megaregions, by region",
    labels=("region",))
_skipped_total = _reg.counter(
    "paddle_fusion_skipped_total",
    "hot chains the fusion pass skipped (stale artifact, schema "
    "mismatch, no declared region), by reason",
    labels=("reason",))
_active = _reg.gauge(
    "paddle_fusion_active",
    "1 while a fused megaregion is installed for the region",
    labels=("region",))

#: (chain ops tuple, reason) pairs already reported — the skip event is
#: emitted once per chain per process, the counter counts every skip
_skip_noted: set = set()

#: region name -> weakly-referenced installed targets; the active gauge
#: reflects whether any install target is still ALIVE, re-evaluated on
#: every plan()/apply() (a dropped fused optimizer must not report an
#: active megaregion forever — same liveness discipline as the memory
#: ledger's pool table)
_installed_targets: Dict[str, Any] = {}


def _refresh_active_gauges() -> None:
    for region, refs in _installed_targets.items():
        alive = [r for r in refs if r() is not None]
        _installed_targets[region] = alive
        _active.set(1.0 if alive else 0.0, region=region)


def _note_install(region: str, target: "FusedOptimizerStep") -> None:
    import weakref
    _installed_targets.setdefault(region, []).append(weakref.ref(target))


# ---------------------------------------------------------------------------
# Fusable regions
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class FusionRegion:
    """A declared fusable region: a named rewrite this tree knows how to
    install, matched against hot chains by op signature. ``signatures``
    are contiguous op subsequences as they appear in the artifact."""

    name: str
    signatures: Tuple[Tuple[str, ...], ...]
    doc: str = ""

    def match(self, ops: Sequence[str]) -> Optional[Tuple[str, ...]]:
        """The first signature appearing contiguously in ``ops``."""
        ops = tuple(ops)
        for sig in self.signatures:
            n = len(sig)
            if any(ops[i:i + n] == sig
                   for i in range(len(ops) - n + 1)):
                return sig
        return None


#: built-in regions (a test/bench may register more via REGIONS)
REGIONS: Dict[str, FusionRegion] = {
    "optimizer_chain": FusionRegion(
        name="optimizer_chain",
        signatures=(("grad_clip", "optimizer_update"),
                    ("optimizer_update", "optimizer_update"),
                    ("optimizer_update",)),
        doc="eager grad transform -> per-param optimizer update chain "
            "fused into ONE bit-exact jitted megaregion "
            "(FusedOptimizerStep)"),
}


@dataclass
class FusionCandidate:
    region: FusionRegion
    ops: Tuple[str, ...]
    matched: Tuple[str, ...]
    count: int = 0
    est_us: float = 0.0


@dataclass
class FusionPlan:
    """The pass output: chains mapped to installable regions plus the
    structured skips. ``apply`` installs each candidate on the optimizer
    and reports what it did."""

    candidates: List[FusionCandidate] = field(default_factory=list)
    skipped: List[Dict[str, Any]] = field(default_factory=list)

    def candidate(self, region_name: str) -> Optional[FusionCandidate]:
        for c in self.candidates:
            if c.region.name == region_name:
                return c
        return None

    def apply(self, optimizer) -> Dict[str, Any]:
        """Install every planned region on ``optimizer``. Returns
        ``{region name: installed object}``; an optimizer that lacks
        the install surface (or rejects it) is skipped with reason
        ``target-unsupported`` — never an exception."""
        installed: Dict[str, Any] = {}
        for cand in self.candidates:
            name = cand.region.name
            if name in installed:
                continue
            # idempotence: re-applying over an already-installed region
            # must not re-count the admission or re-emit the event
            already = isinstance(getattr(optimizer, "_fused_step", None),
                                 FusedOptimizerStep)
            try:
                installed[name] = install_optimizer_fusion(optimizer)
            except Exception as exc:
                # the degradation contract covers installation too: a
                # target without the surface (AttributeError) or one
                # that rejects it (ValueError) becomes a structured
                # skip, never a raise
                _note_skip(cand.ops, "target-unsupported", region=name,
                           error=f"{type(exc).__name__}: {exc}")
                continue
            _note_install(name, installed[name])
            if already:
                continue
            _admitted_total.inc(region=name)
            emit_event("fusion_applied", region=name,
                       chain="->".join(cand.ops),
                       est_us=cand.est_us, count=cand.count)
        _refresh_active_gauges()
        return installed


def _note_skip(ops: Sequence[str], reason: str, **extra) -> None:
    """Count every skip; emit the structured event once per (chain,
    reason) per process so a pass re-run cannot flood the event log."""
    _skipped_total.inc(reason=reason)
    key = (tuple(ops), reason)
    if key in _skip_noted:
        return
    _skip_noted.add(key)
    emit_event("fusion_skipped", chain="->".join(ops), reason=reason,
               **extra)


class FusionPass:
    """Maps a ``paddle_tpu.hot_chains`` artifact to installable fused
    regions. ``resolver`` (op name -> current symbol) defaults to the
    analysis ProjectIndex view (:func:`profiling.dispatch_sites`); the
    pass trusts op names only as far as they still resolve in the
    CURRENT tree, so a stale artifact degrades to structured skips."""

    def __init__(self, regions: Optional[Dict[str, FusionRegion]] = None,
                 resolver: Optional[Callable[[], Dict[str, str]]] = None):
        self.regions = dict(regions if regions is not None else REGIONS)
        self._resolver = resolver or dispatch_sites

    # -- artifact intake ----------------------------------------------------

    @staticmethod
    def load(path: str) -> Dict[str, Any]:
        with open(path) as f:
            return json.load(f)

    def plan(self, artifact: Any) -> FusionPlan:
        """Rank-order walk over the artifact's chains. Never raises on
        artifact problems: schema mismatches and unresolvable symbols
        become ``fusion_skipped`` entries."""
        _refresh_active_gauges()
        plan = FusionPlan()
        if not isinstance(artifact, dict) \
                or artifact.get("kind") != ARTIFACT_KIND \
                or artifact.get("schema_version",
                                artifact.get("version")) != PROFILE_VERSION:
            got = None
            if isinstance(artifact, dict):
                got = (artifact.get("kind"),
                       artifact.get("schema_version",
                                    artifact.get("version")))
            _note_skip(("<artifact>",), "schema-mismatch", got=repr(got),
                       want=f"{ARTIFACT_KIND} v{PROFILE_VERSION}")
            plan.skipped.append({"chain": ("<artifact>",),
                                 "reason": "schema-mismatch"})
            return plan
        sites = self._resolver()
        claimed = artifact.get("symbols") or {}
        for chain in artifact.get("chains", []):
            ops = tuple(chain.get("ops", ()))
            if not ops:
                continue
            # staleness first: an op the ARTIFACT resolved to a symbol
            # that no longer resolves in the current ProjectIndex means
            # the capture predates a refactor — never rewrite against it
            stale = [op for op in ops if claimed.get(op)
                     and op not in sites]
            if stale:
                _note_skip(ops, "symbol-missing",
                           missing=",".join(stale))
                plan.skipped.append({"chain": ops,
                                     "reason": "symbol-missing",
                                     "missing": stale})
                continue
            matched_region = None
            matched_sig = None
            for region in self.regions.values():
                sig = region.match(ops)
                if sig is not None:
                    matched_region, matched_sig = region, sig
                    break
            if matched_region is None:
                _note_skip(ops, "no-region")
                plan.skipped.append({"chain": ops, "reason": "no-region"})
                continue
            missing = [op for op in matched_sig if op not in sites]
            if missing:
                # the region's own taps are gone from the tree (the
                # artifact predates a rename of the fusable code)
                _note_skip(ops, "symbol-missing", region=matched_region.name,
                           missing=",".join(missing))
                plan.skipped.append({"chain": ops,
                                     "reason": "symbol-missing",
                                     "missing": missing})
                continue
            plan.candidates.append(FusionCandidate(
                region=matched_region, ops=ops, matched=matched_sig,
                count=int(chain.get("count", 0)),
                est_us=float(chain.get("est_us", 0.0))))
        return plan


# ---------------------------------------------------------------------------
# Eager-granularity staging (bit-exact megaregions)
# ---------------------------------------------------------------------------
class _Stager:
    """Records host-scalar materialisations during trace and replays
    their f64 evaluations per call (see :class:`HostScalar`)."""

    def __init__(self):
        self.slots: List[Callable[[Dict[str, float]], float]] = []
        self.traced = None          # traced scalar-vector during trace
        self.env: Dict[str, float] = {}

    def leaf(self, name: str) -> "HostScalar":
        return HostScalar(self, lambda env, n=name: env[n])

    def slot(self, ev):
        j = len(self.slots)
        self.slots.append(ev)
        return self.traced[j]

    def values(self) -> np.ndarray:
        return np.asarray([np.float32(ev(self.env)) for ev in self.slots],
                          np.float32)


class HostScalar:
    """A lazily-evaluated host (float64) scalar expression.

    Passed where eager code passes a Python float (``lr``, ``step``):
    scalar-scalar arithmetic stays on the host at full f64 precision
    exactly like the eager interpreter, and the moment an expression
    meets a traced array it materialises as one f32 input slot — the
    same single rounding the eager op's weak-typed scalar takes. The
    traced program therefore never bakes a step-dependent constant
    (no per-step recompiles) and never computes scalar math in f32
    (no bit drift vs. eager)."""

    __array_priority__ = 200        # win dunder dispatch vs np/jnp arrays

    def __init__(self, stager: _Stager, ev):
        self._st = stager
        self._ev = ev

    # -- composition --------------------------------------------------------
    def _lift(self, other):
        if isinstance(other, HostScalar):
            return other._ev
        if isinstance(other, (int, float)) and not isinstance(other, bool):
            return lambda env, v=other: v
        return None

    def _binop(self, other, op, rev: bool):
        oe = self._lift(other)
        if oe is None:              # traced-array operand: materialise
            t = self._st.slot(self._ev)
            return op(other, t) if rev else op(t, other)
        me = self._ev
        if rev:
            return HostScalar(self._st, lambda env: op(oe(env), me(env)))
        return HostScalar(self._st, lambda env: op(me(env), oe(env)))

    def __mul__(self, o): return self._binop(o, lambda a, b: a * b, False)
    def __rmul__(self, o): return self._binop(o, lambda a, b: a * b, True)
    def __add__(self, o): return self._binop(o, lambda a, b: a + b, False)
    def __radd__(self, o): return self._binop(o, lambda a, b: a + b, True)
    def __sub__(self, o): return self._binop(o, lambda a, b: a - b, False)
    def __rsub__(self, o): return self._binop(o, lambda a, b: a - b, True)
    def __truediv__(self, o):
        return self._binop(o, lambda a, b: a / b, False)
    def __rtruediv__(self, o):
        return self._binop(o, lambda a, b: a / b, True)
    def __pow__(self, o): return self._binop(o, lambda a, b: a ** b, False)
    def __rpow__(self, o): return self._binop(o, lambda a, b: a ** b, True)
    def __neg__(self):
        return HostScalar(self._st, lambda env: -self._ev(env))


def _eval_guarded(jaxpr, consts, lim, *args):
    """Re-emit a jaxpr with a contraction fence (``min(x, lim)``, lim a
    runtime +inf) after every floating-point equation output — each
    intermediate is pinned to its eagerly-rounded value, so XLA's
    cross-op FMA contraction and division re-association cannot change
    a bit (module docstring).

    Float *literals* are fenced too: under jit a Python-scalar operand
    becomes a compile-time constant that XLA rewrites (``x / c`` turns
    into ``x * (1/c)``), while the eager interpreter ships it as a
    runtime buffer and divides for real. Routing each float literal
    through the fence makes it runtime again — dtype-exact (the jaxpr
    already recorded the weak-type promotion), value-identical."""
    env: Dict[Any, Any] = {}

    def read(v):
        if isinstance(v, _JaxprLiteral):
            val = v.val
            aval = v.aval
            if (getattr(aval, "dtype", None) is not None
                    and jnp.issubdtype(aval.dtype, jnp.floating)):
                return jnp.minimum(jnp.asarray(val, aval.dtype),
                                   lim.astype(aval.dtype))
            return val
        return env[v]

    for v, c in zip(jaxpr.constvars, consts):
        env[v] = c
    for v, a in zip(jaxpr.invars, args):
        env[v] = a
    for eqn in jaxpr.eqns:
        invals = [read(v) for v in eqn.invars]
        outs = eqn.primitive.bind(*invals, **eqn.params)
        if not eqn.primitive.multiple_results:
            outs = [outs]
        for v, o in zip(eqn.outvars, outs):
            if hasattr(o, "dtype") and jnp.issubdtype(o.dtype,
                                                      jnp.floating):
                # dtype-matched fence: a bare minimum(bf16, f32) would
                # silently promote the intermediate
                o = jnp.minimum(o, lim.astype(o.dtype))
            env[v] = o
    return [read(v) for v in jaxpr.outvars]


def stage_eager(fn: Callable, *example_args):
    """Trace ``fn`` once over ``example_args`` (shape/dtype only) and
    return the jitted ``staged(lim, *args)`` evaluating it with per-op
    contraction fences — the program a megaregion dispatches to stay
    bit-identical to the eager chain it replaces."""
    closed = jax.make_jaxpr(fn)(*example_args)

    def staged(lim, *args):
        flat, treedef = jax.tree_util.tree_flatten(args)
        del treedef  # the jaxpr's invars ARE the flat order
        outs = _eval_guarded(closed.jaxpr, closed.consts, lim, *flat)
        return outs
    return jax.jit(staged), closed


# ---------------------------------------------------------------------------
# Region: optimizer_chain — the fused grad-transform/update megaregion
# ---------------------------------------------------------------------------
class _ClipParam:
    """need_clip stand-in handed to grad-clip transforms under trace
    (same device as jit.TrainStep's compiled path)."""

    __slots__ = ("need_clip",)

    def __init__(self, nc: bool):
        self.need_clip = bool(nc)


class FusedOptimizerStep:
    """ONE jitted dispatch for the whole eager optimizer chain: grad
    transform (the optimizer's own ``_grad_clip``) + every parameter's
    ``_update`` + host metric taps, replayed through the
    eager-granularity stager so committed params/accumulators are
    byte-identical to ``Optimizer.step()`` — verified per optimizer
    family by ``tests/test_fusion.py`` and gated by
    ``benchmarks/bench_fusion.py``.

    Installed via :func:`install_optimizer_fusion` (the pass's
    ``optimizer_chain`` region): ``optimizer.step()`` then delegates
    here. The compiled program's signature depends only on parameter
    shapes/dtypes, state slots and static per-param attributes — the
    step counter and LR enter as host-staged scalar inputs, so a
    training loop never recompiles it. Buffers are NOT donated: the
    eager step leaves previous arrays valid for outside holders
    (checkpoint refs), and the fused step keeps that contract."""

    def __init__(self, optimizer):
        self._opt = optimizer
        self._compiled: Dict[Tuple, Tuple] = {}
        self.steps_fused = 0

    # -- build (one program per parameter-set signature) --------------------

    def _hyper_signature(self) -> Tuple:
        """Every scalar hyperparameter the traced program bakes in as a
        constant (betas, eps, momentum, weight decay, the grad-clip
        bound, ...). Mutating one after install MUST rebuild — eager
        ``step()`` honours the new value immediately, and the fused
        step promises bit-identity with eager. ``_step_count`` and
        ``_learning_rate`` are excluded: both enter as host-staged
        runtime inputs, never as constants."""
        opt = self._opt
        skip = {"_step_count", "_learning_rate"}

        def scalars(obj):
            return tuple(sorted(
                (k, bool(v) if isinstance(v, bool) else float(v))
                for k, v in vars(obj).items()
                if k not in skip and isinstance(v, (int, float, bool))))

        clip = opt._grad_clip
        csig = (() if clip is None
                else (type(clip).__name__,) + scalars(clip))
        return scalars(opt) + (csig,)

    def _signature(self, params) -> Tuple:
        opt = self._opt
        sig = [self._hyper_signature()]
        for p in params:
            st = opt._state_of(p)
            sig.append((
                tuple(p._value.shape), str(p._value.dtype),
                tuple(p._grad_value.shape), str(p._grad_value.dtype),
                tuple(sorted((k, tuple(v.shape), str(v.dtype))
                             for k, v in st.items())),
                bool(opt._decay_enabled(p)),
                float(p.optimize_attr.get("learning_rate", 1.0)),
                bool(getattr(p, "need_clip", True)),
            ))
        return tuple(sig)

    def _build(self, params):
        opt = self._opt
        stager = _Stager()
        wd_on = [opt._decay_enabled(p) for p in params]
        mults = [p.optimize_attr.get("learning_rate", 1.0) for p in params]
        clip_objs = [_ClipParam(getattr(p, "need_clip", True))
                     for p in params]

        def whole(scal, pvals, gvals, svals):
            stager.traced = scal
            lr = stager.leaf("lr")
            step = stager.leaf("step")
            grads = list(gvals)
            if opt._grad_clip is not None:
                pairs = opt._grad_clip(list(zip(clip_objs, grads)))
                grads = [g for _, g in pairs]
            saved_wd = opt._weight_decay
            new_p, new_s = [], []
            try:
                for i in range(len(pvals)):
                    opt._weight_decay = saved_wd if wd_on[i] else 0.0
                    nv, ns = opt._update(pvals[i], grads[i],
                                         dict(svals[i]), lr * mults[i],
                                         step)
                    new_p.append(nv)
                    new_s.append(ns)
            finally:
                opt._weight_decay = saved_wd
            return new_p, new_s

        pv = [p._value for p in params]
        gv = [p._grad_value for p in params]
        sv = [dict(opt._state_of(p)) for p in params]
        # generous fixed slot vector: sized from a dry trace would need
        # two passes; 4 slots/param + 8 covers every shipped optimizer
        scal_dim = 4 * len(params) + 8
        dummy = jnp.zeros((scal_dim,), jnp.float32)
        jitted, _ = stage_eager(whole, dummy, pv, gv, sv)
        if len(stager.slots) > scal_dim:     # pragma: no cover - guard
            raise RuntimeError(
                f"optimizer staged {len(stager.slots)} host scalars > "
                f"slot vector {scal_dim}")
        out_tree = jax.tree_util.tree_structure((pv, sv))
        return jitted, stager, scal_dim, out_tree

    # -- the service surface (Optimizer.step delegates here) ----------------

    def step(self) -> None:
        opt = self._opt
        armed = chain_armed[0]
        t0 = time.perf_counter_ns() if armed else 0
        opt._step_count += 1
        params = [p for p in opt._parameter_list
                  if p._grad_value is not None and p.trainable]
        if not params:
            return
        key = self._signature(params)
        entry = self._compiled.get(key)
        if entry is None:
            recompiles.record_miss("fusion.optimizer_chain",
                                   ("params", len(params)))
            entry = self._compiled[key] = self._build(params)
        jitted, stager, scal_dim, out_tree = entry
        stager.env = {"lr": opt.get_lr(), "step": opt._step_count}
        scal = np.zeros((scal_dim,), np.float32)
        vals = stager.values()
        scal[:len(vals)] = vals
        pv = [p._value for p in params]
        gv = [p._grad_value for p in params]
        sv = [dict(opt._state_of(p)) for p in params]
        outs = jitted(jnp.float32(np.inf), jnp.asarray(scal), pv, gv, sv)
        new_p, new_s = jax.tree_util.tree_unflatten(out_tree, outs)
        for p, nv, ns in zip(params, new_p, new_s):
            p._value = nv
            opt._accumulators[id(p)] = ns
        self.steps_fused += 1
        if armed:
            note_chain(op_name="fused_optimizer_step",
                       dur_ns=time.perf_counter_ns() - t0)


def install_optimizer_fusion(optimizer) -> FusedOptimizerStep:
    """Install the ``optimizer_chain`` megaregion: ``optimizer.step()``
    delegates to the fused step from now on (idempotent)."""
    fused = getattr(optimizer, "_fused_step", None)
    if isinstance(fused, FusedOptimizerStep):
        return fused
    fused = FusedOptimizerStep(optimizer)
    optimizer._fused_step = fused
    return fused
