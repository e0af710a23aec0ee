"""``paddle_tpu.jit`` — dy2static equivalent.

The reference compiles imperative code via AST transforms + SOT bytecode
tracing (python/paddle/jit/, SURVEY.md §2.5 dy2static row). Here jax.jit IS
the tracer: ``to_static`` lifts a Layer's parameters/buffers into traced
arguments and jit-compiles the forward; ``TrainStep`` compiles the full
forward+backward+optimizer update into ONE XLA program (the equivalent of the
reference's whole-graph executor path, with XLA doing the stream scheduling).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Parameter, Tensor
from ..core import autograd as _ag
from ..nn.layer import Layer
from ..optimizer.optimizer import Optimizer
from .. import random as _random
from .functional import bind, param_arrays, buffer_arrays, tree_unwrap, tree_wrap


class RecompileWarning(UserWarning):
    """A compiled function saw a new input signature and recompiled."""


class CompileGuard:
    """Input-signature guard for jit boundaries — the SOT-guard equivalent
    (reference: python/paddle/jit/sot/ bytecode guards, SURVEY.md §2.5
    dy2static row / §7 hard-part #3).

    jax.jit retraces silently on any shape/dtype/pytree change; this guard
    makes every such cache miss VISIBLE: ``recompile_count`` counts misses
    after the first compile and each miss emits a :class:`RecompileWarning`
    naming the signature drift, so a shape leak in a training loop cannot
    silently recompile per step.
    """

    def __init__(self, name: str):
        self.name = name
        self._sigs: set = set()
        self.recompile_count = 0

    @staticmethod
    def signature(*trees):
        import jax as _jax

        leaves, treedef = _jax.tree_util.tree_flatten(trees)
        return (treedef,) + tuple(
            (getattr(v, "shape", ()), str(getattr(v, "dtype", type(v).__name__)))
            for v in leaves)

    def check(self, *trees) -> bool:
        """Record the call signature; returns True when it misses the cache
        (first call does not count as a recompile)."""
        import warnings

        sig = self.signature(*trees)
        if sig in self._sigs:
            return False
        miss = bool(self._sigs)
        self._sigs.add(sig)
        # every cache miss (first compile included) lands in the global
        # trace-cache-miss counter + event log with the shape signature.
        # record_miss, not note: self._sigs already dedupes per instance,
        # and two same-named guards (e.g. two models' "forward") must each
        # count their own real recompiles
        from ..observability.runtime import recompiles
        recompiles.record_miss(f"jit.{self.name}", sig)
        if miss:
            self.recompile_count += 1
            warnings.warn(
                f"{self.name}: input signature changed (seen "
                f"{len(self._sigs)} distinct signatures) -> XLA recompile "
                f"#{self.recompile_count}. Pad/bucket inputs to stable "
                "shapes to avoid per-step compilation.",
                RecompileWarning, stacklevel=3)
        return miss


class StaticFunction:
    """jit-compiled forward (inference/eval) over an imperative fn/Layer.

    The wrapped fn passes through the dy2static AST rewrite first
    (jit/dy2static.py), so data-dependent Python ``if``/``while`` over
    Tensors lower to lax.cond / lax.while_loop instead of failing at trace
    time — the SOT-conversion analog. When tracing still fails
    (ConversionError or an untraceable predicate) and
    ``FLAGS_dy2static_fallback`` is on (default), the call falls back to
    the EAGER path with a warning and stays eager — the reference SOT's
    graceful-fallback behaviour; ``FLAGS_dy2static_fallback=0`` restores
    the strict raise.
    """

    def __init__(self, fn: Callable, layer: Optional[Layer] = None,
                 donate_params: bool = False):
        from .dy2static import convert_control_flow
        self._orig_fn = fn
        self._fn = convert_control_flow(fn)
        self._layer = layer
        self._jitted = None
        self._fallback = False
        self.guard = CompileGuard(getattr(fn, "__name__", "to_static"))

    def _build(self):
        layer = self._layer

        def pure(params, buffers, key, args, kwargs):
            with _random.traced_key_scope(key):
                wargs = tree_wrap(args)
                wkwargs = tree_wrap(kwargs)
                if layer is not None:
                    with bind(layer, params, buffers):
                        out = self._fn(*wargs, **wkwargs)
                else:
                    out = self._fn(*wargs, **wkwargs)
                return tree_unwrap(out)

        self._jitted = jax.jit(pure)

    def __call__(self, *args, **kwargs):
        if self._fallback:
            return self._orig_fn(*args, **kwargs)
        if self._jitted is None:
            self._build()
        params = param_arrays(self._layer) if self._layer else {}
        buffers = buffer_arrays(self._layer) if self._layer else {}
        key = _random.next_key()
        uargs, ukwargs = tree_unwrap(args), tree_unwrap(kwargs)
        self.guard.check(uargs, ukwargs)
        from .dy2static import ConversionError
        from ..core.tensor import TracedIterationError
        try:
            out = self._jitted(params, buffers, key, uargs, ukwargs)
        except (ConversionError, TracedIterationError,
                jax.errors.ConcretizationTypeError) as e:
            from ..flags import flag_value
            if not flag_value("dy2static_fallback"):
                raise
            import warnings
            warnings.warn(
                f"{self.guard.name}: tracing failed "
                f"({type(e).__name__}: {str(e).splitlines()[0]}); falling "
                "back to the EAGER path for this and future calls — the "
                "function will not be compiled "
                "(FLAGS_dy2static_fallback=0 restores the strict raise)",
                stacklevel=2)
            self._fallback = True
            return self._orig_fn(*args, **kwargs)
        return tree_wrap(out)

    @property
    def recompile_count(self) -> int:
        return self.guard.recompile_count


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              **kwargs):
    """Parity with paddle.jit.to_static (decorator or call form)."""

    def decorate(fn):
        if isinstance(fn, Layer):
            sf = StaticFunction(fn.forward, layer=fn)
            fn.forward = sf
            return fn
        layer = getattr(fn, "__self__", None)
        if isinstance(layer, Layer):
            return StaticFunction(fn, layer=layer)
        return StaticFunction(fn, layer=None)

    if function is not None:
        return decorate(function)
    return decorate


class TrainStep:
    """One fully-compiled training step: forward + tape backward + clip +
    optimizer update + buffer (e.g. BN stats) update, as a single XLA program
    with donated parameter/optimizer buffers.

    Equivalent of the reference's static-graph hot loop (SURVEY.md §3.4), but
    derived automatically from imperative code.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer: Optimizer,
                 in_shardings=None, donate: bool = True):
        self.model = model
        self.loss_fn = loss_fn  # (model, *batch) -> scalar Tensor
        self.optimizer = optimizer
        self._donate = donate
        self._jitted = None
        self.guard = CompileGuard(type(self).__name__)
        # materialise optimizer state for every trainable param now
        self._trainable = [
            (name, p) for name, p in model.named_parameters() if p.trainable
        ]
        for _, p in self._trainable:
            optimizer._state_of(p)

    # -- pytree helpers -----------------------------------------------------
    def _opt_state_tree(self):
        return {name: dict(self.optimizer._accumulators[id(p)])
                for name, p in self._trainable}

    def _write_back(self, params, opt_state, buffers):
        by_name = dict(self.model.named_parameters())
        for name, v in params.items():
            by_name[name]._value = v
        for name, p in self._trainable:
            self.optimizer._accumulators[id(p)] = dict(opt_state[name])
        buf_objs = {n: b for n, b in self.model.named_buffers() if b is not None}
        for name, v in buffers.items():
            if name in buf_objs:
                buf_objs[name]._value = v

    # -- build --------------------------------------------------------------
    def _build(self):
        donate = (0, 1, 2) if self._donate else ()
        self._jitted = jax.jit(self._make_step_fn(), donate_argnums=donate)

    def _make_step_fn(self):
        model = self.model
        opt = self.optimizer
        loss_fn = self.loss_fn
        trainable_names = [n for n, _ in self._trainable]
        lr_mults = {n: p.optimize_attr.get("learning_rate", 1.0)
                    for n, p in self._trainable}
        need_clip = {n: getattr(p, "need_clip", True) for n, p in self._trainable}
        # honour per-param decay exclusion (AdamW.apply_decay_param_fun,
        # Lamb.exclude_from_weight_decay_fn) in the compiled path too
        wd_on = {n: opt._decay_enabled(p) for n, p in self._trainable}

        def step(params, opt_state, buffers, batch, lr, step_i, key):
            with _random.traced_key_scope(key):
                with bind(model, params, buffers) as mutated_buffers:
                    for _, p in model.named_parameters():
                        p._grad_value = None
                    wbatch = tree_wrap(batch)
                    loss = loss_fn(model, *wbatch)
                    with _ag.enable_grad():
                        loss.backward()
                    pobjs = dict(model.named_parameters())
                    grads = {n: pobjs[n]._grad_value for n in trainable_names}
                # clip (outside bind: pure arrays now)
                if opt._grad_clip is not None:
                    class _P:  # lightweight stand-in carrying need_clip
                        __slots__ = ("need_clip",)
                        def __init__(self, nc):
                            self.need_clip = nc
                    pairs = [(_P(need_clip[n]), grads[n]) for n in trainable_names]
                    pairs = opt._grad_clip(pairs)
                    grads = {n: g for n, (_, g) in zip(trainable_names, pairs)}
                new_params = dict(params)
                new_state = {}
                saved_wd = opt._weight_decay
                for n in trainable_names:
                    g = grads[n]
                    if g is None:
                        new_state[n] = opt_state[n]
                        continue
                    opt._weight_decay = saved_wd if wd_on[n] else 0.0
                    nv, ns = opt._update(params[n], g, dict(opt_state[n]),
                                         lr * lr_mults[n], step_i)
                    new_params[n] = nv
                    new_state[n] = ns
                opt._weight_decay = saved_wd
                return tree_unwrap(loss), new_params, new_state, mutated_buffers

        return step

    def __call__(self, *batch):
        if self._jitted is None:
            self._build()
        self.guard.check(tree_unwrap(batch))
        opt = self.optimizer
        opt._step_count += 1
        params = param_arrays(self.model)
        opt_state = self._opt_state_tree()
        buffers = buffer_arrays(self.model)
        lr = opt.get_lr()
        key = _random.next_key()
        loss, new_params, new_state, new_buffers = self._jitted(
            params, opt_state, buffers, tree_unwrap(batch),
            jnp.asarray(lr, jnp.float32), jnp.asarray(opt._step_count, jnp.int32), key)
        self._write_back(new_params, new_state, new_buffers)
        return Tensor(loss)

    def multi_step(self, k: int):
        """Compile ``k`` optimizer steps into ONE dispatch.

        Returns a callable with the same batch signature as the step,
        except every batch array carries a leading ``k`` axis (one slice
        per inner step). One ``lax.scan`` with the (params, opt-state,
        buffers) carry donated — one host round-trip per k steps instead
        of per step, amortizing the per-dispatch host cost (rounds 2-5
        measured it on the then installation, BASELINE.md; not measured
        on the current one).

        The LR is sampled once per dispatch (an LRScheduler advances k
        counts but the k inner steps share one value); the returned loss
        is the LAST inner step's. Each inner step draws its own PRNG key,
        so dropout masks differ per step as in the sequential loop.
        """
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        # one compiled runner per k: calling multi_step(k) in a loop must
        # not re-jit the largest program in the module every iteration
        cache = self.__dict__.setdefault("_multi_step_cache", {})
        if k in cache:
            return cache[k]
        inner = self._make_step_fn()

        def multi(params, opt_state, buffers, batch, lr, step_i, keys):
            leaves, treedef = jax.tree_util.tree_flatten(batch)

            def body(carry, inp):
                p, o, b, si = carry
                step_batch = jax.tree_util.tree_unflatten(
                    treedef, inp[:-1])
                loss, p, o, b = inner(p, o, b, step_batch, lr, si,
                                      inp[-1])
                return (p, o, b, si + 1), loss

            (p, o, b, _), losses = jax.lax.scan(
                body, (params, opt_state, buffers, step_i),
                tuple(leaves) + (keys,))
            return losses[-1], p, o, b

        donate = (0, 1, 2) if self._donate else ()
        multi_jit = jax.jit(multi, donate_argnums=donate)
        opt = self.optimizer
        guard = CompileGuard(f"TrainStep.multi_step[{k}]")

        def run(*batch):
            vals = tree_unwrap(batch)
            for leaf in jax.tree_util.tree_leaves(vals):
                if jnp.ndim(leaf) == 0 or jnp.shape(leaf)[0] != k:
                    raise ValueError(
                        f"multi_step({k}) batch arrays need a leading "
                        f"{k} axis; got shape {jnp.shape(leaf)}")
            guard.check(vals)  # surface silent k-scan recompiles
            base_step = opt._step_count + 1
            opt._step_count += k
            params = param_arrays(self.model)
            opt_state = self._opt_state_tree()
            buffers = buffer_arrays(self.model)
            keys = jax.random.split(_random.next_key(), k)
            loss, new_params, new_state, new_buffers = multi_jit(
                params, opt_state, buffers, vals,
                jnp.asarray(opt.get_lr(), jnp.float32),
                jnp.asarray(base_step, jnp.int32), keys)
            self._write_back(new_params, new_state, new_buffers)
            return Tensor(loss)

        cache[k] = run
        return run


def not_to_static(fn):
    return fn


def enable_to_static(flag: bool):
    pass


from .save_load import save, load, TranslatedLayer  # noqa: E402,F401
from .bucketing import ShapeBucketer, pad_to_bucket, next_bucket  # noqa: E402,F401
from .dy2static import ConversionError, convert_control_flow  # noqa: E402,F401
from .fusion import (FusionCandidate, FusionPass, FusionPlan,  # noqa: E402,F401
                     FusionRegion, FusedOptimizerStep,
                     install_optimizer_fusion, stage_eager)
from .fusion import REGIONS as FUSION_REGIONS  # noqa: E402,F401
