"""Declarative op front-end — ``define_op`` (OCCA's host API, unified).

Every public kernel op (matmul, rmsnorm, ssm_scan, flash-attention, ...) is
ONE ``define_op`` declaration: a kernel-language builder, a pure oracle, and a
shape->defines derivation. The front-end owns everything the per-op host
wrappers used to duplicate —

  * backend selection   (``backend="auto"`` -> ``$REPRO_BACKEND`` if set,
                         else pallas; interpret off-TPU, via
                         :func:`repro.core.device.default_device`)
  * defines derivation  (``derive_defines`` with ``fit_block`` + degradation
                         guards, per call, cached by the Device kernel cache)
  * kernel build/cache  (``Device.build_kernel`` — OCCA's runtime compile)
  * custom-VJP wiring   (an :class:`OpVJP` declaration instead of per-op
                         ``jax.custom_vjp`` boilerplate)
  * autotuning          (``op.tune(args)`` sweeps the op's declared knobs,
                         validates against the oracle, persists winners)

and registers the op in a process-wide registry so tooling (tests, benchmark
harnesses, serving) can enumerate every op and its oracle.

    matmul = define_op(
        "matmul", builder=matmul_builder, ref=matmul_ref,
        derive_defines=_defines, sweep={"bm": [...], "bn": [...]}, ...)
    c = matmul(a, b)                      # pallas (interpret off-TPU)
    c = matmul(a, b, backend="loops")     # same kernel source, loops expansion
    best = matmul.tune((a, b))            # sweep, validate vs ref, cache
"""

from __future__ import annotations

import functools
import os
from typing import Callable, Mapping, Sequence

import jax

from . import tune as _tune
from .device import default_device
from .lang import BACKENDS

__all__ = ["Op", "OpShard", "OpVJP", "define_op", "get_op", "oracle_vjp",
           "registered_ops"]

_REGISTRY: dict[str, "Op"] = {}


def registered_ops() -> dict[str, "Op"]:
    """Snapshot of the process-wide op registry (name -> Op)."""
    return dict(_REGISTRY)


def get_op(name: str) -> "Op":
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no op named {name!r} registered; known: {sorted(_REGISTRY)}") from None


class OpVJP:
    """Custom-VJP declaration for a :func:`define_op` op.

    ``bwd(params, residuals, cotangent) -> per-primal-arg cotangents`` is the
    only required piece; ``params`` carries the resolved ``backend`` and
    ``interpret`` so a backward built from unified-language kernels runs on
    the same expansion as the forward. ``residuals(outs, args, params)``
    selects what the backward needs (default: the primal args); ``outs`` is
    the FULL kernel output tuple, so residual-only outputs (flash-attention's
    lse) are available even though callers never see them."""

    def __init__(self, bwd: Callable, residuals: Callable | None = None):
        self.bwd = bwd
        self.residuals = residuals or (lambda outs, args, params: args)


def oracle_vjp(ref_fn: Callable, *, params: Sequence[str] = ()) -> OpVJP:
    """An :class:`OpVJP` that differentiates the op's reference oracle.

    The forward runs the kernel; the backward is ``jax.vjp`` through
    ``ref_fn(*primals, **{k: params[k] for k in params})`` — correct whenever
    the kernel and the oracle compute the same function (which the test suite
    asserts), without writing a backward kernel."""

    def bwd(call_params, res, g):
        kw = {k: call_params[k] for k in params if k in call_params}
        _, pullback = jax.vjp(lambda *xs: ref_fn(*xs, **kw), *res)
        return pullback(g)

    return OpVJP(bwd=bwd)


class OpShard:
    """Executable mesh schedule for an op whose spec binds a ShardAxis.

    Where ``lang.ShardAxis`` is the spec-level DECLARATION (validated and
    cost-priced by the analyzer), ``OpShard`` is the op-level SCHEDULE:
    calling the op with ``mesh=`` wraps it in ``shard_map`` over these specs
    and drives the declared collective —

      ``"ppermute"``      a ring: ``step`` runs the per-chunk kernel on the
                          shard's current data, ``merge`` folds its partials
                          into the accumulator, and the ``rotate`` args hop to
                          the next shard between steps (``lax.ppermute``).
                          The whole ring is a static Python loop, so jax
                          autodiff transposes it for free (cotangents of the
                          rotated args ride the inverse ring home).
      ``"psum"`` /        one ``step`` per shard over its local slice, then an
      ``"psum_scatter"``  all-reduce (or reduce-scatter along
                          ``scatter_axis``) of the partials.

    ``in_specs(axis, args)`` / ``out_specs(axis)`` produce the shard_map
    partition specs. ``extent_param`` names an op param to set to the mesh
    axis size (so derived defines — and therefore the spec's ShardAxis extent
    and the tune-cache key — track the shard count). ``step`` defaults to the
    op's public call, which re-resolves ``backend=`` INSIDE shard_map: backend
    resolution is per-shard, not per-mesh.
    """

    def __init__(self, *, mesh_axis: str = "model",
                 collective: str = "ppermute", in_specs: Callable,
                 out_specs: Callable, rotate: Sequence[int] = (),
                 extent_param: str | None = None, scatter_axis: int = 0,
                 step: Callable | None = None, merge: Callable | None = None,
                 done: Callable | None = None):
        if collective not in ("ppermute", "psum", "psum_scatter"):
            raise ValueError(f"OpShard collective {collective!r} unknown")
        if collective == "ppermute" and (not rotate or merge is None):
            raise ValueError(
                "OpShard(collective='ppermute') needs rotate= arg indices "
                "and a merge= hook — a ring with nothing rotating or no way "
                "to fold partials cannot reduce across shards")
        self.mesh_axis = mesh_axis
        self.collective = collective
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.rotate = tuple(int(i) for i in rotate)
        self.extent_param = extent_param
        self.scatter_axis = int(scatter_axis)
        self.step = step or (
            lambda op, args, params, *, t, n, axis: op(*args, **params))
        self.merge = merge
        self.done = done


def _freeze(params: Mapping) -> tuple:
    return tuple(sorted(params.items()))


def _thaw(frozen: tuple) -> dict:
    return dict(frozen)


class Op:
    """A declared op: the public callable returned by :func:`define_op`.

    Hook signatures (all take/return plain tuples + a mutable params dict):

      early(args, params)        -> result or None   shape short-circuits
      pre(args, params)          -> kernel args      host-side arg prep
                                                     (may pop params it eats)
      derive_defines(args, params) -> defines dict   shapes -> addDefine set
      post(outs, args, params)   -> public result    host-side output shaping
                                                     (default: single output
                                                     unwrapped, tuple kept)

    ``args`` for ``post``/``OpVJP`` hooks are the ORIGINAL call args (pre is
    kernel-facing only). ``public_outputs`` exposes just the first n kernel
    outputs (the rest are residual-only, e.g. softmax stats)."""

    def __init__(self, name, builder, ref, derive_defines, *, vjp=None,
                 sweep=None, defaults=None, public_outputs=None,
                 early=None, pre=None, post=None, ref_params=(),
                 tune_ref=None, example=None, doc=None, array_params=(),
                 analyze=None, shard=None):
        self.name = name
        self.builder = builder
        self.ref = ref
        self.derive_defines = derive_defines
        self.vjp = vjp
        self.sweep = dict(sweep or {})
        self.defaults = dict(defaults or {})
        self.array_params = tuple(array_params)
        self.public_outputs = public_outputs
        self.ref_params = tuple(ref_params)
        self.tune_ref = tune_ref
        self.example = example
        # per-op static-analysis strictness override (None = the process
        # mode: $REPRO_ANALYZE / analyze.set_analysis_mode)
        self.analyze = analyze
        # declared mesh schedule (OpShard) behind the mesh= call param
        self.shard = shard
        self._early = early
        self._pre = pre
        self._post = post
        self.__doc__ = doc or (ref.__doc__ if ref is not None else None)
        self.__name__ = name
        if vjp is not None:
            self._core = self._build_vjp_core()

    # -- call plumbing -------------------------------------------------------
    def _resolve(self, kw: Mapping) -> tuple[str, bool | None, dict]:
        unknown = (set(kw) - set(self.defaults) - set(self.array_params)
                   - {"backend", "interpret"})
        if unknown:
            raise TypeError(
                f"op {self.name!r} got unexpected params {sorted(unknown)}; "
                f"known: {sorted(set(self.defaults) | set(self.array_params))} "
                "(+ backend, interpret)")
        params = dict(self.defaults)
        params.update(dict.fromkeys(self.array_params))
        params.update(kw)
        backend = params.pop("backend", "auto")
        interpret = params.pop("interpret", None)
        if backend == "auto":
            # REPRO_BACKEND pins what "auto" means process-wide — the CI
            # backend-matrix re-runs the cross-backend suites under jnp and
            # loops so a pallas-only regression can't hide behind the default
            backend = os.environ.get("REPRO_BACKEND", "pallas")
            if backend not in BACKENDS:
                raise ValueError(
                    f"REPRO_BACKEND={backend!r} is not a backend; expected "
                    f"one of {BACKENDS}")
        return backend, interpret, params

    def _prepare(self, args, params) -> tuple[tuple, dict, dict]:
        """The shared call prologue: pre-hook (may eat params) + shape->defines
        derivation. Returns (kernel args, defines, post-pre params)."""
        params = dict(params)
        if self._pre is not None:
            args = tuple(self._pre(tuple(args), params))
        return tuple(args), self.derive_defines(tuple(args), params), params

    def _run_kernel(self, args, backend, interpret, params) -> tuple:
        """prepare -> build (Device kernel cache) -> run; ALL kernel outputs."""
        args, defines, _ = self._prepare(args, params)
        kern = default_device(backend, interpret).build_kernel(
            self.builder, defines, analyze=self.analyze)
        return kern.run(*args)

    def _publish(self, outs, args, params):
        pub = outs if self.public_outputs is None else outs[: self.public_outputs]
        if self._post is not None:
            return self._post(pub, args, params)
        return pub[0] if len(pub) == 1 else pub

    def _primal(self, args, backend, interpret, params):
        outs = self._run_kernel(args, backend, interpret, params)
        return self._publish(outs, args, params), outs

    def _build_vjp_core(self):
        vjp = self.vjp

        @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
        def core(frozen, *args):
            backend, interpret, params = self._resolve(_thaw(frozen))
            return self._primal(args, backend, interpret, params)[0]

        def core_fwd(frozen, *args):
            backend, interpret, params = self._resolve(_thaw(frozen))
            result, outs = self._primal(args, backend, interpret, params)
            return result, vjp.residuals(outs, args, params)

        def core_bwd(frozen, res, g):
            # the bwd hook sees the resolved backend/interpret so a declared
            # backward KERNEL runs on the same expansion as the forward —
            # grads are backend-portable, not pallas-only
            backend, interpret, params = self._resolve(_thaw(frozen))
            params["interpret"] = interpret
            params["backend"] = backend
            return tuple(vjp.bwd(params, res, g))

        core.defvjp(core_fwd, core_bwd)
        return core

    def _shard_call(self, mesh, args, kw):
        """Run under ``shard_map`` per the declared :class:`OpShard` schedule.

        The collective loop is traced ONCE for all shards (shard_map's SPMD
        contract), so per-shard positions must come from ``lax.axis_index``
        inside the step hook, never from Python. ``check_vma=False``: the ring
        writes sharded outputs through explicit collectives the varying-
        manual-axes checker cannot see."""
        from jax import lax  # deferred: op.py stays import-light

        sh = self.shard
        if sh is None:
            raise ValueError(
                f"op {self.name!r} declares no mesh schedule (OpShard); "
                "mesh= is not supported here")
        ax = sh.mesh_axis
        if ax not in dict(getattr(mesh, "shape", {})):
            raise ValueError(
                f"op {self.name!r}: mesh has no axis {ax!r} "
                f"(axes: {tuple(getattr(mesh, 'shape', {}))})")
        n = int(mesh.shape[ax])
        params = dict(kw)
        if sh.extent_param:
            params.setdefault(sh.extent_param, n)

        def local(*largs):
            if sh.collective == "ppermute":
                # ring: at step t, shard i holds chunk (i + t) % n of every
                # rotated arg; a backward pass through this loop transposes
                # each ppermute, carrying dk/dv-style cotangents home
                perm = [(j, (j - 1) % n) for j in range(n)]
                cur = list(largs)
                acc = None
                for t in range(n):
                    part = sh.step(self, tuple(cur), dict(params),
                                   t=t, n=n, axis=ax)
                    acc = part if acc is None else sh.merge(acc, part)
                    if t + 1 < n:
                        for i in sh.rotate:
                            cur[i] = lax.ppermute(cur[i], ax, perm)
                return sh.done(acc) if sh.done is not None else acc
            part = sh.step(self, largs, dict(params), t=0, n=n, axis=ax)
            if sh.collective == "psum":
                return jax.tree.map(lambda x: lax.psum(x, ax), part)
            return jax.tree.map(
                lambda x: lax.psum_scatter(
                    x, ax, scatter_dimension=sh.scatter_axis, tiled=True),
                part)

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=tuple(sh.in_specs(ax, args)),
                           out_specs=sh.out_specs(ax), check_vma=False)
        return fn(*args)

    def __call__(self, *args, **kw):
        mesh = kw.pop("mesh", None)
        if mesh is not None:
            return self._shard_call(mesh, args, kw)
        backend, interpret, params = self._resolve(kw)
        if self._early is not None:
            got = self._early(args, dict(params))
            if got is not None:
                return got
        if self.vjp is not None:
            # array-valued params cannot thread through custom_vjp's static
            # (nondiff) param tuple — reject loudly rather than freeze a
            # tracer or silently drop the value from the backward pass
            live = [n for n in self.array_params if params.get(n) is not None]
            if live:
                raise ValueError(
                    f"op {self.name!r}: params {live} take arrays and are not "
                    "differentiable through the public op; use the functional "
                    f"entry point ({self.name}.raw / its wrapper) instead")
            for n in self.array_params:
                params.pop(n, None)
            return self._core(
                _freeze(dict(params, backend=backend, interpret=interpret)),
                *args)
        return self._primal(args, backend, interpret, params)[0]

    def raw(self, *args, **kw):
        """Run the kernel and return ALL its outputs (no VJP, no post/early):
        the functional entry point for tests and composition."""
        backend, interpret, params = self._resolve(kw)
        return self._run_kernel(args, backend, interpret, params)

    # -- oracle access -------------------------------------------------------
    def reference(self, *args, **kw):
        """The op's oracle at public-call granularity (backend-independent)."""
        _, _, params = self._resolve(kw)
        return self.ref(*args, **{k: params[k] for k in self.ref_params
                                  if k in params})

    # -- autotuning ----------------------------------------------------------
    def tune(self, args, *, sweep=None, cache=True, warmup=1, repeats=3,
             validate=True, prune=True, **kw):
        """Sweep this op's tuning knobs on real args; returns the winning
        defines (a :class:`repro.core.tune.TuneResult`).

        Sweeps are over DEFINES keys (the builder's addDefine surface).
        Candidates validate against the op's oracle — not against each other.
        ``prune=True`` (default) lets the static cost model reject
        VMEM-overflow and strictly-dominated candidates before they are
        built or timed (reasons in ``result.pruned``). Winners persist under
        ``$REPRO_CACHE_DIR`` (``cache=False`` opts out): a warm cache
        performs zero builds and zero timed sweeps."""
        backend, interpret, params = self._resolve(kw)
        run_args, defines, params = self._prepare(args, params)
        sweep = dict(self.sweep if sweep is None else sweep)
        if not sweep:
            raise ValueError(f"op {self.name!r} declares no tuning sweep")
        # lazy: autotune evaluates the oracle only after a cache miss, so a
        # warm cache pays neither sweep timings nor the reference forward
        ref = None
        if validate:
            tref = self.tune_ref
            if tref is not None:
                ref = lambda *a: tref(run_args, params)  # noqa: E731
            elif self.ref is not None:
                kwf = {k: params[k] for k in self.ref_params if k in params}
                ref = lambda *a: self.ref(*a, **kwf)  # noqa: E731
        return _tune.autotune(
            default_device(backend, interpret), self.builder, defines,
            sweep=sweep, args=run_args, warmup=warmup, repeats=repeats,
            validate=validate, ref=ref, cache=cache, name=self.name,
            prune=prune)

    def cached_winner(self, args, *, sweep=None, **kw):
        """The persisted ``op.tune`` winner for these args, or None — a PURE
        cache lookup: no kernel builds, no timed sweeps, no oracle. This is
        how serving warmup adopts tuned block sizes (``$REPRO_CACHE_DIR``)
        instead of hardcoded defaults."""
        backend, interpret, params = self._resolve(kw)
        _, defines, _ = self._prepare(args, params)
        sweep = dict(self.sweep if sweep is None else sweep)
        if not sweep:
            return None
        dev = default_device(backend, interpret)
        return _tune.cached_winner(self.name, defines, sweep, dev.backend,
                                   dev.interpret)

    def __repr__(self):
        return (f"Op({self.name!r}, params={sorted(self.defaults)}, "
                f"sweep={sorted(self.sweep)}, vjp={self.vjp is not None})")


def define_op(name: str, *, builder: Callable, ref: Callable | None,
              derive_defines: Callable, vjp: OpVJP | None = None,
              sweep: Mapping | None = None, defaults: Mapping | None = None,
              public_outputs: int | None = None, early: Callable | None = None,
              pre: Callable | None = None, post: Callable | None = None,
              ref_params: Sequence[str] = (), tune_ref: Callable | None = None,
              example: Callable | None = None, doc: str | None = None,
              array_params: Sequence[str] = (), register: bool = True,
              analyze: str | None = None,
              shard: OpShard | None = None) -> Op:
    """Declare a public op over the unified kernel language; see :class:`Op`.

    ``example(rng) -> (args, params)`` supplies representative inputs so the
    registry-wide portability test can sweep every op across all backends
    against its ``ref`` without op-specific test code. ``array_params`` names
    params that may hold arrays (e.g. a carried state ``h0``): they are legal
    on the functional ``op.raw``/``op.tune`` paths but rejected on the
    differentiable call (arrays cannot be static custom_vjp params)."""
    op = Op(name, builder, ref, derive_defines, vjp=vjp, sweep=sweep,
            defaults=defaults, public_outputs=public_outputs, early=early,
            pre=pre, post=post, ref_params=ref_params, tune_ref=tune_ref,
            example=example, doc=doc, array_params=array_params,
            analyze=analyze, shard=shard)
    if register:
        # silent overwrites are the same collision class the PR-1 kernel-cache
        # fix eliminated: callers holding the first Op would diverge from the
        # registry with no error
        if name in _REGISTRY:
            raise ValueError(
                f"an op named {name!r} is already registered; pick a unique "
                "name or pass register=False to keep it out of the registry")
        _REGISTRY[name] = op
    return op
