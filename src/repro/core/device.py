"""occa::device analogue — run-time backend selection + kernel build cache.

``Device("pallas")`` on a TPU host compiles real Pallas kernels; on this CPU
container it transparently selects ``interpret=True`` (the kernel *language*
is identical — that is the portability contract). ``build_kernel`` performs
the paper's run-time compilation: the builder is invoked with the injected
``defines`` (addDefine analogue), expanded for the device's backend, jitted,
and cached keyed by (builder *identity*, defines, backend) — OCCA's kernel
cache. Identity matters: two closures produced by the same factory share a
``__qualname__`` but are different kernels, so the cache is keyed on the
function object itself (weakly, where possible) rather than its name.
"""

from __future__ import annotations

import dataclasses
import threading
import weakref
from typing import Callable

import jax

from . import analyze as _analyze
from . import lang
from .kernel import Kernel
from .memory import Memory

__all__ = ["Device", "BuildStats", "default_device", "fit_block"]


@dataclasses.dataclass
class BuildStats:
    builds: int = 0
    cache_hits: int = 0


def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under the kernel's name, so its jit is ``jit_<name>`` in
    traces and compile logs rather than the expansion's ``jit_fn``."""
    def kernel(*args):
        return fn(*args)

    kernel.__name__ = kernel.__qualname__ = name
    return kernel


class Device:
    """A compute backend with its own kernel build cache."""

    BACKENDS = lang.BACKENDS

    def __init__(self, backend: str = "jnp", *, interpret: bool | None = None):
        if backend not in self.BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected one of {self.BACKENDS}")
        self.backend = backend
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = bool(interpret)
        # id(builder anchor) -> (ref-or-strong-anchor, {key: Kernel}). Keyed by
        # object IDENTITY (never __eq__/__hash__: two equal-but-distinct
        # instances must not share kernels). Weakly-referenced anchors are
        # evicted by a finalizer so caching never pins short-lived closures;
        # non-weakrefable anchors are held strongly (keeping the id valid)
        # with bounded FIFO eviction.
        self._cache: dict = {}
        self._strong_keys: list = []
        self._lock = threading.Lock()
        self.stats = BuildStats()

    # -- memory ---------------------------------------------------------------
    def malloc(self, array_or_shape, dtype=None) -> Memory:
        import jax.numpy as jnp

        if isinstance(array_or_shape, (tuple, list)) or isinstance(array_or_shape, int):
            shape = (array_or_shape,) if isinstance(array_or_shape, int) else tuple(array_or_shape)
            array = jnp.zeros(shape, dtype or jnp.float32)
        else:
            array = jnp.asarray(array_or_shape, dtype)  # dtype=None keeps as-is
        return Memory(self, array)

    _STRONG_CACHE_MAX = 64

    @staticmethod
    def _evict_entry(cache, key, ref):
        ent = cache.get(key)
        if ent is not None and ent[0] is ref:  # don't drop a reused-id entry
            cache.pop(key, None)

    def _builder_cache(self, builder) -> dict:
        """Per-builder kernel sub-cache, keyed on object identity.

        Bound methods are a fresh object per attribute access, so they are
        unwrapped and anchored on the *instance* (with the underlying function
        in the subkey) — ``dev.build_kernel(obj.builder, ...)`` in a loop hits
        the cache. Plain closures recreated per call inherently cannot: hold
        onto the builder object to reuse its cache."""
        anchor, fn = builder, None
        if getattr(builder, "__func__", None) is not None \
                and getattr(builder, "__self__", None) is not None:
            anchor, fn = builder.__self__, builder.__func__
        key = id(anchor)
        ent = self._cache.get(key)
        if ent is not None:
            ref, sub = ent
            live = ref() if isinstance(ref, weakref.ref) else ref
            if live is not anchor:  # stale id reuse: rebuild the entry
                ent = None
        if ent is None:
            sub = {}
            try:
                ref = weakref.ref(anchor)
                self._cache[key] = (ref, sub)
                weakref.finalize(anchor, self._evict_entry, self._cache, key, ref)
            except TypeError:  # anchor not weakref-able: hold it strongly
                self._cache[key] = (anchor, sub)
                self._strong_keys.append(key)
                while len(self._strong_keys) > self._STRONG_CACHE_MAX:
                    # bounded: evict oldest so strong refs can't pile up forever
                    self._cache.pop(self._strong_keys.pop(0), None)
        if fn is None:
            return sub
        per_fn = sub.get(fn)
        if per_fn is None:
            per_fn = sub[fn] = {}
        return per_fn

    # -- run-time kernel compilation -------------------------------------------
    def build_kernel(self, builder: Callable, defines: dict | None = None, *,
                     analyze: str | None = None) -> Kernel:
        defines = dict(defines or {})
        # backend/interpret are set in __init__ but are public attributes: keep
        # them in the key so mutating them can't serve stale kernels.
        key = (_freeze(defines), self.backend, self.interpret)
        with self._lock:
            hit = self._builder_cache(builder).get(key)
            if hit is not None:
                self.stats.cache_hits += 1
                return hit

        D = lang.defines_namespace(defines)
        spec = builder(D)
        if not isinstance(spec, lang.Spec):
            raise TypeError(f"builder {builder!r} must return lang.Spec, got {type(spec)}")
        # the static analyzer gates every cache-miss build (grid invariants
        # already ran in Spec.__post_init__; this adds the body-trace
        # liveness/coverage pass). ``analyze`` overrides the process mode
        # per build ($REPRO_ANALYZE / set_analysis_mode; "off" skips).
        _analyze.check_built_spec(spec, D, mode=analyze)
        fn = lang.expand(spec, D, self.backend, interpret=self.interpret)
        kern = Kernel(self, spec, jax.jit(_named(fn, spec.name)), defines)

        with self._lock:
            self._builder_cache(builder)[key] = kern
            self.stats.builds += 1
        return kern

    def synchronize(self) -> None:
        # jax dispatch is async; nothing to do beyond letting callers
        # block on results (block_until_ready on Memory.data).
        pass

    def __repr__(self):
        return f"Device(backend={self.backend!r}, interpret={self.interpret})"


_DEFAULT_DEVICES: dict = {}
_DEFAULT_DEVICES_LOCK = threading.Lock()


def default_device(backend: str, interpret: bool | None = None) -> Device:
    """Process-wide Device per (backend, interpret), so ops that build kernels
    on the fly (matmul, rmsnorm, …) share one kernel cache instead of one per
    module. ``interpret=None`` lets the Device pick (interpret off-TPU)."""
    with _DEFAULT_DEVICES_LOCK:
        key = (backend, interpret)
        dev = _DEFAULT_DEVICES.get(key)
        if dev is None:
            dev = _DEFAULT_DEVICES[key] = Device(backend, interpret=interpret)
        return dev


def fit_block(block: int, n: int, multiple: int = 1) -> int:
    """Largest divisor of ``n`` that is <= ``block`` (blocks must tile exactly).
    With ``multiple``, the largest such divisor that is also a multiple of it
    when one exists (a TPU lane axis wants 128), else the plain fit."""
    if n <= 0:
        raise ValueError(f"fit_block: cannot tile a dimension of size {n}")
    if block <= 0:
        raise ValueError(f"fit_block: block must be positive, got {block}")
    block = min(int(block), int(n))
    if multiple > 1 and block < n:
        b = block - block % multiple
        while b >= multiple:
            if n % b == 0:
                return b
            b -= multiple
    while n % block:
        block -= 1
    return block
