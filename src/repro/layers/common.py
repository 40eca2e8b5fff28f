"""Shared layer utilities: init, norms, kernel-backend selection."""

from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

from repro.kernels.rmsnorm import rmsnorm as rmsnorm_pallas_op
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.parallel.context import shard_kernel

__all__ = [
    "dense_init", "rms_init", "rmsnorm", "kernel_backend", "use_kernel_backend",
    "silu", "softplus",
]

# Which realization the perf-critical ops use: "pallas" (the kernels) on a
# TPU, "jnp" (the XLA references) elsewhere. ``use_kernel_backend`` pins
# one for a scope (tests compare the two; pallas runs interpreted off-TPU).
_kernel_backend: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "repro_kernel_backend", default=None)

# ops the TPU compiler refuses: on a TPU their layers keep the XLA reference
# unless a scope pins "pallas" (ssm_scan: no dynamic_slice lowering)
TPU_REFUSED = frozenset({"ssm_scan"})


def kernel_backend(op: str | None = None) -> str:
    """The realization for ``op`` (None: any op not in ``TPU_REFUSED``)."""
    pinned = _kernel_backend.get()
    if pinned is not None:
        return pinned
    if jax.default_backend() == "tpu" and op not in TPU_REFUSED:
        return "pallas"
    return "jnp"


@contextlib.contextmanager
def use_kernel_backend(name: str):
    if name not in ("jnp", "pallas"):
        raise ValueError(f"kernel backend must be jnp|pallas, got {name!r}")
    tok = _kernel_backend.set(name)
    try:
        yield
    finally:
        _kernel_backend.reset(tok)


def dense_init(rng, shape, dtype, *, scale: float | None = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(rng, -3, 3, shape, jnp.float32)
            * std).astype(dtype)


def rms_init(shape):
    return jnp.ones(shape, jnp.float32)


def rmsnorm(x, w, *, eps=1e-6):
    if kernel_backend() == "pallas":
        rows = ("batch",) + (None,) * (x.ndim - 1)
        return shard_kernel(lambda x, w: rmsnorm_pallas_op(x, w, eps=eps),
                            (x, w), (rows, (None,)), rows)
    return rmsnorm_ref(x, w, eps=eps)


def silu(x):
    return x * jax.nn.sigmoid(x)


def softplus(x):
    return jax.nn.softplus(x)
