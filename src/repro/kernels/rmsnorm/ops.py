"""Public RMSNorm op — a single ``define_op`` declaration (oracle VJP)."""

from __future__ import annotations

import math

import jax.numpy as jnp

from repro.core import define_op, fit_block, oracle_vjp
from .kernel import rmsnorm_builder
from .ref import rmsnorm_ref

__all__ = ["rmsnorm", "rmsnorm_unified", "rmsnorm_pallas"]


def _early(args, params):
    x, w = args
    if x.size == 0:
        return jnp.asarray(x)  # empty input: nothing to normalize
    return None


def _pre(args, params):
    # rows pad up to a block multiple (sliced off in _post) instead of letting
    # fit_block degrade to an odd divisor: a TPU row block must be a multiple
    # of 8 or span every row
    x, w = args
    d = x.shape[-1]
    rows = math.prod(x.shape[:-1])
    pad = (-rows) % min(int(params["block_rows"]), rows)
    x2 = x.reshape(rows, d)
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, w


def _defines(args, params):
    x2, w = args
    rows, d = x2.shape
    return dict(rows=rows, d=d,
                block_rows=fit_block(params["block_rows"], rows),
                eps=float(params["eps"]),
                dtype=jnp.dtype(x2.dtype).name,
                wdtype=jnp.dtype(w.dtype).name)


def _post(outs, args, params):
    x = args[0]
    return outs[0][:math.prod(x.shape[:-1])].reshape(x.shape)


def _example(rng):
    x = rng.randn(3, 20, 64).astype("float32")
    w = rng.randn(64).astype("float32")
    return (x, w), dict(block_rows=16)


rmsnorm = define_op(
    "rmsnorm",
    builder=rmsnorm_builder,
    ref=rmsnorm_ref,
    derive_defines=_defines,
    early=_early,
    pre=_pre,
    post=_post,
    vjp=oracle_vjp(rmsnorm_ref, params=("eps",)),
    defaults=dict(eps=1e-6, block_rows=256),
    ref_params=("eps",),
    sweep=dict(block_rows=[32, 64, 128, 256, 512]),
    example=_example,
    doc="""x: (..., D); w: (D,). Normalizes the last axis on any backend.

    Differentiable (oracle VJP through ``rmsnorm_ref``); the forward is the
    unified-language kernel on the selected backend.""",
)


# -- backward-compatible names ------------------------------------------------

def rmsnorm_unified(x, w, *, eps=1e-6, block_rows=256, backend="pallas",
                    interpret=None):
    """Thin alias over the op (historic name for the unified expansion)."""
    return rmsnorm(x, w, eps=eps, block_rows=block_rows, backend=backend,
                   interpret=interpret)


def rmsnorm_pallas(x, w, *, eps=1e-6, block_rows=256, interpret=True):
    """Backward-compatible name for the pallas expansion (interpret honored)."""
    return rmsnorm(x, w, eps=eps, block_rows=block_rows, backend="pallas",
                   interpret=interpret)
