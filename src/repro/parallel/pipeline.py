"""Pipeline parallelism (GPipe) over a "pipe" mesh axis.

Stage weights live stage-sharded (leading dim = S over the pipe axis); M
microbatches stream through S stages with ``ppermute`` handoffs. The
schedule runs T = M + S - 1 ticks (bubble fraction (S-1)/T) inside a
``lax.scan``, so the whole pipeline is reverse-differentiable — backward
replays the schedule with reversed permutes (GPipe semantics, activations
rematerialized by the scan).

    y_mb = pipeline_apply(stage_fn, stage_params, x_mb, mesh=mesh)

``stage_fn(params_i, x) -> y`` must preserve x's shape/dtype (a residual
stack). Combine with DP/TP by adding the pipe axis to the mesh; stage
params specs get P("pipe", ...) prepended (see ``stage_param_specs``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "split_stages", "stage_param_specs"]


def split_stages(stacked_params, n_stages: int):
    """(L, ...) layer-stacked params -> (S, L/S, ...) stage-stacked."""
    def resh(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])
    return jax.tree.map(resh, stacked_params)


def stage_param_specs(pspecs, axis: str = "pipe"):
    """Prepend the pipe axis to every stage-stacked param spec."""
    return jax.tree.map(lambda s: P(axis, *s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def pipeline_apply(stage_fn, stage_params, x_microbatches, *, mesh: Mesh,
                   axis: str = "pipe"):
    """Run (M, mb, ...) microbatches through S pipeline stages.

    stage_params: pytree with leading dim S, sharded P(axis, ...).
    Returns (M, mb, ...) outputs of the final stage (replicated over axis).
    """
    n_stages = mesh.shape[axis]
    m_micro = x_microbatches.shape[0]
    ticks = m_micro + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def run(params_local, xs):
        # params_local: (1, ...) slice on this stage; xs: full (M, mb, ...)
        params_i = jax.tree.map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(xs[0])

        def tick(buf, t):
            # stage 0 ingests microbatch t (clamped; masked past the end)
            feed = xs[jnp.clip(t, 0, m_micro - 1)]
            feed = jnp.where(t < m_micro, feed, zero)
            x_in = jnp.where(idx == 0, feed, buf)
            y = stage_fn(params_i, x_in)
            buf_next = jax.lax.ppermute(y, axis, perm)
            return buf_next, y

        _, ys = jax.lax.scan(tick, zero, jnp.arange(ticks))
        # the final stage emitted microbatch m at tick m + S - 1
        outs = ys[n_stages - 1:]
        # replicate the last stage's outputs to every pipe rank
        outs = jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    pipe_spec = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(pipe_spec, P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x_microbatches)
