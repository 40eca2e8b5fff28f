"""Sequence-parallel RING flash attention — the kernel language on a mesh.

One ``define_op`` declaration (``ring_flash``) is three things at once:

* a single-device kernel — one ring STEP: flash attention of a query shard
  against one kv chunk at dynamic absolute offsets (``q_start``/``k_start``
  input tiles), emitting the chunk-local ``(o, lse)``;
* a declared schedule — the spec binds its kv reduce axis to a mesh axis
  (``lang.ShardAxis``: ``ppermute`` ring, k/v rotating), which the analyzer
  validates over the mesh-extended grid and the cost model prices in
  interconnect bytes;
* a distributed op — calling it with ``mesh=`` wraps the step in
  ``shard_map`` (``core.op.OpShard``): a static Python ring loop runs the
  step per chunk, merges partials with the exact logsumexp reweighting, and
  ``lax.ppermute``-rotates k/v between steps.

The backward needs no ring-specific plumbing: each step is a
``jax.custom_vjp`` (``_ring_step``) whose backward feeds the step's own lse
and the lse-cotangent-adjusted delta into ``ring_flash_bwd_builder``; jax
then transposes the ring loop itself — every ``ppermute`` becomes its
inverse, carrying the dk/dv cotangents back around the ring to their owner.

``ring_flash_attention`` is the public wrapper: with ``mesh=`` it runs the
distributed ring; without, it runs the SAME per-step kernel + merge over
locally-split chunks (``ring_steps=``) — a bit-comparable single-process
reference, which is also how CPU CI proves the schedule correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec

from repro.core import OpShard, default_device, define_op, fit_block
from .kernel import (_mask_block, flash_delta_builder, ring_flash_bwd_builder,
                     ring_flash_fwd_builder)

__all__ = ["ring_flash", "ring_flash_attention", "ring_merge",
           "ring_step_ref"]

_NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# defines / hooks for the registered per-step op
# ---------------------------------------------------------------------------

def _ring_pre(args, params):
    # read-only on params (.get, never .pop) — same contract as flash_decode
    q, k, v = args
    q_start = params.get("q_start")
    if q_start is None:
        q_start = 0
    q_start = jnp.asarray(q_start, jnp.int32).reshape(1, 1)
    k_start = params.get("k_start")
    if k_start is None:
        k_start = 0
    k_start = jnp.asarray(k_start, jnp.int32).reshape(1, 1)
    return q, k, v, q_start, k_start


def _ring_defines(args, params):
    q, k, v = args[:3]
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    dv = v.shape[-1]
    if h % hk:
        raise ValueError(f"ring_flash: {h} query heads not a multiple of "
                         f"{hk} kv heads")
    if q.dtype != k.dtype or q.dtype != v.dtype:
        raise ValueError(f"ring_flash: dtypes disagree "
                         f"({q.dtype}/{k.dtype}/{v.dtype})")
    block_q, block_kv = params["block_q"], params["block_kv"]
    bq, bkv = fit_block(block_q, sq), fit_block(block_kv, skv)
    ncells = b * h * (sq // bq) * (skv // bkv)
    degraded = bq < min(block_q, sq) or bkv < min(block_kv, skv)
    if degraded and ncells > 1 << 16:
        raise ValueError(
            f"ring_flash: shard seq lens ({sq}, {skv}) degraded blocks to "
            f"({bq}, {bkv}) = {ncells} grid cells; pad the shards or pass "
            "block sizes that divide them")
    sm_scale = params["sm_scale"]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    window = params["window"]
    return dict(
        b=b, h=h, hk=hk, sq=sq, skv=skv, d=d, dv=dv,
        block_q=bq, block_kv=bkv,
        causal=bool(params["causal"]),
        window=None if window is None else int(window),
        prefix_len=int(params["prefix_len"]),
        sm_scale=float(sm_scale),
        ring_steps=int(params["ring_steps"]),
        mesh_axis=str(params["mesh_axis"]),
        dtype=jnp.dtype(q.dtype).name)


def ring_step_ref(q, k, v, *, q_start=None, k_start=None, causal=True,
                  window=None, sm_scale=None, prefix_len=0):
    """Dense oracle for ONE ring step: masked softmax attention of q (absolute
    positions ``q_start + i``) against one kv chunk (positions
    ``k_start + j``). Fully-masked rows return 0 (the merge identity)."""
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    g = h // hk
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    q0 = 0 if q_start is None else jnp.asarray(q_start, jnp.int32).reshape(())
    k0 = 0 if k_start is None else jnp.asarray(k_start, jnp.int32).reshape(())
    kf = jnp.repeat(k, g, axis=1) if g > 1 else k
    vf = jnp.repeat(v, g, axis=1) if g > 1 else v
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) * sm_scale
    q_pos = q0 + jnp.arange(sq, dtype=jnp.int32)
    k_pos = k0 + jnp.arange(skv, dtype=jnp.int32)
    mask = _mask_block(q_pos, k_pos, causal=causal, window=window,
                       prefix_len=prefix_len)[None, None]
    s = jnp.where(mask, s, _NEG_INF)
    m = s.max(-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - jnp.where(m == _NEG_INF, 0.0, m)), 0.0)
    l = p.sum(-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkv->bhqv", p, vf.astype(jnp.float32))
    return (o / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)


def _ring_tune_ref(args, params):
    q, k, v, qs, ks = args
    kw = {n: params[n] for n in ("causal", "window", "sm_scale", "prefix_len")}
    return ring_step_ref(q, k, v, q_start=qs, k_start=ks, **kw)


def _ring_example(rng):
    q = rng.randn(1, 4, 64, 32).astype("float32")
    k = rng.randn(1, 2, 64, 32).astype("float32")
    v = rng.randn(1, 2, 64, 32).astype("float32")
    # ring_steps=4: the linted/benchmarked default config is MESH-BOUND (the
    # spec carries an active ShardAxis), so the analyzer's cross-shard checks
    # and the cost model's comm column run in CI, not just under a mesh
    return (q, k, v), dict(causal=True, block_q=32, block_kv=32, ring_steps=4)


# ---------------------------------------------------------------------------
# the exact step merge + the differentiable per-step call
# ---------------------------------------------------------------------------

def ring_merge(a, b):
    """Exactly merge two chunk-local softmax partials ``(o, lse)``.

    Standard flash/logsumexp reweighting, guarded (double-``where``) so
    fully-masked partials (``lse = -inf``) contribute an exact 0 with clean
    gradients — no ``-inf - -inf`` NaNs forward or backward."""
    o_a, lse_a = a
    o_b, lse_b = b
    m = jnp.maximum(lse_a, lse_b)
    m_s = jnp.where(m == _NEG_INF, 0.0, m)
    ea = jnp.where(lse_a == _NEG_INF, 0.0,
                   jnp.exp(jnp.where(lse_a == _NEG_INF, 0.0, lse_a - m_s)))
    eb = jnp.where(lse_b == _NEG_INF, 0.0,
                   jnp.exp(jnp.where(lse_b == _NEG_INF, 0.0, lse_b - m_s)))
    tot = ea + eb
    den = jnp.where(tot == 0.0, 1.0, tot)
    o = (o_a.astype(jnp.float32) * (ea / den)[..., None] +
         o_b.astype(jnp.float32) * (eb / den)[..., None]).astype(o_a.dtype)
    lse = jnp.where(tot == 0.0, _NEG_INF, m_s + jnp.log(den))
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_step(frozen, q, k, v, q_start, k_start):
    """One differentiable ring step: ``(o, lse)`` at the given offsets.

    ``frozen`` is the sorted-items tuple of the op params (hashable). Runs
    inside ``shard_map`` — the raw op call underneath re-resolves backend=
    per shard."""
    o, lse = ring_flash.raw(q, k, v, q_start=q_start, k_start=k_start,
                            **dict(frozen))
    return o, lse[..., 0]


def _ring_step_fwd(frozen, q, k, v, q_start, k_start):
    o, lse = ring_flash.raw(q, k, v, q_start=q_start, k_start=k_start,
                            **dict(frozen))
    return (o, lse[..., 0]), (q, k, v, q_start, k_start, o, lse)


def _ring_step_bwd(frozen, res, g):
    q, k, v, q_start, k_start, o, lse = res
    g_o, g_lse = g
    backend, interpret, params = ring_flash._resolve(dict(frozen))
    D = _ring_defines((q, k, v), params)
    b, h, hk = D["b"], D["h"], D["hk"]
    skv, d, dv = D["skv"], D["d"], D["dv"]
    grp = h // hk
    dev = default_device(backend, interpret)
    do = g_o.astype(q.dtype)

    delta_kern = dev.build_kernel(flash_delta_builder, dict(
        b=b, h=h, sq=D["sq"], dv=dv, block_q=D["block_q"], dtype=D["dtype"]))
    delta, = delta_kern.run(do, o.astype(q.dtype))
    # lse is a PUBLIC output of the step (the merge consumes it), so its
    # cotangent lands in the softmax jacobian: ds = p * (dp - delta + g_lse)
    # — the existing fused backward with an adjusted delta
    delta = delta - g_lse[..., None]

    bwd_kern = dev.build_kernel(ring_flash_bwd_builder, D)
    dq, dk_h, dv_h = bwd_kern.run(q, k, v, do, lse, delta, q_start, k_start)
    dk = dk_h.reshape(b, hk, grp, skv, d).sum(2).astype(k.dtype)
    dvv = dv_h.reshape(b, hk, grp, skv, dv).sum(2).astype(v.dtype)

    def f0(a):  # integer offsets: zero-sized tangent space
        return np.zeros(a.shape, jax.dtypes.float0)

    return dq.astype(q.dtype), dk, dvv, f0(q_start), f0(k_start)


_ring_step.defvjp(_ring_step_fwd, _ring_step_bwd)


# ---------------------------------------------------------------------------
# the declared mesh schedule (OpShard hooks)
# ---------------------------------------------------------------------------

def _ring_shard_step(op, args, params, *, t, n, axis):
    """Ring step ``t`` inside shard_map: shard ``i`` holds kv chunk
    ``(i + t) % n``; queries sit at the end of the GLOBAL kv stream."""
    q, k, v = args[:3]
    sq, skv = q.shape[2], k.shape[2]
    i = lax.axis_index(axis)
    base = n * skv - n * sq
    qs = jnp.reshape(base + i * sq, (1, 1)).astype(jnp.int32)
    ks = jnp.reshape(((i + t) % n) * skv, (1, 1)).astype(jnp.int32)
    frozen = tuple(sorted(params.items()))
    return _ring_step(frozen, q, k, v, qs, ks)


def _ring_in_specs(axis, args):
    p = PartitionSpec(None, None, axis, None)   # q/k/v sharded on seq
    return (p, p, p)


def _ring_out_specs(axis):
    return PartitionSpec(None, None, axis, None)


ring_flash = define_op(
    "ring_flash",
    builder=ring_flash_fwd_builder,
    ref=ring_step_ref,
    derive_defines=_ring_defines,
    pre=_ring_pre,
    public_outputs=1,                        # lse is merge/backward-only
    defaults=dict(causal=True, window=None, sm_scale=None, prefix_len=0,
                  block_q=128, block_kv=128, ring_steps=1,
                  mesh_axis="model"),
    array_params=("q_start", "k_start"),     # dynamic absolute offsets
    ref_params=("q_start", "k_start", "causal", "window", "sm_scale",
                "prefix_len"),
    tune_ref=_ring_tune_ref,
    sweep=dict(block_q=[64, 128, 256, 512], block_kv=[64, 128, 256, 512]),
    example=_ring_example,
    shard=OpShard(
        mesh_axis="model", collective="ppermute",
        in_specs=_ring_in_specs, out_specs=_ring_out_specs,
        rotate=(1, 2),                       # k, v hop around the ring
        extent_param="ring_steps",           # defines/tune key track shards
        step=_ring_shard_step, merge=ring_merge,
        done=lambda acc: acc[0]),            # public result: o
    doc="""One ring step of sequence-parallel flash attention: q against a kv
    chunk at dynamic absolute offsets (``q_start``/``k_start``). Call with
    ``mesh=`` to run the full shard_map ring (k/v rotating by ppermute,
    partials merged exactly); ``ring_flash_attention`` wraps both modes.""",
)


# ---------------------------------------------------------------------------
# public wrapper: mesh ring or local (single-process) ring
# ---------------------------------------------------------------------------

def ring_flash_attention(q, k, v, *, mesh=None, mesh_axis="model",
                         ring_steps=None, causal=True, window=None,
                         sm_scale=None, prefix_len=0, block_q=128,
                         block_kv=128, backend="auto", interpret=None):
    """Sequence-parallel ring flash attention, differentiable in both modes.

    ``mesh=`` runs the declared shard_map schedule: q/k/v arrive sharded
    along their sequence axis over ``mesh_axis``, kv chunks rotate around the
    ring, and the backward retraces the ring in reverse (dk/dv cotangents
    ride the transposed ppermute home). Without a mesh, the SAME per-step
    kernel + exact merge runs over ``ring_steps`` locally-split kv chunks —
    the single-device form of the schedule, bit-comparable against
    ``flash_attention`` and against the mesh run.

    Queries are aligned to the end of the global kv stream (the
    ``flash_attention`` convention), so equal global lengths give plain
    causal self-attention."""
    params = dict(causal=causal, window=window, sm_scale=sm_scale,
                  prefix_len=prefix_len, block_q=block_q, block_kv=block_kv,
                  mesh_axis=mesh_axis, backend=backend, interpret=interpret)
    if mesh is not None:
        if ring_steps is not None and ring_steps != int(mesh.shape[mesh_axis]):
            raise ValueError(
                f"ring_flash_attention: ring_steps={ring_steps} contradicts "
                f"mesh axis {mesh_axis!r} of size {mesh.shape[mesh_axis]}")
        return ring_flash(q, k, v, mesh=mesh, **params)
    n = 1 if ring_steps is None else int(ring_steps)
    sq, skv = q.shape[2], k.shape[2]
    if n < 1 or skv % n:
        raise ValueError(
            f"ring_flash_attention: ring_steps={n} does not divide the kv "
            f"length {skv}")
    chunk = skv // n
    base = skv - sq
    frozen = tuple(sorted(dict(params, ring_steps=n).items()))
    qs = jnp.full((1, 1), base, jnp.int32)
    acc = None
    for t in range(n):
        kc = lax.slice_in_dim(k, t * chunk, (t + 1) * chunk, axis=2)
        vc = lax.slice_in_dim(v, t * chunk, (t + 1) * chunk, axis=2)
        ks = jnp.full((1, 1), t * chunk, jnp.int32)
        part = _ring_step(frozen, q, kc, vc, qs, ks)
        acc = part if acc is None else ring_merge(acc, part)
    return acc[0]
