"""Ambient parallelism context: logical-axis activation sharding.

Models call ``shard_activation(x, kind)``; with no rules in scope this is a
no-op (CPU smoke tests), under ``use_rules(Rules(mesh=...))`` it emits
``with_sharding_constraint`` with the mapped PartitionSpec on that mesh.
Kinds:

  "act_btd"  (batch, seq, d_model)       -> (batch_axes, seq_axes, None)
  "act_btf"  (batch, seq, features)      -> (batch_axes, None, "model")
  "act_bhsd" (batch, heads, seq, hd)     -> (batch_axes, "model", None, None)
  "act_bd"   (batch, d)                  -> (batch_axes, None)
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Optional

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["shard_activation", "shard_kernel", "use_rules", "current_rules",
           "Rules"]


class Rules:
    def __init__(self, *, batch_axes=("pod", "data"), model_axis="model",
                 seq_axes=None, mesh=None, ring_axis=None):
        self.batch_axes = batch_axes
        self.model_axis = model_axis
        self.seq_axes = seq_axes
        self.mesh = mesh
        # sequence-parallel attention: when set, q/k/v shard their SEQUENCE
        # dim over this mesh axis and attention runs the declared ring
        # schedule (kernels.flash_attention.ring) instead of leaving GSPMD
        # to infer collectives around a head-sharded flash call
        self.ring_axis = ring_axis

    def spec(self, kind: str) -> Optional[P]:
        b, m, s = self.batch_axes, self.model_axis, self.seq_axes
        table = {
            "act_btd": P(b, s, None),
            "act_btf": P(b, None, m),
            "act_bhsd": (P(b, None, self.ring_axis, None) if self.ring_axis
                         else P(b, m, None, None)),
            "act_bd": P(b, None),
            "act_btv": P(b, None, m),
        }
        return table.get(kind)


_rules: contextvars.ContextVar[Optional[Rules]] = contextvars.ContextVar(
    "repro_parallel_rules", default=None)


def current_rules() -> Optional[Rules]:
    return _rules.get()


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    tok = _rules.set(rules)
    try:
        yield
    finally:
        _rules.reset(tok)


def shard_activation(x, kind: str):
    rules = _rules.get()
    if rules is None:
        return x
    spec = rules.spec(kind)
    if spec is None:
        return x
    if rules.mesh is None:
        return jax.lax.with_sharding_constraint(x, spec)
    # drop axes that do not divide the dim — an invalid constraint would
    # either fail or push GSPMD into "involuntary full rematerialization"
    # (replicate-then-reshard), which shows up as huge collectives.
    entries = []
    for i, ax in enumerate(spec):
        if ax is None or i >= x.ndim:
            entries.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= rules.mesh.shape[a]
        entries.append(ax if x.shape[i] % size == 0 else None)
    # the rules carry their mesh, so the constraint is always applied: an
    # invalid one raises instead of silently dropping the placement
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, P(*entries)))


def shard_kernel(fn, args, in_axes, out_axes):
    """Call ``fn(*args)`` — a Pallas kernel call — per shard of the rules'
    mesh.

    GSPMD cannot partition a compiled Mosaic kernel, so under rules whose
    mesh has more than one device ``fn`` runs inside ``shard_map``. Axes
    are logical, one entry per array dim: ``"batch"`` (the rules' batch
    axes), ``"model"`` (its model axis) or None. A logical axis that does
    not divide EVERY input dim it labels is dropped everywhere, so arrays
    that must agree (q heads and kv heads) shard together or replicate
    together; an output dim may only carry a label some input dim carries.
    Without such a mesh this is ``fn(*args)``."""
    rules = _rules.get()
    mesh = rules.mesh if rules is not None else None
    if mesh is None or mesh.size == 1:
        return fn(*args)
    names = {"batch": tuple(a for a in rules.batch_axes
                            if a in mesh.axis_names),
             "model": (rules.model_axis,)}
    sizes = {k: math.prod(mesh.shape[a] for a in v) for k, v in names.items()}
    usable = {k for k in names
              if all(x.shape[d] % sizes[k] == 0
                     for x, axes in zip(args, in_axes)
                     for d, ax in enumerate(axes) if ax == k)}

    def spec(axes):
        return P(*(names[ax] if ax in usable else None for ax in axes))

    def is_axes(a):
        return isinstance(a, tuple) and all(e is None or isinstance(e, str)
                                            for e in a)

    local = jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(spec(a) for a in in_axes),
        out_specs=jax.tree.map(spec, out_axes, is_leaf=is_axes),
        check_vma=False)
    return local(*args)
