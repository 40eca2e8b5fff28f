"""Shared tune-probe shapes + winner adoption for the launchers and CLI.

One place derives the kernel-op shapes a workload will hit — serving
(prefill flash + decode flash + fused LM head at batch rows) and training
(causal flash at the train sequence + fused-CE LM head at ``B*(S-1)`` rows)
— as ``{op_name: (ShapeDtypeStruct args, params)}`` probe dicts, and one
place (:func:`adopt`) turns persisted ``op.tune`` winners for those probes
into updated op defaults, keyed by workload kind. Consumers:

  * ``tuning.adopt(cfg, shapes, kind=...)``  THE warmup surface — serve /
                                             train / mesh launchers (their
                                             old ``apply_tuned_winners``
                                             names are deprecated shims)
  * ``repro.serving.Engine``                 adopts flash_decode's winner
                                             as its page size
  * ``repro.tune_cli``                       materializes the probes as real
                                             arrays and runs the sweeps — the
                                             fleet-wide pre-tuning entry point

Probes are SHAPES ONLY (``jax.ShapeDtypeStruct``): ``Op.cached_winner`` is a
pure cache lookup, so adoption performs zero builds and zero timed sweeps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device import fit_block

__all__ = ["adopt", "serving_probes", "train_probes", "mesh_probes",
           "adopt_winners"]


def _head_dims(cfg):
    h = getattr(cfg, "n_heads", 0)
    hk = getattr(cfg, "n_kv_heads", 0) or h
    hd = getattr(cfg, "resolved_head_dim", 0)
    return h, hk, hd


def _lm_head_shapes(cfg, rows: int):
    from repro.models import pad_vocab

    d = cfg.d_model
    vpad = pad_vocab(cfg.vocab_size)
    dtype = jnp.dtype(getattr(cfg, "dtype", "float32"))
    probe = jax.ShapeDtypeStruct
    return (probe((rows, d), dtype), probe((d, vpad), dtype)), vpad


def serving_probes(cfg, batch: int, prompt_len: int, max_len: int) -> dict:
    """Probe shapes for one serving config: prefill attention, single-token
    decode attention, and the fused last-token LM head (``batch`` rows)."""
    probe = jax.ShapeDtypeStruct
    probes = {}
    h, hk, hd = _head_dims(cfg)
    dtype = jnp.dtype(getattr(cfg, "dtype", "float32"))
    window = getattr(cfg, "window", None)
    if h and hd:  # latent-attention archs (MLA) have no flash probes here
        probes["flash_attention"] = (
            (probe((batch, h, prompt_len, hd), dtype),
             probe((batch, hk, prompt_len, hd), dtype),
             probe((batch, hk, prompt_len, hd), dtype)),
            dict(causal=True, window=window))
        # windowed archs probe too: rolling-window decode runs the unified
        # kernel (slot_pos input tile) — the cache holds min(max_len, window)
        m = min(max_len, window) if window else max_len
        probes["flash_decode"] = (
            (probe((batch, h, 1, hd), dtype),
             probe((batch, hk, m, hd), dtype),
             probe((batch, hk, m, hd), dtype)),
            dict(window=window))
        if not window:
            # paged decode (the continuous-batching engine path). The op has
            # no kernel-side sweep — the page size IS the pool layout, and
            # the engine adopts flash_decode's tuned block_kv as its page
            # size — but the probe keeps the engine shapes visible to the
            # CLI / analyze sweeps. Block-table params are REAL arrays (the
            # op's pre hook reads them), sized for ``batch`` full sequences.
            page = fit_block(512, max_len)
            nsp = max_len // page
            npages = batch * nsp + 1          # + the reserved null page 0
            tab = (np.arange(batch * nsp, dtype=np.int32)
                   .reshape(batch, nsp) + 1)
            probes["flash_decode_paged"] = (
                (probe((batch, h, 1, hd), dtype),
                 probe((npages, hk, page, hd), dtype),
                 probe((npages, hk, page, hd), dtype)),
                dict(block_table=tab,
                     kv_len=np.full((batch,), max_len, np.int32)))
    (x, w), _ = _lm_head_shapes(cfg, batch)
    probes["lm_head_logits"] = ((x, w), dict(vocab=cfg.vocab_size))
    return probes


def train_probes(cfg, global_batch: int, seq_len: int) -> dict:
    """Probe shapes for one train-step config: causal attention at the full
    sequence and the fused-CE LM head at ``B * (S - 1)`` rows."""
    probe = jax.ShapeDtypeStruct
    probes = {}
    h, hk, hd = _head_dims(cfg)
    dtype = jnp.dtype(getattr(cfg, "dtype", "float32"))
    if h and hd:
        probes["flash_attention"] = (
            (probe((global_batch, h, seq_len, hd), dtype),
             probe((global_batch, hk, seq_len, hd), dtype),
             probe((global_batch, hk, seq_len, hd), dtype)),
            dict(causal=True, window=getattr(cfg, "window", None)))
    rows = global_batch * max(seq_len - 1, 1)
    (x, w), _ = _lm_head_shapes(cfg, rows)
    labels = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
    probes["lm_head_ce"] = ((x, w, labels), dict(vocab=cfg.vocab_size))
    return probes


def mesh_probes(cfg, batch: int, prompt_len: int, *, shards: int,
                mesh_axis: str = "model") -> dict:
    """Probe shapes for ring-attention prefill over ``shards`` devices.

    Under ``shard_map`` every shard runs the PER-SHARD kernel — sequence
    length ``prompt_len // shards`` — so that is the shape to tune;
    ``ring_steps`` rides in the params, keeping the persisted cache key (and
    the spec's declared shard extent) distinct per mesh size."""
    probe = jax.ShapeDtypeStruct
    probes = {}
    h, hk, hd = _head_dims(cfg)
    dtype = jnp.dtype(getattr(cfg, "dtype", "float32"))
    if shards < 1 or prompt_len % shards:
        raise ValueError(
            f"mesh_probes: shards={shards} does not divide prompt_len="
            f"{prompt_len}")
    loc = prompt_len // shards
    if h and hd:
        probes["ring_flash"] = (
            (probe((batch, h, loc, hd), dtype),
             probe((batch, hk, loc, hd), dtype),
             probe((batch, hk, loc, hd), dtype)),
            dict(causal=True, window=getattr(cfg, "window", None),
                 ring_steps=shards, mesh_axis=mesh_axis))
    return probes


def _winner_overflows(op, args, params, winner) -> bool:
    """True when a persisted winner's static VMEM footprint exceeds the
    current ``$REPRO_VMEM_BUDGET`` at these probe shapes — a stale entry
    tuned under other constraints must not be adopted (the very first build
    would raise VMEM_OVERFLOW). A winner that cannot even build at these
    shapes is a corrupt cache entry: the error propagates."""
    from types import SimpleNamespace

    from repro.core.analyze import vmem_budget, vmem_footprint

    # sweep keys may be op params (flash_decode's block_kv) or bare
    # defines (matmul's bm/bn/bk): route each winner key accordingly
    pwin = {k: v for k, v in winner.items() if k in op.defaults}
    _, _, params = op._resolve(dict(params, **pwin))
    _, defines, _ = op._prepare(tuple(args), params)
    spec = op.builder(SimpleNamespace(**dict(defines, **winner)))
    return vmem_footprint(spec)[0] > vmem_budget()


def adopt(cfg, shapes: dict, *, kind: str) -> dict:
    """THE adoption surface: build ``kind``'s probe shapes and adopt their
    persisted tune winners into the op defaults. ``shapes`` carries the
    workload dims by name:

      kind="serve"  ->  batch, prompt_len, max_len
      kind="train"  ->  global_batch, seq_len
      kind="mesh"   ->  batch, prompt_len, shards [, mesh_axis]

    Replaces the three per-launcher ``apply_tuned_winners`` wrappers (which
    now delegate here, with deprecation notes). Returns the adopted
    ``{op_name: winner_defines}``."""
    if kind == "serve":
        probes = serving_probes(cfg, shapes["batch"], shapes["prompt_len"],
                                shapes["max_len"])
    elif kind == "train":
        probes = train_probes(cfg, shapes["global_batch"], shapes["seq_len"])
    elif kind == "mesh":
        probes = mesh_probes(cfg, shapes["batch"], shapes["prompt_len"],
                             shards=shapes["shards"],
                             mesh_axis=shapes.get("mesh_axis", "model"))
    else:
        raise ValueError(f"adopt: kind must be serve|train|mesh, got {kind!r}")
    return adopt_winners(probes)


def adopt_winners(probes: dict) -> dict:
    """Update op defaults from persisted ``op.tune`` winners for ``probes``
    (``$REPRO_CACHE_DIR``) — a pure cache lookup via the op registry, no
    builds, no timed sweeps (winners only pay a cheap static VMEM-footprint
    check, so a stale oversized winner can't poison the defaults). Returns
    ``{op_name: winner_defines}``."""
    import repro.kernels  # noqa: F401 — registers the op families
    from repro.core import registered_ops

    applied = {}
    for name, (args, params) in probes.items():
        op = registered_ops().get(name)
        if op is None:
            continue
        winner = op.cached_winner(args, **params)
        if winner and _winner_overflows(op, args, params, winner):
            continue
        if winner:
            op.defaults.update(winner)
            applied[name] = winner
    return applied
