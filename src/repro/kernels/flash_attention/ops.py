"""Public flash-attention ops — ``define_op`` declarations, fwd AND bwd.

``flash_attention`` is one declaration with a fully unified custom VJP: the
forward runs ``flash_fwd_builder`` on any backend; the backward runs the
delta-precompute and the dk/dv and dq kernels (``flash_bwd_dkdv_builder``,
``flash_bwd_dq_builder``) on the SAME backend, wired through the
front-end's VJP declaration. No O(S^2) residuals are saved — only
(q, k, v, o, lse); the backward recomputes p blockwise from the lse stats.

``flash_decode`` is a second declaration for single-token serving: the same
online-softmax kernel specialized to one query row per head (a KV head's
query heads served together from one read of its block), with TWO dynamic
inputs — ``kv_len`` masking the unfilled tail of the cache and ``slot_pos``
mapping each cache slot to its absolute position, so rotated rolling-window
caches run the same kernel (no grad needed at serving time).
``decode_attention`` is its thin public wrapper.

``flash_decode_paged`` is the continuous-batching variant: KV lives in a
POOL of fixed-size pages shared by every sequence, and a per-sequence
``block_table`` (the vLLM PagedAttention idiom) is declared as a
tile-indexed index map (``Tile(index_tile=...)``) — the kernel's K/V index
maps read the table at runtime to gather non-contiguous pages, on every
backend, with the indirection analyzer-bounds-checked (``BOUNDS_TABLE``)
and cost-priced as a gather. A second table, ``kv_table``, addresses K/V
alone, so a model's layer-stacked pool is read in place (layer ``l`` at
``block_table + l*P`` of the flattened stack) while positions stay per
pool page. ``paged_decode_attention`` is its wrapper.
There is no kernel-side tuning knob: the block size IS the page size, a
property of the pool layout the serving engine owns (it adopts
``flash_decode``'s tuned ``block_kv`` winner as its page size).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import OpVJP, define_op, fit_block, vmem_budget
from .kernel import (flash_attention_bwd, flash_decode_builder,
                     flash_fwd_builder, paged_decode_builder)
from .ref import decode_ref, mha_ref, paged_decode_ref

__all__ = ["flash_attention", "flash_decode", "decode_attention",
           "flash_decode_paged", "paged_decode_attention",
           "flash_attention_fwd"]


def _defines(args, params):
    q, k, v = args
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    dv = v.shape[-1]
    if h % hk:
        raise ValueError(f"flash_attention: {h} query heads not a multiple of "
                         f"{hk} kv heads")
    if q.dtype != k.dtype or q.dtype != v.dtype:
        raise ValueError(f"flash_attention: dtypes disagree "
                         f"({q.dtype}/{k.dtype}/{v.dtype})")
    block_q, block_kv = params["block_q"], params["block_kv"]
    bq, bkv = fit_block(block_q, sq), fit_block(block_kv, skv)
    ncells = b * h * (sq // bq) * (skv // bkv)
    degraded = bq < min(block_q, sq) or bkv < min(block_kv, skv)
    if degraded and ncells > 1 << 16:
        raise ValueError(
            f"flash_attention: seq lens ({sq}, {skv}) degraded blocks to "
            f"({bq}, {bkv}) = {ncells} grid cells; pad the sequences or pass "
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
        dtype=jnp.dtype(q.dtype).name)


def _pre(args, params):
    # a causal self-attention longer than a block pads its sequence to a
    # block multiple (sliced off in _post / _bwd) instead of letting
    # fit_block degrade to an odd divisor: a TPU block must be a multiple of
    # (8, 128) or span the whole axis. Exact: the padded keys sit after
    # every real query, so the causal mask hides them.
    q, k, v = args
    s = q.shape[2]
    if (not params["causal"] or k.shape[2] != s
            or int(params["prefix_len"]) > s):
        return args
    blocks = [b for b in (int(params["block_q"]), int(params["block_kv"]))
              if b < s]
    pad = (-s) % math.lcm(*blocks) if blocks else 0
    if not pad:
        return args
    return tuple(jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                 for a in args)


def _post(outs, args, params):
    return outs[0][:, :, :args[0].shape[2]]


def _residuals(outs, args, params):
    o, lse = outs
    q, k, v = _pre(args, params)
    return q, k, v, o, lse


def _bwd(params, res, g):
    q, k, v, o, lse = res
    s = g.shape[2]
    pad = o.shape[2] - s
    if pad:
        g = jnp.pad(g, ((0, 0), (0, 0), (0, pad), (0, 0)))
    backend = params["backend"]   # already resolved by the VJP front-end
    # re-derive through _defines so fwd and bwd share ONE fitting policy
    # (block sizes, sm_scale default) — the raw requested blocks may not
    # divide the sequence lengths
    D = _defines((q, k, v), params)
    grads = flash_attention_bwd(
        q, k, v, o, g, lse, causal=D["causal"], window=D["window"],
        sm_scale=D["sm_scale"], prefix_len=D["prefix_len"],
        block_q=D["block_q"], block_kv=D["block_kv"], backend=backend,
        interpret=params.get("interpret"))
    if pad:
        grads = tuple(d[:, :, :s] for d in grads)
    return grads


def _tune_ref(args, params):
    q, k, v = args
    kw = {k_: params[k_] for k_ in ("causal", "window", "sm_scale", "prefix_len")}
    return mha_ref(q, k, v, **kw)  # validates o; lse has no oracle here


def _example(rng):
    q = rng.randn(1, 4, 64, 32).astype("float32")
    k = rng.randn(1, 2, 64, 32).astype("float32")
    v = rng.randn(1, 2, 64, 32).astype("float32")
    return (q, k, v), dict(causal=True, block_q=32, block_kv=32)


flash_attention = define_op(
    "flash_attention",
    builder=flash_fwd_builder,
    ref=mha_ref,
    derive_defines=_defines,
    pre=_pre,
    post=_post,
    vjp=OpVJP(bwd=_bwd, residuals=_residuals),
    public_outputs=1,                       # lse is residual-only
    defaults=dict(causal=True, window=None, sm_scale=None, prefix_len=0,
                  block_q=128, block_kv=128),
    ref_params=("causal", "window", "sm_scale", "prefix_len"),
    tune_ref=_tune_ref,
    sweep=dict(block_q=[64, 128, 256, 512], block_kv=[64, 128, 256, 512]),
    example=_example,
    doc="""Differentiable flash attention. q (B,H,Sq,Dqk), k (B,Hk,Skv,Dqk),
    v (B,Hk,Skv,Dv); supports GQA/MQA, causal, sliding-window and prefix-LM
    masking. Unified-language forward AND backward (dk/dv and dq kernels)
    on every backend; a ragged causal self-attention pads its sequence to
    the block inside the op.""",
)


def flash_attention_fwd(q, k, v, *, causal=True, window=None, sm_scale=None,
                        prefix_len=0, block_q=128, block_kv=128,
                        backend="auto", interpret=None):
    """Forward + lse stats (b, h, sq) (functional; the op's full kernel
    output, the kernel's trailing singleton lse axis and any sequence
    padding dropped)."""
    o, lse = flash_attention.raw(
        q, k, v, causal=causal, window=window, sm_scale=sm_scale,
        prefix_len=prefix_len, block_q=block_q, block_kv=block_kv,
        backend=backend, interpret=interpret)
    s = q.shape[2]
    return o[:, :, :s], lse[:, :, :s, 0]


# ---------------------------------------------------------------------------
# single-token decode
# ---------------------------------------------------------------------------

def _group_query(q, hk, name):
    """The decode kernels' query: ``(b, h, 1, d)`` as ``(b, hk, g, d)``, a
    bitcast putting query head ``i`` in KV head ``i // g``'s group."""
    b, h, one, d = q.shape
    if one != 1:
        raise ValueError(f"{name}: expected a single query token, "
                         f"got q of shape {q.shape}")
    if h % hk:
        raise ValueError(f"{name}: {h} query heads not a multiple of "
                         f"{hk} kv heads")
    shape = (b, hk, h // hk, d)
    if isinstance(q, jax.ShapeDtypeStruct):     # shape probes (warmup, tuning)
        return jax.ShapeDtypeStruct(shape, q.dtype)
    return q.reshape(shape)


def _ungroup_query(q):
    b, hk, g, d = q.shape
    return q.reshape(b, hk * g, 1, d)


def _decode_post(outs, args, params):
    # the kernel's (b, hk, g, dv) output as the op's (b, h, 1, dv)
    o = outs[0]
    return o.reshape(args[0].shape[:-1] + o.shape[-1:])


def _kv_heads_per_cell(hk, block, d, dv, itemsize):
    """KV heads per decode grid cell: the most (a divisor of ``hk``) whose
    K and V blocks of ``block`` slots, double-buffered and with their rows
    padded to the 128 lanes they take in VMEM, fit 3/4 of the VMEM budget
    (the rest holds the body's f32 temporaries); one if even a single
    head's do not fit (the build's VMEM verdict then reports it)."""
    lanes = -(-d // 128) * 128 + -(-dv // 128) * 128
    per_head = 2 * block * lanes * itemsize
    budget = vmem_budget() * 3 // 4
    for c in range(hk, 0, -1):
        if hk % c == 0 and c * per_head <= budget:
            return c
    return 1


def _decode_pre(args, params):
    # read-only on params (.get, never .pop): pre hooks must not eat keys
    # from a dict a caller may reuse across calls
    q, k, v = args
    q = _group_query(q, k.shape[1], "flash_decode")
    skv = k.shape[2]
    kv_len = params.get("kv_len")
    if kv_len is None:
        kv_len = skv                         # full cache valid
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1, 1)
    slot_pos = params.get("slot_pos")
    if slot_pos is None:
        # positional default — slot i holds absolute position i — so callers
        # without rotated caches are untouched (the old iota mask, exactly)
        slot_pos = jnp.arange(skv, dtype=jnp.int32)
    slot_pos = jnp.asarray(slot_pos, jnp.int32).reshape(1, skv)
    return q, k, v, kv_len, slot_pos


def _decode_defines(args, params):
    q, k, v, kv_len, slot_pos = args
    b, hk, g, d = q.shape
    skv = k.shape[2]
    dv = v.shape[-1]
    if q.dtype != k.dtype or q.dtype != v.dtype:
        raise ValueError(f"flash_decode: dtypes disagree "
                         f"({q.dtype}/{k.dtype}/{v.dtype})")
    if tuple(slot_pos.shape) != (1, skv):
        raise ValueError(f"flash_decode: slot_pos shape {slot_pos.shape} "
                         f"does not match the cache length ({skv} slots)")
    want = params["block_kv"]
    bkv = fit_block(want, skv)
    hkc = _kv_heads_per_cell(hk, bkv, d, dv, jnp.dtype(q.dtype).itemsize)
    ncells = b * (hk // hkc) * (skv // bkv)
    if bkv < min(want, skv) and ncells > 1 << 16:
        raise ValueError(
            f"flash_decode: cache len {skv} degraded block_kv to {bkv} = "
            f"{ncells} grid cells; pad the cache or pass a dividing block_kv")
    sm_scale = params["sm_scale"]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    window = params["window"]
    return dict(
        b=b, h=hk * g, hk=hk, hkc=hkc, skv=skv, d=d, dv=dv, block_kv=bkv,
        window=None if window is None else int(window),
        sm_scale=float(sm_scale),
        dtype=jnp.dtype(q.dtype).name)


def _decode_tune_ref(args, params):
    import numpy as np

    # slot_pos-aware oracle: the tune validation scores rotated caches the
    # same way the kernel does (a truncating positional oracle would declare
    # every windowed candidate wrong)
    q, k, v, kv_len, slot_pos = args
    n = int(np.asarray(kv_len).reshape(-1)[0])
    o = decode_ref(_ungroup_query(q), k, v, window=params["window"],
                   sm_scale=params["sm_scale"], kv_len=n,
                   slot_pos=jnp.asarray(slot_pos).reshape(-1))
    return o.reshape(q.shape[:-1] + o.shape[-1:])


def _decode_example(rng):
    q = rng.randn(1, 4, 1, 32).astype("float32")
    k = rng.randn(1, 2, 128, 32).astype("float32")
    v = rng.randn(1, 2, 128, 32).astype("float32")
    return (q, k, v), dict(block_kv=32)


flash_decode = define_op(
    "flash_decode",
    builder=flash_decode_builder,
    ref=decode_ref,
    derive_defines=_decode_defines,
    pre=_decode_pre,
    post=_decode_post,
    defaults=dict(window=None, sm_scale=None, block_kv=512),
    array_params=("kv_len", "slot_pos"),    # dynamic length + slot positions
    ref_params=("window", "sm_scale"),
    tune_ref=_decode_tune_ref,
    sweep=dict(block_kv=[128, 256, 512, 1024]),
    example=_decode_example,
    doc="""Single-token decode attention: q (B,H,1,D) against a kv cache
    (B,Hk,S,D). ``kv_len`` (int or traced scalar) masks the unfilled tail of
    the cache — the query sits at position kv_len-1 — so one compiled kernel
    serves every step of an incremental-decode loop. ``slot_pos`` ((S,) i32,
    -1 = empty) gives each cache slot's absolute position for ROTATED
    rolling-window caches (slot = pos % W); omitted, slots are positional.""",
)


# ---------------------------------------------------------------------------
# paged single-token decode (continuous batching)
# ---------------------------------------------------------------------------

def _paged_pre(args, params):
    # read-only on params (.get, never .pop) — same contract as _decode_pre
    q, k, v = args
    npages, _, page, _ = k.shape
    q = _group_query(q, k.shape[1], "flash_decode_paged")
    b = q.shape[0]
    table = params.get("block_table")
    if table is None:
        raise ValueError(
            "flash_decode_paged: block_table= is required — per-sequence "
            "page indices into the pool, shape (B, n_seq_pages) i32")
    table = jnp.asarray(table, jnp.int32)
    if table.ndim == 1:
        table = table[None]
    nsp = table.shape[-1]
    table = table.reshape(b, nsp)
    kv_table = params.get("kv_table")
    kv_table = table if kv_table is None else \
        jnp.asarray(kv_table, jnp.int32).reshape(b, nsp)
    kv_len = params.get("kv_len")
    if kv_len is None:
        kv_len = nsp * page                  # full logical capacity valid
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(-1)
    if kv_len.shape[0] == 1:
        kv_len = jnp.broadcast_to(kv_len, (b,))
    kv_len = kv_len.reshape(b, 1)
    pos = params.get("pos_pages")
    if pos is None:
        # positional default: logical block j of sequence b holds absolute
        # positions [j*page, (j+1)*page), scattered through the table into
        # pool layout. Pages no sequence's valid prefix reaches stay -1
        # (empty), so junk table entries past kv_len can never score.
        logical = jnp.arange(nsp * page, dtype=jnp.int32).reshape(nsp, page)
        valid = (jnp.arange(nsp, dtype=jnp.int32) * page)[None, :] < kv_len
        tgt = jnp.where(valid, table, npages)        # sentinel rows drop
        pos = jnp.full((npages, page), -1, jnp.int32).at[tgt.reshape(-1)].set(
            jnp.broadcast_to(logical, (b, nsp, page)).reshape(-1, page),
            mode="drop")
    # the kernel's tile is (npages, 1, page): its (1, page) block then spans
    # the full trailing dims, as the TPU's block-shape rule requires
    pos = jnp.asarray(pos, jnp.int32)
    pos = pos.reshape(pos.shape[0], 1, page)
    return q, k, v, table, kv_table, kv_len, pos


def _paged_defines(args, params):
    q, k, v, table, kv_table, kv_len, pos = args
    b, hk, g, d = q.shape
    kv_pages, _, page, _ = k.shape
    npages = pos.shape[0]
    dv = v.shape[-1]
    if q.dtype != k.dtype or q.dtype != v.dtype:
        raise ValueError(f"flash_decode_paged: dtypes disagree "
                         f"({q.dtype}/{k.dtype}/{v.dtype})")
    if tuple(v.shape[:3]) != (kv_pages, hk, page):
        raise ValueError(f"flash_decode_paged: v pool shape {v.shape} does "
                         f"not match k pool {k.shape}")
    nsp = table.shape[-1]
    stacked = params.get("kv_table") is not None
    if (kv_pages % npages) if stacked else (kv_pages != npages):
        raise ValueError(f"flash_decode_paged: pos_pages shape {pos.shape} "
                         f"does not match the pool ({kv_pages} pages of "
                         f"{page} slots)")
    sm_scale = params["sm_scale"]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    window = params["window"]
    return dict(
        b=b, h=hk * g, hk=hk, d=d, dv=dv, npages=npages, kv_pages=kv_pages,
        page=page, nseq_pages=nsp,
        hkc=_kv_heads_per_cell(hk, page, d, dv, jnp.dtype(q.dtype).itemsize),
        window=None if window is None else int(window),
        sm_scale=float(sm_scale),
        dtype=jnp.dtype(q.dtype).name)


def _paged_tune_ref(args, params):
    # kernel-facing args and output: q (b, hk, g, d) -> o (b, hk, g, dv)
    q, k, v, table, kv_table, kv_len, pos = args
    o = paged_decode_ref(_ungroup_query(q), k, v, block_table=table,
                         kv_table=kv_table, kv_len=kv_len, pos_pages=pos,
                         window=params["window"], sm_scale=params["sm_scale"])
    return o.reshape(q.shape[:-1] + o.shape[-1:])


def _paged_example(rng):
    import numpy as np

    q = rng.randn(1, 4, 1, 32).astype("float32")
    k = rng.randn(8, 2, 32, 32).astype("float32")
    v = rng.randn(8, 2, 32, 32).astype("float32")
    table = np.array([[1, 3, 2, 5]], np.int32)   # non-contiguous pages
    return (q, k, v), dict(block_table=table, kv_len=100)


flash_decode_paged = define_op(
    "flash_decode_paged",
    builder=paged_decode_builder,
    ref=paged_decode_ref,
    derive_defines=_paged_defines,
    pre=_paged_pre,
    post=_decode_post,
    defaults=dict(window=None, sm_scale=None),
    array_params=("block_table", "kv_table", "kv_len", "pos_pages"),
    # the array params ride ref_params too: the oracle needs the table
    ref_params=("window", "sm_scale", "block_table", "kv_table", "kv_len",
                "pos_pages"),
    tune_ref=_paged_tune_ref,
    sweep=dict(),             # the page size IS the block size (pool layout)
    example=_paged_example,
    doc="""Paged single-token decode attention: q (B,H,1,D) against page
    POOLS k (P,Hk,page,D) / v (P,Hk,page,Dv), gathered through a per-sequence
    ``block_table`` ((B,n_seq_pages) i32) read by the kernel's index maps at
    runtime (a tile-indexed index map — no contiguous copy on any backend).
    The grid is (B, n_seq_pages): one cell per (sequence, page) reads the
    page's K and V for every KV head once (fewer heads per cell only if
    the VMEM budget cannot hold them) and serves all H query heads.
    ``kv_len`` ((B,) i32) is per-sequence; ``pos_pages`` ((P,page) i32, -1 =
    empty) gives pool slots' absolute positions for rotated-window layouts;
    omitted, logical order is positional. ``kv_table`` ((B,n_seq_pages) i32,
    default ``block_table``) is the table K/V read through: with pools
    stacked by layer and flattened to (L*P,...), ``block_table + l*P`` reads
    layer l's pages in place, while ``pos_pages`` stays (P,page) and is read
    through ``block_table``.""",
)


def paged_decode_attention(q, k_pages, v_pages, *, block_table, kv_table=None,
                           kv_len=None, pos_pages=None, window=None,
                           sm_scale=None, backend="auto", interpret=None):
    """Paged decode attention over a shared KV page pool (no grad).

    The serving-engine hot path: each sequence reads its KV through its
    ``block_table`` row, so mixed-length continuous batches share one pool
    with zero copying (see ``flash_decode_paged``). A layer-stacked pool is
    read where it lies: flatten it to ``(L*P, ...)`` and pass
    ``kv_table=block_table + l*P``."""
    return flash_decode_paged(
        q, k_pages, v_pages, block_table=block_table, kv_table=kv_table,
        kv_len=kv_len, pos_pages=pos_pages, window=window, sm_scale=sm_scale,
        backend=backend, interpret=interpret)


def decode_attention(q, k, v, *, window=None, sm_scale=None, block_kv=None,
                     kv_len=None, slot_pos=None, backend="auto",
                     interpret=None):
    """Single-token decode attention (no grad needed at serving time).

    ``block_kv=None`` (the default) defers to the op's current default —
    which serving warmup may have replaced with a persisted tune winner; an
    explicit value always wins. ``slot_pos`` routes rotated rolling-window
    caches through the SAME kernel (see ``flash_decode``)."""
    kw = {} if block_kv is None else {"block_kv": block_kv}
    return flash_decode(q, k, v, window=window, sm_scale=sm_scale,
                        kv_len=kv_len, slot_pos=slot_pos, backend=backend,
                        interpret=interpret, **kw)
