"""Pure-jnp oracle for (GQA / causal / sliding-window) attention."""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["mha_ref", "decode_ref", "paged_decode_ref", "rolling_slot_pos"]


def rolling_slot_pos(window: int, t: int):
    """The slot -> absolute-position map of a rolling cache of ``window``
    slots after ``t`` decoded tokens (slot = pos % window; -1 = never
    written). THE definition of the rolling-cache layout contract — shared
    by benchmarks, examples and the decode oracle's callers."""
    import numpy as np

    sp = np.full((window,), -1, np.int32)
    for p in range(max(t - window, 0), t):
        sp[p % window] = p
    return sp


def _expand_kv(k, n_q_heads):
    """(B, Hk, S, D) -> (B, H, S, D) by group broadcast."""
    b, hk, s, d = k.shape
    g = n_q_heads // hk
    return jnp.repeat(k, g, axis=1)


def _mask(sq, skv, *, causal, window, prefix_len):
    q_pos = jnp.arange(sq) + (skv - sq)
    k_pos = jnp.arange(skv)
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    if prefix_len:
        # prefix-LM (paligemma): keys inside the prefix are always visible
        mask |= jnp.broadcast_to(k_pos[None, :] < prefix_len, mask.shape)
    return mask


def mha_ref(q, k, v, *, causal=True, window=None, sm_scale=None, prefix_len=0):
    """q: (B, H, Sq, Dqk); k: (B, Hk, Skv, Dqk); v: (B, Hk, Skv, Dv).

    ``window`` (int) masks keys with q_pos - k_pos >= window (sliding window,
    mixtral-style; the diagonal is always kept). ``prefix_len`` makes the
    first ``prefix_len`` keys visible to every query (prefix-LM). Query
    positions are aligned to the END of the kv sequence (prefill: Sq == Skv;
    decode: Sq < Skv).
    """
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    dv = v.shape[-1]
    g = h // hk
    if sm_scale is None:
        sm_scale = 1.0 / d ** 0.5
    # grouped einsums: no repeated-kv materialization, no f32 kv copies
    # (f32 MXU accumulation via preferred_element_type)
    qg = q.reshape(b, hk, g, sq, d)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * sm_scale
    mask = _mask(sq, skv, causal=causal, window=window, prefix_len=prefix_len)
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = jnp.where(mask[None, None, None], p, 0.0)
    denom = p.sum(-1, keepdims=True)
    p = p / jnp.where(denom == 0, 1.0, denom)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, sq, dv).astype(q.dtype)


def mha_chunked(q, k, v, *, causal=True, window=None, sm_scale=None,
                prefix_len=0, block_q=1024):
    """Memory-sane jnp attention: lax.scan over query blocks (online softmax
    not needed — full key dim per block, O(B*H*block_q*Skv) working set).
    Used by the models for long prefills (the XLA path of the flash design).
    """
    import jax

    b, h, sq, dqk = q.shape
    _, hk, skv, dv = v.shape
    if sm_scale is None:
        sm_scale = 1.0 / dqk ** 0.5
    block_q = min(block_q, sq)
    while sq % block_q:
        block_q -= 1
    nq = sq // block_q
    g = h // hk
    q4 = q.reshape(b, hk, g, sq, dqk)
    k_pos = jnp.arange(skv)
    q_off = skv - sq

    def one_block(qi):
        qb = jax.lax.dynamic_slice_in_dim(q4, qi * block_q, block_q, axis=3)
        s = jnp.einsum("bkgqd,bksd->bkgqs", qb, k,
                       preferred_element_type=jnp.float32) * sm_scale
        q_pos = qi * block_q + jnp.arange(block_q) + q_off
        mask = jnp.ones((block_q, skv), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        if prefix_len:
            mask |= k_pos[None, :] < prefix_len
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jnp.exp(s - s.max(-1, keepdims=True))
        p = jnp.where(mask[None, None, None], p, 0.0)
        denom = p.sum(-1, keepdims=True)
        p = p / jnp.where(denom == 0, 1.0, denom)
        return jnp.einsum("bkgqs,bksd->bkgqd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    blocks = jax.lax.map(one_block, jnp.arange(nq))       # (nq,b,hk,g,block_q,dv)
    out = jnp.moveaxis(blocks, 0, 3).reshape(b, hk, g, sq, dv)
    return out.reshape(b, h, sq, dv).astype(q.dtype)


def decode_ref(q, k, v, *, window=None, sm_scale=None, kv_len=None,
               slot_pos=None):
    """Single-token decode oracle: q (B, H, 1, D) vs a cache (B, Hk, S, D).

    Positional caches (slot i holds position i): ``kv_len`` (a concrete int)
    truncates to the valid prefix; masking is mha_ref's causal/window mask.
    ROTATED rolling-window caches: pass ``slot_pos`` ((S,) i32 — each slot's
    absolute position, -1 for never-written) plus ``kv_len``; masking is then
    slot_pos-driven, scoring the same function as the unified ``flash_decode``
    kernel. This is the oracle the windowed autotune validates against."""
    if slot_pos is None:
        if kv_len is not None:
            k, v = k[:, :, :kv_len], v[:, :, :kv_len]
        return mha_ref(q, k, v, causal=True, window=window, sm_scale=sm_scale)
    b, h, _, d = q.shape
    hk, m = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // hk
    if sm_scale is None:
        sm_scale = 1.0 / d ** 0.5
    sp = jnp.asarray(slot_pos, jnp.int32).reshape(-1)
    q_pos = (sp.max() if kv_len is None
             else jnp.asarray(kv_len, jnp.int32).reshape(()) - 1)
    mask = (sp >= 0) & (sp <= q_pos)
    if window is not None:
        mask &= (q_pos - sp) < window
    qg = q.reshape(b, hk, g, d)
    s = jnp.einsum("bkgd,bkmd->bkgm", qg, k,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = jnp.where(mask[None, None, None], p, 0.0)
    denom = p.sum(-1, keepdims=True)
    p = p / jnp.where(denom == 0, 1.0, denom)
    o = jnp.einsum("bkgm,bkmd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, 1, dv).astype(q.dtype)


def paged_decode_ref(q, k_pages, v_pages, *, block_table, kv_table=None,
                     kv_len=None, pos_pages=None, window=None, sm_scale=None):
    """Paged single-token decode oracle: q (B, H, 1, D) against page POOLS.

    The cache is a pool of fixed-size pages shared by every sequence —
    k_pages (P, Hk, page, D), v_pages (P, Hk, page, Dv) — and each sequence
    owns the pages its ``block_table`` row names: block_table (B, n_seq_pages)
    i32, logical block j of sequence b living in pool page block_table[b, j].
    ``kv_len`` ((B,) or (B, 1) i32) is each sequence's valid prefix length;
    ``pos_pages`` ((P, page) i32, -1 = empty) gives each pool slot's absolute
    position (rotated-window layouts); omitted, logical order is positional.
    ``kv_table`` (default ``block_table``) is the table the pools are read
    through: a layer-stacked pool flattened to (L*P, ...) is read at
    ``block_table + l*P``, while ``pos_pages`` stays per pool page (P, page).
    This is the function ``flash_decode_paged`` computes; per-sequence it
    equals ``decode_ref`` on the gathered contiguous cache."""
    b, h, _, d = q.shape
    npages, hk, page, _ = k_pages.shape
    dv = v_pages.shape[-1]
    g = h // hk
    if sm_scale is None:
        sm_scale = 1.0 / d ** 0.5
    tab = jnp.asarray(block_table, jnp.int32).reshape(b, -1)
    nsp = tab.shape[1]
    m = nsp * page
    if kv_len is None:
        kv_len = m
    n = jnp.asarray(kv_len, jnp.int32).reshape(-1)
    if n.shape[0] == 1:
        n = jnp.broadcast_to(n, (b,))
    n = n.reshape(b)
    kvtab = tab if kv_table is None else \
        jnp.asarray(kv_table, jnp.int32).reshape(b, nsp)
    # gather each sequence's pages into logical-contiguous (B, Hk, m, D)
    kb = jnp.moveaxis(k_pages[kvtab], 2, 1).reshape(b, hk, m, d)
    vb = jnp.moveaxis(v_pages[kvtab], 2, 1).reshape(b, hk, m, dv)
    if pos_pages is None:
        sp = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (b, m))
    else:
        sp = jnp.asarray(pos_pages, jnp.int32)[tab].reshape(b, m)
    q_pos = n - 1                                          # (B,)
    mask = (sp >= 0) & (sp <= q_pos[:, None])
    if window is not None:
        mask &= (q_pos[:, None] - sp) < window
    qg = q.reshape(b, hk, g, d)
    s = jnp.einsum("bkgd,bkmd->bkgm", qg, kb,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = jnp.where(mask[:, None, None], p, 0.0)
    denom = p.sum(-1, keepdims=True)
    p = p / jnp.where(denom == 0, 1.0, denom)
    o = jnp.einsum("bkgm,bkmd->bkgd", p.astype(vb.dtype), vb,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, 1, dv).astype(q.dtype)
