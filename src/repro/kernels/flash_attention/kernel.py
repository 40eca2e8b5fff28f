"""Blocked online-softmax attention (FlashAttention) in the unified language.

TPU adaptation (DESIGN.md §2): work-groups -> grid cells holding one
(block_q x head_dim) query tile in VMEM; the kv dimension is the trailing
*reduce* axis so the softmax running state (m, l, acc) lives in VMEM scratch
and persists across sequential grid steps — the TPU realization of the CUDA
flash-attention inner loop. Causal/sliding-window blocks that are fully
masked are skipped whole with ``ctx.cell_when`` (no MXU work issued on
pallas; a ``lax.cond`` skip on the functional expansions).

Every kernel here is one unified-language source expanding to
jnp/loops/pallas — the bespoke hand-tiled Pallas era is over:

* ``flash_fwd_builder``    forward + lse stats (reduce over kv blocks)
* ``flash_delta_builder``  fused rowwise ``sum(do * o)`` precompute
* ``flash_bwd_dkdv_builder`` / ``flash_bwd_dq_builder``  the backward as
  two passes with transposed grids, each recomputing ``p`` from the lse
  stats: dk/dv accumulate over the inner q-block sweep (grid
  (b, h, nk, nq)), dq over the inner k-block sweep (grid (b, h, nq, nk)).
  Every output block is revisited on CONSECUTIVE grid steps only — the one
  accumulation pattern a compiled TPU pipeline supports (it writes an
  output block back when its index changes and never reads it again).
* ``flash_decode_builder`` single-token decode against a (possibly partially
  filled, possibly ROTATED rolling-window) kv cache; the valid length is a
  dynamic ``kv_len`` input and the slot->absolute-position map a dynamic
  ``slot_pos`` input tile, so one compiled kernel serves every step of an
  incremental-decode loop — including past the wrap of a rolling cache.

Host paths live in the ``define_op`` declarations in ``ops.py``;
``flash_attention_bwd`` below is the backward's host wrapper (kernel builds
via the shared Device cache + the GQA head-group reduction).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax

from repro.core import Scratch, ShardAxis, Spec, Tile, default_device

__all__ = ["flash_fwd_builder", "flash_delta_builder",
           "flash_bwd_dkdv_builder", "flash_bwd_dq_builder",
           "flash_decode_builder", "flash_attention_bwd",
           "ring_flash_fwd_builder", "ring_flash_bwd_builder"]

_NEG_INF = float("-inf")


def flash_fwd_builder(D):
    """q: (b, h, sq, d); k: (b, hk, skv, d); v: (b, hk, skv, dv) ->
    o: (b, h, sq, dv), lse: (b, h, sq, 1) f32 (softmax stats for the
    backward; the trailing singleton keeps the (bq, 1) block legal on the
    TPU, whose last two block dims must be (8, 128)-aligned or full).

    Grid (b, h, nq, nk) with nk the sequential reduce axis; m/l/acc running
    state in scratch, init under ``is_first``, flushed under ``is_last``;
    fully-masked (q, kv)-blocks are ``cell_when``-skipped."""
    b, h, hk = D.b, D.h, D.hk
    sq, skv, d, dv = D.sq, D.skv, D.d, D.dv
    bq, bkv = D.block_q, D.block_kv
    causal, window, prefix = D.causal, D.window, D.prefix_len
    sm_scale = D.sm_scale
    g = h // hk
    q_offset = skv - sq  # queries aligned to the end of the kv stream
    dtype = jnp.dtype(D.dtype)

    def body(ctx, q_ref, k_ref, v_ref, o_ref, lse_ref):
        m_scr, l_scr, acc_scr = ctx.scratch
        qi = ctx.outer_id(2)
        ki = ctx.reduce_id(0)

        @ctx.when(ctx.is_first)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        run = _run_cond(qi, ki, causal=causal, window=window,
                        prefix_len=prefix, block_q=bq, block_kv=bkv,
                        q_offset=q_offset)

        @ctx.cell_when(run)
        def _step():
            q_pos = qi * bq + lax.iota(jnp.int32, bq) + q_offset
            k_pos = ki * bkv + lax.iota(jnp.int32, bkv)
            q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
            k = k_ref[0, 0].astype(jnp.float32)          # (bkv, d)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
            mask = _mask_block(q_pos, k_pos, causal=causal, window=window,
                               prefix_len=prefix)
            s = jnp.where(mask, s, _NEG_INF)

            m_prev = m_scr[:, :1]                         # (bq, 1)
            l_prev = l_scr[:, :1]
            m_cur = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            # correction for fully-masked history (m_prev == -inf): acc is 0
            corr = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_cur))
            p = jnp.exp(s - m_cur)
            p = jnp.where(mask, p, 0.0)                   # kills -inf - -inf NaNs
            v = v_ref[0, 0].astype(jnp.float32)
            acc_scr[...] = acc_scr[...] * corr + lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_scr[:, :1] = l_prev * corr + p.sum(-1, keepdims=True)
            m_scr[:, :1] = m_cur

        @ctx.when(ctx.is_last)
        def _fin():
            l = l_scr[:, :1]
            o_ref[0, 0] = (acc_scr[...] /
                           jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
            # log-sum-exp per query row (softmax stats for the backward kernel)
            lse_ref[0, 0] = m_scr[:, :1] + jnp.log(jnp.where(l == 0.0, 1.0, l))

    return Spec(
        "flash_attention_fwd",
        grid=(b, h, sq // bq, skv // bkv),
        reduce_axes=(3,),
        scratch=[Scratch((bq, 128), jnp.float32),   # m (lane-replicated col 0)
                 Scratch((bq, 128), jnp.float32),   # l
                 Scratch((bq, dv), jnp.float32)],   # acc
        inputs=[
            Tile("q", (b, h, sq, d), dtype, block=(1, 1, bq, d),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            Tile("k", (b, hk, skv, d), dtype, block=(1, 1, bkv, d),
                 index=lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
            Tile("v", (b, hk, skv, dv), dtype, block=(1, 1, bkv, dv),
                 index=lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
        ],
        outputs=[
            Tile("o", (b, h, sq, dv), dtype, block=(1, 1, bq, dv),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            Tile("lse", (b, h, sq, 1), jnp.float32, block=(1, 1, bq, 1),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        body=body)


# ---------------------------------------------------------------------------
# shared masking / recompute helpers (pure jnp — usable from any expansion)
# ---------------------------------------------------------------------------

def _mask_block(q_pos, k_pos, *, causal, window, prefix_len):
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    if prefix_len:
        mask |= jnp.broadcast_to(k_pos[None, :] < prefix_len, mask.shape)
    return mask


def _p_block(q, k, lse, mask, sm_scale):
    """Recomputed softmax block from the saved (bq, 1) row stats."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
    p = jnp.exp(s - lse)
    return jnp.where(mask, p, 0.0)


def _run_cond(qi, ki, *, causal, window, prefix_len, block_q, block_kv,
              q_offset):
    """Whole-block skip: strictly-above-diagonal (causal) or out-of-window."""
    run = jnp.bool_(True)
    if causal:
        run &= (ki * block_kv) <= (qi * block_q + q_offset + block_q - 1)
    if window is not None:
        run &= (qi * block_q + q_offset) - (ki * block_kv + block_kv - 1) < window
    if prefix_len:
        run |= (ki * block_kv) < prefix_len   # prefix keys always visible
    return run


# ---------------------------------------------------------------------------
# backward: delta precompute + ONE fused dq/dk/dv kernel
# ---------------------------------------------------------------------------

def flash_delta_builder(D):
    """do, o: (b, h, sq, dv) -> delta: (b, h, sq, 1) f32, rowwise sum(do * o).

    The multiply and the row reduction fuse in one grid cell — the (b,h,sq,dv)
    product never materializes."""
    b, h, sq, dv = D.b, D.h, D.sq, D.dv
    bq = D.block_q
    dtype = jnp.dtype(D.dtype)

    def body(ctx, do_ref, o_ref, delta_ref):
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        delta_ref[0, 0] = (do * o).sum(-1, keepdims=True)

    return Spec(
        "flash_delta",
        grid=(b, h, sq // bq),
        inputs=[
            Tile("do", (b, h, sq, dv), dtype, block=(1, 1, bq, dv),
                 index=lambda b_, h_, qi: (b_, h_, qi, 0)),
            Tile("o", (b, h, sq, dv), dtype, block=(1, 1, bq, dv),
                 index=lambda b_, h_, qi: (b_, h_, qi, 0)),
        ],
        outputs=[
            Tile("delta", (b, h, sq, 1), jnp.float32, block=(1, 1, bq, 1),
                 index=lambda b_, h_, qi: (b_, h_, qi, 0)),
        ],
        body=body)


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki, *,
              bq, bkv, q_offset, causal, window, prefix, sm_scale):
    """One (qi, ki) tile of the backward: (q, k, do, p, ds) in f32."""
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    q_pos = qi * bq + lax.iota(jnp.int32, bq) + q_offset
    k_pos = ki * bkv + lax.iota(jnp.int32, bkv)
    mask = _mask_block(q_pos, k_pos, causal=causal, window=window,
                       prefix_len=prefix)
    p = _p_block(q, k, lse_ref[0, 0], mask, sm_scale)           # (bq, bkv)
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, 0]) * sm_scale                  # (bq, bkv)
    return q, k, do, p, ds


def _bwd_dims(D):
    return dict(b=D.b, h=D.h, hk=D.hk, sq=D.sq, skv=D.skv, d=D.d, dv=D.dv,
                bq=D.block_q, bkv=D.block_kv, g=D.h // D.hk,
                q_offset=D.skv - D.sq, dtype=jnp.dtype(D.dtype))


def _bwd_inputs(n, *, qkv_index, row_index):
    """The six backward inputs; ``qkv_index``/``row_index`` map the grid to
    (q-block, k-block) ids, so both transposed grids share one declaration."""
    b, h, hk, sq, skv = n["b"], n["h"], n["hk"], n["sq"], n["skv"]
    d, dv, bq, bkv, g, dtype = (n["d"], n["dv"], n["bq"], n["bkv"], n["g"],
                                n["dtype"])

    def qi_of(*ids):
        return qkv_index(*ids)[0]

    def ki_of(*ids):
        return qkv_index(*ids)[1]

    return [
        Tile("q", (b, h, sq, d), dtype, block=(1, 1, bq, d),
             index=lambda *ids: (ids[0], ids[1], qi_of(*ids), 0)),
        Tile("k", (b, hk, skv, d), dtype, block=(1, 1, bkv, d),
             index=lambda *ids: (ids[0], ids[1] // g, ki_of(*ids), 0)),
        Tile("v", (b, hk, skv, dv), dtype, block=(1, 1, bkv, dv),
             index=lambda *ids: (ids[0], ids[1] // g, ki_of(*ids), 0)),
        Tile("do", (b, h, sq, dv), dtype, block=(1, 1, bq, dv),
             index=lambda *ids: (ids[0], ids[1], qi_of(*ids), 0)),
        Tile("lse", (b, h, sq, 1), jnp.float32, block=(1, 1, bq, 1),
             index=row_index),
        Tile("delta", (b, h, sq, 1), jnp.float32, block=(1, 1, bq, 1),
             index=row_index),
    ]


def flash_bwd_dkdv_builder(D):
    """Flash backward, dk/dv pass: grid (b, h, nk, nq), the q-block sweep
    innermost and sequential. dk/dv (per QUERY head, f32) accumulate in
    their output blocks across the consecutive q-block visits; the GQA
    head-group reduction happens on the host in :func:`flash_attention_bwd`."""
    n = _bwd_dims(D)
    b, h, skv, d, dv = n["b"], n["h"], n["skv"], n["d"], n["dv"]
    bq, bkv = n["bq"], n["bkv"]
    kw = dict(bq=bq, bkv=bkv, q_offset=n["q_offset"], causal=D.causal,
              window=D.window, prefix=D.prefix_len, sm_scale=D.sm_scale)

    def body(ctx, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
             dk_ref, dv_ref):
        ki = ctx.outer_id(2)
        qi = ctx.reduce_id(0)

        @ctx.when(ctx.is_first)
        def _init():
            dk_ref[0, 0] = jnp.zeros((bkv, d), jnp.float32)
            dv_ref[0, 0] = jnp.zeros((bkv, dv), jnp.float32)

        run = _run_cond(qi, ki, causal=D.causal, window=D.window,
                        prefix_len=D.prefix_len, block_q=bq, block_kv=bkv,
                        q_offset=n["q_offset"])

        @ctx.cell_when(run)
        def _step():
            q, _, do, p, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                        delta_ref, qi, ki, **kw)
            dv_ref[0, 0] = dv_ref[0, 0] + lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # p^T @ do
            dk_ref[0, 0] = dk_ref[0, 0] + lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # ds^T @ q

    return Spec(
        "flash_attention_bwd_dkdv",
        grid=(b, h, skv // bkv, n["sq"] // bq),
        reduce_axes=(3,),
        inputs=_bwd_inputs(n, qkv_index=lambda b_, h_, ki, qi: (qi, ki),
                           row_index=lambda b_, h_, ki, qi: (b_, h_, qi, 0)),
        outputs=[
            Tile("dk", (b, h, skv, d), jnp.float32, block=(1, 1, bkv, d),
                 index=lambda b_, h_, ki, qi: (b_, h_, ki, 0)),
            Tile("dv", (b, h, skv, dv), jnp.float32, block=(1, 1, bkv, dv),
                 index=lambda b_, h_, ki, qi: (b_, h_, ki, 0)),
        ],
        body=body)


def flash_bwd_dq_builder(D):
    """Flash backward, dq pass: grid (b, h, nq, nk), the k-block sweep
    innermost and sequential; dq accumulates in f32 scratch and is flushed
    once per query block."""
    n = _bwd_dims(D)
    b, h, sq, d = n["b"], n["h"], n["sq"], n["d"]
    bq, bkv = n["bq"], n["bkv"]
    kw = dict(bq=bq, bkv=bkv, q_offset=n["q_offset"], causal=D.causal,
              window=D.window, prefix=D.prefix_len, sm_scale=D.sm_scale)

    def body(ctx, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref):
        dq_scr, = ctx.scratch
        qi = ctx.outer_id(2)
        ki = ctx.reduce_id(0)

        @ctx.when(ctx.is_first)
        def _init():
            dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

        run = _run_cond(qi, ki, causal=D.causal, window=D.window,
                        prefix_len=D.prefix_len, block_q=bq, block_kv=bkv,
                        q_offset=n["q_offset"])

        @ctx.cell_when(run)
        def _step():
            _, k, _, _, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                       delta_ref, qi, ki, **kw)
            dq_scr[...] += lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # ds @ k

        @ctx.when(ctx.is_last)
        def _flush():
            dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)

    return Spec(
        "flash_attention_bwd_dq",
        grid=(b, h, sq // bq, n["skv"] // bkv),
        reduce_axes=(3,),
        scratch=[Scratch((bq, d), jnp.float32)],
        inputs=_bwd_inputs(n, qkv_index=lambda b_, h_, qi, ki: (qi, ki),
                           row_index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        outputs=[
            Tile("dq", (b, h, sq, d), n["dtype"], block=(1, 1, bq, d),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        body=body)


def flash_attention_bwd(q, k, v, o, do, lse, *, causal=True, window=None,
                        sm_scale=None, prefix_len=0, block_q=128,
                        block_kv=128, backend="pallas", interpret=None):
    """Flash backward host path: delta kernel + the dk/dv and dq kernels +
    GQA head-group reduction. ``lse`` is (b, h, sq) or (b, h, sq, 1).
    Returns (dq, dk, dv)."""
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    dv_dim = v.shape[-1]
    g = h // hk
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    dev = default_device(backend, interpret)
    dtype = jnp.dtype(q.dtype).name
    do = do.astype(q.dtype)

    delta_kern = dev.build_kernel(flash_delta_builder, dict(
        b=b, h=h, sq=sq, dv=dv_dim, block_q=block_q, dtype=dtype))
    delta, = delta_kern.run(do, o.astype(q.dtype))

    bwd_defines = dict(
        b=b, h=h, hk=hk, sq=sq, skv=skv, d=d, dv=dv_dim,
        block_q=block_q, block_kv=block_kv, causal=bool(causal),
        window=None if window is None else int(window),
        prefix_len=int(prefix_len), sm_scale=float(sm_scale), dtype=dtype)
    lse = lse.reshape(b, h, sq, 1)
    args = (q, k, v, do, lse, delta)
    dk_h, dv_h = dev.build_kernel(flash_bwd_dkdv_builder,
                                  bwd_defines).run(*args)
    dq, = dev.build_kernel(flash_bwd_dq_builder, bwd_defines).run(*args)

    # GQA: reduce dk/dv over the query-head group
    dk = dk_h.reshape(b, hk, g, skv, d).sum(2).astype(k.dtype)
    dv = dv_h.reshape(b, hk, g, skv, dv_dim).sum(2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# single-token decode
# ---------------------------------------------------------------------------

def flash_decode_builder(D):
    """q: (b, h, 1, d) vs cache k: (b, hk, skv, d), v: (b, hk, skv, dv),
    kv_len: (1, 1) i32, slot_pos: (1, skv) i32 -> o: (b, h, 1, dv).

    Same online-softmax reduce over kv blocks as the forward, with TWO
    dynamic inputs serving one compiled kernel for every step of a decode
    loop: ``kv_len`` (a whole-array scalar tile) is the number of tokens
    decoded so far — the query sits at absolute position ``kv_len - 1`` —
    and ``slot_pos`` (blocked along the kv axis like k/v) carries each cache
    slot's ABSOLUTE position, ``-1`` for never-written slots. The mask reads
    ``slot_pos`` instead of assuming positional order, so a rolling-window
    cache storing ROTATED slots (slot = pos % W) runs the same kernel: slot
    ``i`` is attended iff ``(slot_pos >= 0) & (slot_pos <= q_pos) &
    (q_pos - slot_pos < window)``. Positional caches pass the identity map
    (the op front-end's default), which recovers the old iota mask exactly.

    The ``kv_len``-driven ``cell_when`` whole-block skip survives for the
    un-wrapped prefix: while ``kv_len <= skv`` a rolling cache has not yet
    rotated (slot == position), so blocks past the query — or fully below
    the window — are skipped without issuing MXU work; once wrapped
    (``kv_len > skv``) every slot may be live and all blocks run."""
    b, h, hk = D.b, D.h, D.hk
    skv, d, dv = D.skv, D.d, D.dv
    bkv = D.block_kv
    window = D.window
    sm_scale = D.sm_scale
    g = h // hk
    dtype = jnp.dtype(D.dtype)

    def body(ctx, q_ref, k_ref, v_ref, len_ref, sp_ref, o_ref):
        m_scr, l_scr, acc_scr = ctx.scratch
        ki = ctx.reduce_id(0)

        @ctx.when(ctx.is_first)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        q_pos = len_ref[0, 0] - 1            # query at the end of the stream
        run = (ki * bkv) <= q_pos
        if window is not None:
            run &= (q_pos - (ki * bkv + bkv - 1)) < window
        # wrapped rotated cache: slots lose positional order, every block may
        # hold live (recent) tokens — the positional skip no longer applies
        run |= q_pos >= skv

        @ctx.cell_when(run)
        def _step():
            sp = sp_ref[0]                   # (bkv,) absolute slot positions
            q = q_ref[0, 0].astype(jnp.float32)          # (1, d)
            k = k_ref[0, 0].astype(jnp.float32)          # (bkv, d)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
            mask = ((sp >= 0) & (sp <= q_pos))[None, :]  # (1, bkv)
            if window is not None:
                mask &= ((q_pos - sp) < window)[None, :]
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_scr[:, :1]
            l_prev = l_scr[:, :1]
            m_cur = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            corr = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_cur))
            p = jnp.exp(s - m_cur)
            p = jnp.where(mask, p, 0.0)
            v = v_ref[0, 0].astype(jnp.float32)
            acc_scr[...] = acc_scr[...] * corr + lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_scr[:, :1] = l_prev * corr + p.sum(-1, keepdims=True)
            m_scr[:, :1] = m_cur

        @ctx.when(ctx.is_last)
        def _fin():
            l = l_scr[:, :1]
            o_ref[0, 0] = (acc_scr[...] /
                           jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    return Spec(
        "flash_decode",
        grid=(b, h, skv // bkv),
        reduce_axes=(2,),
        scratch=[Scratch((1, 128), jnp.float32),   # m
                 Scratch((1, 128), jnp.float32),   # l
                 Scratch((1, dv), jnp.float32)],   # acc
        inputs=[
            Tile("q", (b, h, 1, d), dtype, block=(1, 1, 1, d),
                 index=lambda b_, h_, ki: (b_, h_, 0, 0)),
            Tile("k", (b, hk, skv, d), dtype, block=(1, 1, bkv, d),
                 index=lambda b_, h_, ki: (b_, h_ // g, ki, 0)),
            Tile("v", (b, hk, skv, dv), dtype, block=(1, 1, bkv, dv),
                 index=lambda b_, h_, ki: (b_, h_ // g, ki, 0)),
            Tile("kv_len", (1, 1), jnp.int32),     # whole-array (dynamic len)
            Tile("slot_pos", (1, skv), jnp.int32,  # slot -> absolute position
                 block=(1, bkv), index=lambda b_, h_, ki: (0, ki)),
        ],
        outputs=[
            Tile("o", (b, h, 1, dv), dtype, block=(1, 1, 1, dv),
                 index=lambda b_, h_, ki: (b_, h_, 0, 0)),
        ],
        body=body)

def paged_decode_builder(D):
    """q: (b, h, 1, d) vs a PAGED cache pool k: (P, hk, page, d),
    v: (P, hk, page, dv), block_table: (b, NP) i32, kv_len: (b, 1) i32,
    pos_pages: (P, 1, page) i32 -> o: (b, h, 1, dv).

    The continuous-batching decode kernel (vLLM's PagedAttention idiom
    through the unified language): each sequence owns a per-slot list of
    fixed-size pages scattered through a shared pool, and the KV index maps
    READ the block table at run time — ``Tile(index_tile=("block_table",
    0))`` — to gather logical page ``j`` of sequence ``b`` from pool page
    ``block_table[b, j]``. ``pos_pages`` rides the pool through the same
    table: row ``p`` carries pool page ``p``'s absolute slot positions
    (``-1`` for never-written slots, exactly ``flash_decode``'s ``slot_pos``
    contract), so rolling-window rotated caches and partially-filled tail
    pages mask identically to the contiguous kernel. ``kv_len`` is
    per-sequence — mixed prompt/generation lengths share one compiled grid.

    Bit parity with :func:`flash_decode_builder`: with ``page == block_kv``
    and pages in logical order the online-softmax visits identical blocks in
    identical order, and fully-masked blocks are exact no-ops — so a paged
    decode is bitwise the contiguous decode, pages scattered or not.

    The ``cell_when`` whole-block skip is the contiguous kernel's, applied
    per sequence: while un-wrapped (``kv_len <= capacity``) logical page
    ``j`` holds positions ``[j*page, (j+1)*page)``; never-allocated tail
    pages point at the engine's null page, whose positions are all ``-1``."""
    b, h, hk = D.b, D.h, D.hk
    d, dv = D.d, D.dv
    npages, page, nsp = D.npages, D.page, D.nseq_pages
    window = D.window
    sm_scale = D.sm_scale
    g = h // hk
    cap = nsp * page                       # per-sequence slot capacity
    dtype = jnp.dtype(D.dtype)

    def body(ctx, q_ref, k_ref, v_ref, tab_ref, len_ref, sp_ref, o_ref):
        m_scr, l_scr, acc_scr = ctx.scratch
        j = ctx.reduce_id(0)

        @ctx.when(ctx.is_first)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        q_pos = len_ref[0, 0] - 1            # this sequence's query position
        run = (j * page) <= q_pos
        if window is not None:
            run &= (q_pos - (j * page + page - 1)) < window
        # wrapped rotated cache: slots lose positional order, every page may
        # hold live (recent) tokens — the positional skip no longer applies
        run |= q_pos >= cap

        @ctx.cell_when(run)
        def _step():
            sp = sp_ref[0, 0]                # (page,) absolute slot positions
            q = q_ref[0, 0].astype(jnp.float32)          # (1, d)
            k = k_ref[0, 0].astype(jnp.float32)          # (page, d)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
            mask = ((sp >= 0) & (sp <= q_pos))[None, :]  # (1, page)
            if window is not None:
                mask &= ((q_pos - sp) < window)[None, :]
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_scr[:, :1]
            l_prev = l_scr[:, :1]
            m_cur = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            corr = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_cur))
            p = jnp.exp(s - m_cur)
            p = jnp.where(mask, p, 0.0)
            v = v_ref[0, 0].astype(jnp.float32)
            acc_scr[...] = acc_scr[...] * corr + lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_scr[:, :1] = l_prev * corr + p.sum(-1, keepdims=True)
            m_scr[:, :1] = m_cur

        @ctx.when(ctx.is_last)
        def _fin():
            l = l_scr[:, :1]
            o_ref[0, 0] = (acc_scr[...] /
                           jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    return Spec(
        "flash_decode_paged",
        grid=(b, h, nsp),
        reduce_axes=(2,),
        scratch=[Scratch((1, 128), jnp.float32),   # m
                 Scratch((1, 128), jnp.float32),   # l
                 Scratch((1, dv), jnp.float32)],   # acc
        inputs=[
            Tile("q", (b, h, 1, d), dtype, block=(1, 1, 1, d),
                 index=lambda b_, h_, j: (b_, h_, 0, 0)),
            # pool page axis: dynamic, read from the block table per cell
            # (the static map's 0 there is the ignored placeholder)
            Tile("k", (npages, hk, page, d), dtype, block=(1, 1, page, d),
                 index=lambda b_, h_, j: (0, h_ // g, 0, 0),
                 index_tile=("block_table", 0)),
            Tile("v", (npages, hk, page, dv), dtype, block=(1, 1, page, dv),
                 index=lambda b_, h_, j: (0, h_ // g, 0, 0),
                 index_tile=("block_table", 0)),
            Tile("block_table", (b, nsp), jnp.int32, block=(1, 1),
                 index=lambda b_, h_, j: (b_, j)),
            Tile("kv_len", (b, 1), jnp.int32, block=(1, 1),
                 index=lambda b_, h_, j: (b_, 0)),
            # (npages, 1, page): a (1, page) block of the full trailing dims
            Tile("pos_pages", (npages, 1, page), jnp.int32,
                 block=(1, 1, page), index=lambda b_, h_, j: (0, 0, 0),
                 index_tile=("block_table", 0)),
        ],
        outputs=[
            Tile("o", (b, h, 1, dv), dtype, block=(1, 1, 1, dv),
                 index=lambda b_, h_, j: (b_, h_, 0, 0)),
        ],
        body=body)


# ---------------------------------------------------------------------------
# ring attention: one ring step, offsets as dynamic inputs
# ---------------------------------------------------------------------------

def ring_flash_fwd_builder(D):
    """One RING STEP of sequence-parallel flash attention.

    Identical online-softmax math to :func:`flash_fwd_builder`, with the
    static end-of-stream alignment (``q_offset = skv - sq``) replaced by TWO
    dynamic (1, 1) i32 inputs: ``q_start`` (absolute position of this shard's
    first query row) and ``k_start`` (absolute position of the kv chunk
    currently resident — it changes every ring step as chunks rotate). One
    compiled kernel therefore serves every (shard, step) pair; the causal /
    window block-skip becomes a data-dependent ``cell_when`` predicate, like
    flash-decode's ``kv_len`` skip.

    Outputs are the chunk-local softmax (``o`` normalized by the chunk's own
    ``l``, plus the chunk ``lse``); the host merges steps exactly via the
    standard logsumexp reweighting. A fully-masked query row yields
    ``o = 0, lse = -inf`` — the merge's identity element.

    The spec declares its mesh binding: grid axis 3 (the kv-chunk reduce
    axis) lives across ``ring_steps`` shards of mesh axis ``mesh_axis``, with
    k/v rotating on a declared ``ppermute`` ring.
    """
    b, h, hk = D.b, D.h, D.hk
    sq, skv, d, dv = D.sq, D.skv, D.d, D.dv
    bq, bkv = D.block_q, D.block_kv
    causal, window, prefix = D.causal, D.window, D.prefix_len
    sm_scale = D.sm_scale
    g = h // hk
    dtype = jnp.dtype(D.dtype)

    def body(ctx, q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref):
        m_scr, l_scr, acc_scr = ctx.scratch
        qi = ctx.outer_id(2)
        ki = ctx.reduce_id(0)

        @ctx.when(ctx.is_first)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        q0 = qs_ref[0, 0]
        k0 = ks_ref[0, 0]
        # the block-skip of _run_cond, with dynamic absolute offsets
        run = jnp.bool_(True)
        if causal:
            run &= (k0 + ki * bkv) <= (q0 + qi * bq + bq - 1)
        if window is not None:
            run &= ((q0 + qi * bq) - (k0 + ki * bkv + bkv - 1)) < window
        if prefix:
            run |= (k0 + ki * bkv) < prefix    # prefix keys always visible

        @ctx.cell_when(run)
        def _step():
            q_pos = q0 + qi * bq + lax.iota(jnp.int32, bq)
            k_pos = k0 + ki * bkv + lax.iota(jnp.int32, bkv)
            q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
            k = k_ref[0, 0].astype(jnp.float32)          # (bkv, d)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
            mask = _mask_block(q_pos, k_pos, causal=causal, window=window,
                               prefix_len=prefix)
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_scr[:, :1]
            l_prev = l_scr[:, :1]
            m_cur = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            corr = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_cur))
            p = jnp.exp(jnp.where(mask, s - m_cur, 0.0))
            p = jnp.where(mask, p, 0.0)
            v = v_ref[0, 0].astype(jnp.float32)
            acc_scr[...] = acc_scr[...] * corr + lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_scr[:, :1] = l_prev * corr + p.sum(-1, keepdims=True)
            m_scr[:, :1] = m_cur

        @ctx.when(ctx.is_last)
        def _fin():
            l = l_scr[:, :1]
            o_ref[0, 0] = (acc_scr[...] /
                           jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
            # lse = -inf for fully-masked rows (m stays -inf, l stays 0):
            # exactly the merge identity the host combiner expects
            lse_ref[0, 0] = m_scr[:, :1] + jnp.log(jnp.where(l == 0.0, 1.0, l))

    return Spec(
        "ring_flash_fwd",
        grid=(b, h, sq // bq, skv // bkv),
        reduce_axes=(3,),
        scratch=[Scratch((bq, 128), jnp.float32),   # m
                 Scratch((bq, 128), jnp.float32),   # l
                 Scratch((bq, dv), jnp.float32)],   # acc
        inputs=[
            Tile("q", (b, h, sq, d), dtype, block=(1, 1, bq, d),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            Tile("k", (b, hk, skv, d), dtype, block=(1, 1, bkv, d),
                 index=lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
            Tile("v", (b, hk, skv, dv), dtype, block=(1, 1, bkv, dv),
                 index=lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
            Tile("q_start", (1, 1), jnp.int32),     # whole-array (dynamic)
            Tile("k_start", (1, 1), jnp.int32),     # whole-array (dynamic)
        ],
        outputs=[
            Tile("o", (b, h, sq, dv), dtype, block=(1, 1, bq, dv),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            Tile("lse", (b, h, sq, 1), jnp.float32, block=(1, 1, bq, 1),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        body=body,
        shard=ShardAxis(mesh_axis=D.mesh_axis, axis=3, extent=D.ring_steps,
                        collective="ppermute", rotate=("k", "v")))


def ring_flash_bwd_builder(D):
    """The backward of ONE ring step: ONE fused dq/dk/dv pass over grid
    (b, h, nq, nk), both block axes sequential, with the dynamic
    ``q_start``/``k_start`` offsets, run once per ring step by the host VJP
    with the step's own lse and an lse-cotangent-adjusted delta
    (``delta' = delta - g_lse``, since ``ds = p * (dp - delta + g_lse)``
    when lse is a public output).

    dk/dv accumulate over the OUTER q-block axis, so their blocks are
    revisited non-consecutively: exact on jnp/loops/interpret, refused by
    the compiled Pallas expansion (see ``lang._expand_pallas``).

    The mesh binding mirrors the forward's ring and additionally declares
    ``dk``/``dv`` as shard-resident (grid axis 3 is their SLOT axis — each
    ring step writes the chunk owned by ANOTHER shard; under autodiff their
    cotangents ride the transposed ppermute ring home). Without that
    declaration the analyzer flags RACE_MESH_WRITE.
    """
    b, h, hk = D.b, D.h, D.hk
    sq, skv, d, dv = D.sq, D.skv, D.d, D.dv
    bq, bkv = D.block_q, D.block_kv
    causal, window, prefix = D.causal, D.window, D.prefix_len
    sm_scale = D.sm_scale
    g = h // hk
    nq, nk = sq // bq, skv // bkv
    dtype = jnp.dtype(D.dtype)

    def body(ctx, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
             qs_ref, ks_ref, dq_ref, dk_ref, dv_ref):
        dq_scr, = ctx.scratch
        qi = ctx.reduce_id(0)
        ki = ctx.reduce_id(1)

        @ctx.when(ctx.reduce_first(1))
        def _init_dq():
            dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

        @ctx.when(ctx.reduce_first(0))
        def _init_dkv():
            dk_ref[0, 0] = jnp.zeros((bkv, d), jnp.float32)
            dv_ref[0, 0] = jnp.zeros((bkv, dv), jnp.float32)

        q0 = qs_ref[0, 0]
        k0 = ks_ref[0, 0]
        run = jnp.bool_(True)
        if causal:
            run &= (k0 + ki * bkv) <= (q0 + qi * bq + bq - 1)
        if window is not None:
            run &= ((q0 + qi * bq) - (k0 + ki * bkv + bkv - 1)) < window
        if prefix:
            run |= (k0 + ki * bkv) < prefix

        @ctx.cell_when(run)
        def _step():
            q = q_ref[0, 0].astype(jnp.float32)
            k = k_ref[0, 0].astype(jnp.float32)
            v = v_ref[0, 0].astype(jnp.float32)
            do = do_ref[0, 0].astype(jnp.float32)
            lse = lse_ref[0, 0]
            delta = delta_ref[0, 0]
            q_pos = q0 + qi * bq + lax.iota(jnp.int32, bq)
            k_pos = k0 + ki * bkv + lax.iota(jnp.int32, bkv)
            mask = _mask_block(q_pos, k_pos, causal=causal, window=window,
                               prefix_len=prefix)
            # fully-masked rows carry lse = -inf; keep the exp argument
            # finite so p is an exact 0, not a masked NaN
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
            p = jnp.exp(jnp.where(mask, s - lse, 0.0))
            p = jnp.where(mask, p, 0.0)
            dv_ref[0, 0] = dv_ref[0, 0] + lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            dk_ref[0, 0] = dk_ref[0, 0] + lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_scr[...] += lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @ctx.when(ctx.reduce_last(1))
        def _flush_dq():
            dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)

    return Spec(
        "ring_flash_bwd",
        grid=(b, h, nq, nk),
        reduce_axes=(2, 3),
        scratch=[Scratch((bq, d), jnp.float32)],
        inputs=[
            Tile("q", (b, h, sq, d), dtype, block=(1, 1, bq, d),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            Tile("k", (b, hk, skv, d), dtype, block=(1, 1, bkv, d),
                 index=lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
            Tile("v", (b, hk, skv, dv), dtype, block=(1, 1, bkv, dv),
                 index=lambda b_, h_, qi, ki: (b_, h_ // g, ki, 0)),
            Tile("do", (b, h, sq, dv), dtype, block=(1, 1, bq, dv),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            Tile("lse", (b, h, sq, 1), jnp.float32, block=(1, 1, bq, 1),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            Tile("delta", (b, h, sq, 1), jnp.float32, block=(1, 1, bq, 1),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            Tile("q_start", (1, 1), jnp.int32),
            Tile("k_start", (1, 1), jnp.int32),
        ],
        outputs=[
            Tile("dq", (b, h, sq, d), dtype, block=(1, 1, bq, d),
                 index=lambda b_, h_, qi, ki: (b_, h_, qi, 0), reduce=(3,)),
            Tile("dk", (b, h, skv, d), jnp.float32, block=(1, 1, bkv, d),
                 index=lambda b_, h_, qi, ki: (b_, h_, ki, 0), reduce=(2,)),
            Tile("dv", (b, h, skv, dv), jnp.float32, block=(1, 1, bkv, dv),
                 index=lambda b_, h_, qi, ki: (b_, h_, ki, 0), reduce=(2,)),
        ],
        body=body,
        shard=ShardAxis(mesh_axis=D.mesh_axis, axis=3, extent=D.ring_steps,
                        collective="ppermute", rotate=("k", "v"),
                        sharded_outputs=("dk", "dv")))
