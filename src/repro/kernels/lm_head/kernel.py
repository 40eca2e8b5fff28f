"""Fused LM-head kernels in the unified language — the multi-output-reduce op.

The LM head is the largest single matmul of every decode and train step:
``x (R, d) @ w (d, V)`` with ``R = B*S`` rows and ``V`` the padded vocab.
The unfused model path materializes the full ``(R, V)`` f32 logits and then
runs a separate ``logsumexp`` over them — the hottest unfused path left in
the repo. These kernels fuse the matmul with the row statistics the LM
actually wants, flash-attention-style (online softmax over vocab blocks), so
the softmax normalizer and the gold-token logit come out of ONE pass without
materializing anything ``(R, V)``-shaped beyond a block.

``lm_head_builder`` — grid ``(rows, nv, nk)``, ``reduce_axes=(1, 2)`` (the
vocab-block axis ``nv`` OUTER-sequential, the d-block axis ``nk`` inner).
A logits block accumulates over the ``nk`` sweep in f32 scratch; once
complete (``reduce_last(1)``) it feeds the per-row ONLINE-SOFTMAX state
(running max m, rescaled sum-of-exp l) carried across the ``nv`` sweep in
scratch, plus the gold-token gather against a dynamic ``labels`` input tile.
Its outputs span DIFFERENT reduce granularities in one grid — the
multi-output-reduce direction ``Tile(reduce=...)`` was built for:

  ``emit_logits=1`` (decode):   logits ``Tile(reduce=(2,))`` — one block per
                                (row-block, vocab-block), accumulated over
                                the d sweep; row max ``m`` and first-
                                occurrence ``argmax`` ``Tile(reduce=(1, 2))``
                                — one block per row-block, accumulated over
                                BOTH sweeps (cheap greedy decode).
  ``emit_logits=0`` (chunked CE): ``lse`` (logsumexp) and ``gold`` (the
                                label's logit) ``Tile(reduce=(1, 2))`` ONLY —
                                the ``(R, V)`` logits never exist.

``lm_head_bwd_dx_builder`` / ``lm_head_bwd_dw_builder`` — the CE backward
``softmax(logits) - onehot`` recomputed blockwise from the saved ``lse``
stats (no logits residual), as two passes with transposed grids like the
flash backward: ``dx`` accumulates over the inner vocab sweep (grid
``(nr, nv)``), ``dw`` over the inner row sweep (grid ``(nv, nr)``), so every
output block is revisited on consecutive grid steps only.

Vocab padding (``vocab < V``, Megatron-style pad to a sharding multiple) is
handled INSIDE the kernel: padded columns are excluded from m/l/argmax/gold
and the emitted logits carry the same ``-1e30`` mask as the unfused path.
Host paths live in the ``define_op`` declarations in ``ops.py``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core import Scratch, Spec, Tile

__all__ = ["lm_head_builder", "lm_head_bwd_dx_builder",
           "lm_head_bwd_dw_builder"]

_NEG_INF = float("-inf")
_PAD_LOGIT = -1e30


def _vocab_positions(vi, bv):
    """(1, bv) absolute vocab positions of block ``vi`` (2D iota: TPU-safe)."""
    return vi * bv + lax.broadcasted_iota(jnp.int32, (1, bv), 1)


def lm_head_builder(D):
    """x: (R, d) @ w: (d, V) -> fused logits/row-stat outputs (see module doc).

    Defines: R, d, V (padded vocab), vocab (true size; columns >= vocab are
    padding), block_r/block_v/block_k block sizes (the autotune surface),
    emit_logits (output-set selector), dtype.
    """
    R, d, V, vocab = D.R, D.d, D.V, D.vocab
    br, bv, bk = D.block_r, D.block_v, D.block_k
    emit = bool(D.emit_logits)
    dtype = jnp.dtype(D.dtype)
    nv, nk = V // bv, d // bk

    def body(ctx, *refs):
        if emit:
            x_ref, w_ref, logits_ref, m_ref, arg_ref = refs
            acc, m_scr, amax_scr = ctx.scratch
        else:
            x_ref, w_ref, lab_ref, lse_ref, gold_ref = refs
            acc, m_scr, l_scr, gold_scr = ctx.scratch
        vi = ctx.reduce_id(0)

        @ctx.when(ctx.is_first)                 # vi == 0 & ki == 0: fresh row
        def _init_row_state():
            m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            if emit:
                amax_scr[...] = jnp.zeros(amax_scr.shape, jnp.int32)
            else:
                l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
                gold_scr[...] = jnp.zeros(gold_scr.shape, jnp.float32)

        @ctx.when(ctx.reduce_first(1))          # ki == 0: fresh vocab block
        def _init_acc():
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

        acc[...] += lax.dot_general(
            x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @ctx.when(ctx.reduce_last(1))           # ki == nk-1: block complete
        def _fold_block():
            s = acc[...]                                    # (br, bv) f32
            v_pos = _vocab_positions(vi, bv)                # (1, bv)
            valid = v_pos < vocab                           # (1, bv)
            s_m = jnp.where(valid, s, _NEG_INF)             # padding excluded
            bm = s_m.max(-1, keepdims=True)                 # (br, 1)
            m_prev = m_scr[:, :1]
            m_cur = jnp.maximum(m_prev, bm)
            if emit:
                logits_ref[...] = (s + jnp.where(valid, 0.0, _PAD_LOGIT)
                                   ).astype(logits_ref.dtype)
                # first-occurrence argmax: within the block jnp.argmax picks
                # the first max; across blocks only a STRICTLY larger max
                # displaces the recorded index (earlier block wins ties)
                in_arg = jnp.argmax(s_m, axis=-1).astype(jnp.int32)  # (br,)
                better = bm > m_prev                        # (br, 1)
                amax_scr[:, :1] = jnp.where(better, vi * bv + in_arg[:, None],
                                            amax_scr[:, :1])
                m_scr[:, :1] = m_cur
            else:
                # online-softmax rescale (flash's m/l update over vocab blocks)
                corr = jnp.where(m_prev == _NEG_INF, 0.0,
                                 jnp.exp(m_prev - m_cur))
                p = jnp.where(valid & (m_cur > _NEG_INF),
                              jnp.exp(s - m_cur), 0.0)
                l_scr[:, :1] = l_scr[:, :1] * corr + p.sum(-1, keepdims=True)
                m_scr[:, :1] = m_cur
                # gold-token gather: each row's label lands in exactly one
                # vocab block; padded columns never match a valid label
                lab = lab_ref[...]                          # (br, 1) i32
                hit = (lab == v_pos) & valid                # (br, bv)
                gold_scr[:, :1] += jnp.where(hit, s, 0.0).sum(-1, keepdims=True)

        @ctx.when(ctx.is_last)                  # vocab sweep done: flush
        def _flush():
            if emit:
                m_ref[...] = m_scr[:, :1]
                arg_ref[...] = amax_scr[:, :1]
            else:
                l = l_scr[:, :1]
                lse_ref[...] = m_scr[:, :1] + jnp.log(
                    jnp.where(l == 0.0, 1.0, l))
                gold_ref[...] = gold_scr[:, :1]

    inputs = [
        Tile("x", (R, d), dtype, block=(br, bk),
             index=lambda ri, vi, ki: (ri, ki)),
        Tile("w", (d, V), dtype, block=(bk, bv),
             index=lambda ri, vi, ki: (ki, vi)),
    ]
    row_tile = dict(block=(br, 1), index=lambda ri, vi, ki: (ri, 0))
    if emit:
        outputs = [
            Tile("logits", (R, V), jnp.float32, block=(br, bv),
                 index=lambda ri, vi, ki: (ri, vi), reduce=(2,)),
            Tile("m", (R, 1), jnp.float32, reduce=(1, 2), **row_tile),
            Tile("arg", (R, 1), jnp.int32, reduce=(1, 2), **row_tile),
        ]
        scratch = [Scratch((br, bv), jnp.float32),      # logits accumulator
                   Scratch((br, 128), jnp.float32),     # running max (col 0)
                   Scratch((br, 128), jnp.int32)]       # running argmax
    else:
        inputs.append(Tile("labels", (R, 1), jnp.int32, **row_tile))
        outputs = [
            Tile("lse", (R, 1), jnp.float32, reduce=(1, 2), **row_tile),
            Tile("gold", (R, 1), jnp.float32, reduce=(1, 2), **row_tile),
        ]
        scratch = [Scratch((br, bv), jnp.float32),      # logits accumulator
                   Scratch((br, 128), jnp.float32),     # running max
                   Scratch((br, 128), jnp.float32),     # running sum-of-exp
                   Scratch((br, 128), jnp.float32)]     # gold-token logit
    return Spec(
        "lm_head_logits" if emit else "lm_head_ce",
        grid=(R // br, nv, nk),
        reduce_axes=(1, 2),
        scratch=scratch,
        inputs=inputs,
        outputs=outputs,
        body=body)


def _dlogits(x, w, lab, lse, g, vi, *, bv, vocab):
    """g * (softmax(x @ w) - onehot(labels)) for one (row, vocab) block.
    Padded columns produce p == 0 and never match a valid label, so they
    contribute nothing — exactly the oracle's gradient through the -1e30
    mask."""
    s = lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    v_pos = _vocab_positions(vi, bv)                        # (1, bv)
    valid = v_pos < vocab
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    hit = (lab == v_pos) & valid                            # (br, bv)
    return (p - jnp.where(hit, 1.0, 0.0)) * g               # (br, bv)


def _bwd_inputs(D, index_rv):
    """x, w, labels, lse, g — ``index_rv`` maps the grid to (ri, vi)."""
    R, d, V = D.R, D.d, D.V
    br, bv = D.block_r, D.block_v
    dtype = jnp.dtype(D.dtype)

    def row(*ids):
        return (index_rv(*ids)[0], 0)

    return [
        Tile("x", (R, d), dtype, block=(br, d), index=row),
        Tile("w", (d, V), dtype, block=(d, bv),
             index=lambda *ids: (0, index_rv(*ids)[1])),
        Tile("labels", (R, 1), jnp.int32, block=(br, 1), index=row),
        Tile("lse", (R, 1), jnp.float32, block=(br, 1), index=row),
        Tile("g", (R, 1), jnp.float32, block=(br, 1), index=row),
    ]


def lm_head_bwd_dx_builder(D):
    """CE backward, dx pass: x, w, labels, lse, g -> dx (R, d) f32.

    Grid ``(nr, nv)``, the vocab sweep sequential and innermost: each dx
    block accumulates across consecutive vocab blocks. The d dimension is
    unblocked (one (br, d) x tile / (d, bv) w tile per cell), like flash's
    head_dim."""
    br, bv, d = D.block_r, D.block_v, D.d

    def body(ctx, x_ref, w_ref, lab_ref, lse_ref, g_ref, dx_ref):
        vi = ctx.reduce_id(0)

        @ctx.when(ctx.is_first)
        def _init():
            dx_ref[...] = jnp.zeros((br, d), jnp.float32)

        w = w_ref[...].astype(jnp.float32)                  # (d, bv)
        dl = _dlogits(x_ref[...].astype(jnp.float32), w, lab_ref[...],
                      lse_ref[...], g_ref[...], vi, bv=bv, vocab=D.vocab)
        dx_ref[...] = dx_ref[...] + lax.dot_general(
            dl, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # dl @ w^T

    return Spec(
        "lm_head_ce_bwd_dx",
        grid=(D.R // br, D.V // bv),
        reduce_axes=(1,),
        inputs=_bwd_inputs(D, lambda ri, vi: (ri, vi)),
        outputs=[Tile("dx", (D.R, d), jnp.float32, block=(br, d),
                      index=lambda ri, vi: (ri, 0))],
        body=body)


def lm_head_bwd_dw_builder(D):
    """CE backward, dw pass: x, w, labels, lse, g -> dw (d, V) f32.

    Grid ``(nv, nr)``, the row sweep sequential and innermost: each dw block
    accumulates across consecutive row blocks."""
    br, bv, d = D.block_r, D.block_v, D.d

    def body(ctx, x_ref, w_ref, lab_ref, lse_ref, g_ref, dw_ref):
        vi = ctx.outer_id(0)

        @ctx.when(ctx.is_first)
        def _init():
            dw_ref[...] = jnp.zeros((d, bv), jnp.float32)

        x = x_ref[...].astype(jnp.float32)                  # (br, d)
        dl = _dlogits(x, w_ref[...].astype(jnp.float32), lab_ref[...],
                      lse_ref[...], g_ref[...], vi, bv=bv, vocab=D.vocab)
        dw_ref[...] = dw_ref[...] + lax.dot_general(
            x, dl, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # x^T @ dl

    return Spec(
        "lm_head_ce_bwd_dw",
        grid=(D.V // bv, D.R // br),
        reduce_axes=(1,),
        inputs=_bwd_inputs(D, lambda vi, ri: (ri, vi)),
        outputs=[Tile("dw", (d, D.V), jnp.float32, block=(d, bv),
                      index=lambda vi, ri: (0, vi))],
        body=body)
