"""Public fused LM-head ops — ``define_op`` declarations.

``lm_head_ce`` is the training path: ``(x, w, labels) -> per-row NLL`` with a
custom VJP — the forward runs the fused matmul + online-softmax kernel (the
``(R, V)`` logits never materialize; only the ``lse``/``gold`` row stats come
back), and the backward recomputes ``softmax - onehot`` blockwise from the
saved ``lse`` through the dx/dw backward kernels on the SAME backend as the
forward. ``labels`` is a regular (integer) primal argument, so it threads
through ``jax.custom_vjp`` (its cotangent is the canonical ``float0``).

``lm_head_logits`` is the decode path: ``(x, w) -> logits`` publicly, with
the fused row max and first-occurrence argmax available on ``.raw`` — one
pass gives serving both the logits tensor and the greedy token.

Both declarations share ONE builder (``lm_head_builder``); the output set is
an ``emit_logits`` define. ``vocab`` (the true vocabulary size) masks the
Megatron padding columns inside the kernel.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import OpVJP, cdiv, define_op, default_device, fit_block
from .kernel import (lm_head_builder, lm_head_bwd_dw_builder,
                     lm_head_bwd_dx_builder)
from .ref import lm_head_ce_ref, lm_head_logits_ref, masked_logits_ref

__all__ = ["lm_head_ce", "lm_head_logits"]


def _row_padding(R: int, block_r) -> int:
    """Rows to append so the row-block tiles exactly. R = B*(S-1) is almost
    never divisible by a power-of-two block (S-1 is odd for power-of-two
    seq lens), so the pre hooks pad x/labels up to the next block multiple
    instead of letting ``fit_block`` degrade to an awkward divisor; the post
    hooks slice the padded rows back off."""
    br = min(int(block_r), int(R))
    return (-int(R)) % br if br > 0 else 0


def _pad_rows(a, pad: int, fill=0):
    """Append ``pad`` constant rows; shape-only probes stay shape-only."""
    if pad == 0:
        return a
    if isinstance(a, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct((a.shape[0] + pad,) + tuple(a.shape[1:]),
                                    a.dtype)
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                   constant_values=fill)


def _base_defines(x, w, params, *, op_name):
    R, d = x.shape
    d2, V = w.shape
    if d != d2:
        raise ValueError(f"{op_name}: inner dims disagree ({d} vs {d2})")
    if x.dtype != w.dtype:
        raise ValueError(f"{op_name}: dtypes disagree ({x.dtype} vs {w.dtype})")
    vocab = params["vocab"]
    vocab = V if vocab is None else int(vocab)
    if not 0 < vocab <= V:
        raise ValueError(f"{op_name}: vocab={vocab} outside (0, {V}] "
                         f"(w has {V} padded columns)")
    want = (params["block_r"], params["block_v"], params["block_k"])
    # vocab and d are lane axes of the w / x blocks: 128-multiples first
    br = fit_block(want[0], R)
    bv, bk = fit_block(want[1], V, 128), fit_block(want[2], d, 128)
    ncells = (R // br) * (V // bv) * (d // bk)
    # Degradation guard keyed on grid BLOWUP, not on any shrink: a mild fit
    # (vpad = 256*501 fitting block_v 512 -> 501) is a legitimate production
    # shape, while prime-ish dims collapsing blocks to ~1 would make Spec
    # validation and the expansions pathologically slow — only the latter
    # (grid >> what the requested blocks would give) fails loudly.
    want_cells = (cdiv(R, min(want[0], R)) * cdiv(V, min(want[1], V))
                  * cdiv(d, min(want[2], d)))
    if ncells > 1 << 16 and ncells > 8 * want_cells:
        raise ValueError(
            f"{op_name}: shapes ({R}x{d}x{V}) degraded the requested blocks "
            f"to ({br},{bv},{bk}) = {ncells} grid cells "
            f"(~{want_cells} requested); pad the operands or pass block "
            "sizes that divide the shapes")
    return dict(R=R, d=d, V=V, vocab=vocab, block_r=br, block_v=bv,
                block_k=bk, dtype=jnp.dtype(x.dtype).name)


def _ce_defines(args, params):
    x, w, labels = args[:3]
    D = _base_defines(x, w, params, op_name="lm_head_ce")
    if tuple(labels.shape) != (D["R"], 1):
        raise ValueError(
            f"lm_head_ce: labels shape {tuple(labels.shape)} != "
            f"({D['R']}, 1) — one gold token id per row")
    if jnp.dtype(labels.dtype) != jnp.int32:
        raise ValueError(f"lm_head_ce: labels must be int32, "
                         f"got {labels.dtype}")
    D["emit_logits"] = 0
    return D


def _logits_defines(args, params):
    x, w = args
    D = _base_defines(x, w, params, op_name="lm_head_logits")
    D["emit_logits"] = 1
    return D


def _ce_pre(args, params):
    # pad rows up to a block multiple (labels pad with 0 — a valid token id;
    # the padded rows' NLL is sliced off by the post hook / zeroed in bwd)
    x, w, labels = args
    pad = _row_padding(x.shape[0], params["block_r"])
    return _pad_rows(x, pad), w, _pad_rows(labels, pad)


def _ce_post(outs, args, params):
    lse, gold = outs                            # padded-row stats
    R = args[0].shape[0]                        # ORIGINAL row count
    return (lse - gold)[:R, 0]                  # per-row NLL, (R,) f32


def _ce_residuals(outs, args, params):
    lse, _ = outs                               # lse is PADDED-rows-shaped
    x, w, labels = args
    return x, w, labels, lse


def _fit_bwd_vmem(bdef: dict) -> dict:
    """The bwd working sets carry f32 dx or dw blocks on top of x/w — at
    large (d, V) the forward's fitted ``block_v`` can blow the VMEM budget.
    Shrink the vocab block, then the row block, until both passes' static
    footprints fit in 3/4 of the budget — the static footprint counts
    blocks and scratch, not the body's (br, bv) f32 temporaries, which the
    TPU compiler places in the same scoped VMEM (at d=2048, V=92672 a dw
    pass at 14.7 MB static needed 17.4 MB there). If nothing fits, keep the
    smallest candidate and let the build-time VMEM_OVERFLOW verdict report
    it."""
    from repro.core import analyze as _an

    budget = _an.vmem_budget() * 3 // 4
    V, R = int(bdef["V"]), int(bdef["R"])
    bv, br = int(bdef["block_v"]), int(bdef["block_r"])

    def fits(bv, br):
        D = SimpleNamespace(**dict(bdef, block_v=bv, block_r=br))
        return all(_an.vmem_footprint(b(D))[0] <= budget
                   for b in (lm_head_bwd_dx_builder, lm_head_bwd_dw_builder))

    # vocab blocks shrink while they stay lane-aligned, then row blocks while
    # sublane-aligned (a TPU block's last two dims: 8, 128), then vocab
    # blocks past alignment
    while not fits(bv, br):
        nbv = fit_block(bv // 2, V, 128) if bv > 1 else None
        nbr = fit_block(br // 2, R, 8) if br > 1 else None
        if nbv is not None and nbv % 128 == 0:
            bv = nbv
        elif nbr is not None and nbr % 8 == 0:
            br = nbr
        elif nbv is not None:
            bv = nbv
        else:
            break
    return dict(bdef, block_v=bv, block_r=br)


def _ce_bwd(params, res, g):
    x, w, labels, lse = res
    R = x.shape[0]
    # same padding + fitting policy as the forward (_ce_pre/_ce_defines);
    # padded rows get a ZERO cotangent so they contribute nothing to dw
    pad = _row_padding(R, params["block_r"])
    xp, labp = _pad_rows(x, pad), _pad_rows(labels, pad)
    D = _ce_defines((xp, w, labp), params)
    dev = default_device(params["backend"], params.get("interpret"))
    bdef = _fit_bwd_vmem(dict(
        R=D["R"], d=D["d"], V=D["V"], vocab=D["vocab"],
        block_r=D["block_r"], block_v=D["block_v"], dtype=D["dtype"]))
    g2 = _pad_rows(jnp.asarray(g, jnp.float32).reshape(-1, 1), pad)
    args = (xp, w, labp, lse, g2)
    dx, = dev.build_kernel(lm_head_bwd_dx_builder, bdef).run(*args)
    dw, = dev.build_kernel(lm_head_bwd_dw_builder, bdef).run(*args)
    # integer primals carry the canonical float0 cotangent
    dlabels = np.zeros(np.shape(labels), jax.dtypes.float0)
    return dx[:R].astype(x.dtype), dw.astype(w.dtype), dlabels


def _ce_tune_ref(args, params):
    # kernel-granularity oracle: autotune validates ALL kernel outputs
    x, w, labels = args
    logits = masked_logits_ref(x, w, vocab=params["vocab"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels.reshape(-1, 1).astype(jnp.int32),
                               axis=-1)[:, 0]
    return lse[:, None], gold[:, None]


def _ce_example(rng):
    x = rng.randn(24, 16).astype("float32")
    w = rng.randn(16, 64).astype("float32")
    labels = rng.randint(0, 50, (24, 1)).astype("int32")
    return (x, w, labels), dict(vocab=50, block_r=8, block_v=16, block_k=8)


lm_head_ce = define_op(
    "lm_head_ce",
    builder=lm_head_builder,
    ref=lm_head_ce_ref,
    derive_defines=_ce_defines,
    pre=_ce_pre,
    vjp=OpVJP(bwd=_ce_bwd, residuals=_ce_residuals),
    post=_ce_post,
    defaults=dict(vocab=None, block_r=256, block_v=512, block_k=512),
    ref_params=("vocab",),
    tune_ref=_ce_tune_ref,
    sweep=dict(block_r=[128, 256, 512], block_v=[256, 512, 1024],
               block_k=[128, 256, 512]),
    example=_ce_example,
    doc="""Fused LM-head cross-entropy: x (R, d) @ w (d, V) -> per-row NLL
    (R,) f32 in ONE pass (online softmax over vocab blocks; the (R, V)
    logits never materialize). labels (R, 1) i32; ``vocab`` masks Megatron
    padding columns >= vocab. Differentiable: the backward recomputes
    softmax - onehot blockwise from the saved lse on the same backend.""",
)


def _logits_pre(args, params):
    x, w = args
    return _pad_rows(x, _row_padding(x.shape[0], params["block_r"])), w


def _logits_post(outs, args, params):
    logits, = outs                              # public output only
    return logits[:args[0].shape[0]]


def _logits_tune_ref(args, params):
    return lm_head_logits_ref(*args, vocab=params["vocab"])


def _logits_example(rng):
    x = rng.randn(8, 16).astype("float32")
    w = rng.randn(16, 64).astype("float32")
    return (x, w), dict(vocab=50, block_r=8, block_v=16, block_k=8)


def _logits_public_ref(x, w, *, vocab=None):
    return masked_logits_ref(x, w, vocab=vocab)


lm_head_logits = define_op(
    "lm_head_logits",
    builder=lm_head_builder,
    ref=_logits_public_ref,
    derive_defines=_logits_defines,
    pre=_logits_pre,
    post=_logits_post,
    public_outputs=1,                           # m/arg via .raw (serving)
    defaults=dict(vocab=None, block_r=256, block_v=512, block_k=512),
    ref_params=("vocab",),
    tune_ref=_logits_tune_ref,
    sweep=dict(block_v=[256, 512, 1024], block_k=[128, 256, 512]),
    example=_logits_example,
    doc="""Fused LM-head logits for decode: x (R, d) @ w (d, V) -> masked
    logits (R, V) f32, plus (on ``.raw``) the per-row max and first-
    occurrence argmax over the true vocab — the greedy token comes out of
    the SAME pass as the logits.""",
)
