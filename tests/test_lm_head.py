"""Fused LM-head op + fused model head + this PR's CI satellites.

Covers: the multi-granularity lm_head kernels vs their oracles on all three
backends (vocab padding rows, non-dividing vocab blocks, bf16 activations,
argmax tie semantics), custom-VJP gradients vs the oracle VJP, model-level
fused-CE / fused-decode parity with the unfused paths (exact greedy-argmax
agreement), the labels>=vocab_size host-side guard, the ``Tile(reduce=...)``
validation gaps the op flushed out, and train-shape tune-winner adoption.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BACKENDS, Device, Scratch, Spec, Tile, registered_ops
from repro.kernels.lm_head import (lm_head_ce, lm_head_ce_ref, lm_head_logits,
                                   lm_head_logits_ref)
from repro.configs import get_config, reduced
from repro.models import LM

import repro.kernels  # noqa: F401 — registers the op families

from _hypothesis_compat import given, settings, strategies as st


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if jnp.dtype(dtype) == jnp.bfloat16 \
        else dict(rtol=3e-4, atol=3e-4)


def _mk(seed, R=16, d=16, V=64, vocab=None, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    vocab = V if vocab is None else vocab
    x = jnp.asarray(rng.randn(R, d), jnp.float32).astype(dtype)
    w = jnp.asarray(rng.randn(d, V), jnp.float32).astype(dtype)
    labels = jnp.asarray(rng.randint(0, vocab, (R, 1)), jnp.int32)
    return x, w, labels


# ---------------------------------------------------------------------------
# kernel vs oracle: CE path (lse/gold only — no materialized logits)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_ce_matches_ref_with_padding_and_nondividing_blocks(backend):
    # V=96 padded down to vocab=70 (the last vocab block is PARTIALLY padded
    # and, at block_v=16, one block is FULLY padded); block_v=40 does not
    # divide 96 and fit_block degrades it
    x, w, labels = _mk(0, R=24, d=32, V=96, vocab=70)
    ref = lm_head_ce_ref(x, w, labels, vocab=70)
    for bv in (16, 40, 96):
        got = lm_head_ce(x, w, labels, vocab=70, block_r=8, block_v=bv,
                         block_k=16, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   **_tol(x.dtype),
                                   err_msg=f"{backend} bv={bv}")


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 999),
       vocab=st.sampled_from([64, 63, 40, 1]),
       blocks=st.sampled_from([(8, 16, 8), (16, 64, 16), (4, 24, 4)]),
       dtype=st.sampled_from(["float32", "bfloat16"]))
def test_ce_property_all_backends(seed, vocab, blocks, dtype):
    br, bv, bk = blocks
    x, w, labels = _mk(seed, R=16, d=16, V=64, vocab=vocab,
                       dtype=jnp.dtype(dtype))
    ref = lm_head_ce_ref(x, w, labels, vocab=vocab)
    for backend in BACKENDS:
        got = lm_head_ce(x, w, labels, vocab=vocab, block_r=br, block_v=bv,
                         block_k=bk, backend=backend)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            **_tol(dtype), err_msg=f"{backend} vocab={vocab} blocks={blocks}")


@pytest.mark.parametrize("backend", BACKENDS)
def test_ce_grads_match_oracle_vjp(backend):
    x, w, labels = _mk(1, R=16, d=24, V=64, vocab=50)
    r = jnp.asarray(np.random.RandomState(2).randn(16), jnp.float32)

    def loss_k(x_, w_):
        return (lm_head_ce(x_, w_, labels, vocab=50, block_r=8, block_v=16,
                           block_k=8, backend=backend) * r).sum()

    def loss_r(x_, w_):
        return (lm_head_ce_ref(x_, w_, labels, vocab=50) * r).sum()

    gk = jax.grad(loss_k, argnums=(0, 1))(x, w)
    gr = jax.grad(loss_r, argnums=(0, 1))(x, w)
    for name, a, b in zip("xw", gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4,
                                   err_msg=f"d{name} mismatch on {backend}")


def test_ce_grads_under_jit_bf16():
    x, w, labels = _mk(3, R=8, d=16, V=32, dtype=jnp.bfloat16)
    g = jax.jit(jax.grad(lambda x_: lm_head_ce(
        x_, w, labels, block_r=4, block_v=16, block_k=8).sum()))(x)
    assert g.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


@pytest.mark.parametrize("backend", BACKENDS)
def test_ce_pads_odd_row_counts(backend):
    # R = B*(S-1) is odd-ish for power-of-two seq lens: the pre hook pads
    # rows to a block multiple (labels pad with 0) and the post/bwd hooks
    # slice the pad back off — values AND grads must be pad-invariant
    x, w, labels = _mk(8, R=30, d=16, V=64, vocab=50)
    ref = lm_head_ce_ref(x, w, labels, vocab=50)
    got = lm_head_ce(x, w, labels, vocab=50, block_r=8, block_v=16,
                     block_k=8, backend=backend)
    assert got.shape == (30,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
    gk = jax.grad(lambda x_, w_: lm_head_ce(
        x_, w_, labels, vocab=50, block_r=8, block_v=16, block_k=8,
        backend=backend).sum(), argnums=(0, 1))(x, w)
    gr = jax.grad(lambda x_, w_: lm_head_ce_ref(
        x_, w_, labels, vocab=50).sum(), argnums=(0, 1))(x, w)
    for name, a, b in zip("xw", gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4,
                                   err_msg=f"d{name} pad mismatch {backend}")


def test_ce_traces_at_production_train_shapes():
    # regression: B=8, S=4096, d=2048, llama-3 vocab — rows 8*4095 = 32760
    # never divides a power-of-two block_r, and vpad = 256*501 fits block_v
    # 512 -> 501 (a mild, legitimate degradation). The old any-shrink guard
    # raised here; row padding + the blowup-ratio guard must let the fused
    # CE path trace at real train shapes.
    R, d, V = 8 * 4095, 2048, 128256         # pad_vocab(128256) == 128256
    x = jax.ShapeDtypeStruct((R, d), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((d, V), jnp.bfloat16)
    labels = jax.ShapeDtypeStruct((R, 1), jnp.int32)
    out = jax.eval_shape(
        lambda x_, w_, l_: lm_head_ce(x_, w_, l_, vocab=128256), x, w, labels)
    assert out.shape == (R,) and out.dtype == jnp.float32
    # and the grads trace too (the bwd kernel shares the padding policy)
    dx, dw = jax.eval_shape(
        jax.grad(lambda x_, w_: lm_head_ce(x_, w_, labels,
                                           vocab=128256).sum(),
                 argnums=(0, 1)), x, w)
    assert dx.shape == (R, d) and dw.shape == (d, V)


def test_degradation_guard_still_catches_pathological_shapes():
    # a PRIME vocab dim collapses block_v to 1: the grid explodes far past
    # what the requested blocks would give — the blowup-ratio guard fires
    R, d, V = 25600, 16, 997
    x = jax.ShapeDtypeStruct((R, d), jnp.float32)
    w = jax.ShapeDtypeStruct((d, V), jnp.float32)
    labels = jax.ShapeDtypeStruct((R, 1), jnp.int32)
    with pytest.raises(ValueError, match="degraded"):
        jax.eval_shape(lambda x_, w_, l_: lm_head_ce(x_, w_, l_),
                       x, w, labels)


# ---------------------------------------------------------------------------
# kernel vs oracle: decode path (logits + row max + first-occurrence argmax)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_logits_m_arg_match_ref(backend):
    x, w, _ = _mk(4, R=8, d=16, V=96, vocab=70)
    lref, mref, aref = lm_head_logits_ref(x, w, vocab=70)
    lg, m, arg = lm_head_logits.raw(x, w, vocab=70, block_r=4, block_v=16,
                                    block_k=8, backend=backend)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mref),
                               rtol=3e-4, atol=3e-4)
    assert (np.asarray(arg) == np.asarray(aref)).all()
    # public call returns JUST the masked logits (drop-in for the einsum)
    pub = lm_head_logits(x, w, vocab=70, block_r=4, block_v=16, block_k=8,
                         backend=backend)
    np.testing.assert_allclose(np.asarray(pub), np.asarray(lg))


@pytest.mark.parametrize("backend", BACKENDS)
def test_argmax_first_occurrence_across_blocks(backend):
    # duplicate-max columns in DIFFERENT vocab blocks: jnp.argmax picks the
    # first occurrence; the kernel's running argmax must too (a strictly-
    # greater block max displaces it, an equal one does not)
    x, w, _ = _mk(5, R=4, d=8, V=64)
    w = w.at[:, 41].set(w[:, 9])             # blocks 0 and 2 at block_v=16
    big = jnp.asarray(np.full((8,), 3.0), jnp.float32)
    w = w.at[:, 9].set(big).at[:, 41].set(big)
    x = jnp.abs(x)                           # make column 9/41 the max
    _, mref, aref = lm_head_logits_ref(x, w)
    assert (np.asarray(aref) == 9).all()
    _, m, arg = lm_head_logits.raw(x, w, block_r=4, block_v=16, block_k=8,
                                   backend=backend)
    assert (np.asarray(arg) == 9).all()
    np.testing.assert_allclose(np.asarray(m), np.asarray(mref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# model level: fused CE / fused decode vs the unfused paths
# ---------------------------------------------------------------------------

def _small_cfg(vocab_offset=37):
    cfg = reduced(get_config("llama3_2_1b"))
    # force vpad > vocab_size so the padding rows are live in every test
    return dataclasses.replace(cfg, vocab_size=cfg.vocab_size - vocab_offset)


def _batch(cfg, seed=0, b=2, s=16):
    rng = np.random.RandomState(seed)
    return {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)))}


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_loss_matches_default_loss(backend):
    cfg = _small_cfg()
    m0 = LM(cfg, fused_head=False)
    m1 = LM(cfg, fused_head=True, head_backend=backend)
    params = m0.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    l0, met0 = m0.loss(params, batch)
    l1, met1 = m1.loss(params, batch)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    np.testing.assert_allclose(float(met0["ce"]), float(met1["ce"]),
                               rtol=1e-5)


def test_fused_loss_grads_match_default():
    cfg = _small_cfg()
    params = LM(cfg).init(jax.random.PRNGKey(1))
    batch = _batch(cfg, seed=1)
    g0 = jax.grad(lambda p: LM(cfg, fused_head=False).loss(p, batch)[0])(params)
    g1 = jax.grad(lambda p: LM(cfg, fused_head=True).loss(p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_greedy_step_argmax_matches_greedy_token_exactly(backend):
    cfg = _small_cfg()
    model = LM(cfg, fused_head=True, head_backend=backend)
    baseline = LM(cfg, fused_head=False)
    params = model.init(jax.random.PRNGKey(2))
    tokens = _batch(cfg, seed=2)["tokens"]
    _, cache = model.prefill(params, tokens[:, :8], max_len=16)
    _, cache_b = baseline.prefill(params, tokens[:, :8], max_len=16)
    for t in range(8, 12):
        tok, logits, cache = model.greedy_step(params, tokens[:, t:t + 1],
                                               cache)
        # the fused argmax IS greedy_token of the fused logits — exactly
        assert (np.asarray(tok) ==
                np.asarray(model.greedy_token(logits))).all(), t
        # and the fused logits agree with the unfused head within fp tolerance
        ref_logits, cache_b = baseline.decode_step(params,
                                                   tokens[:, t:t + 1], cache_b)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                                   rtol=2e-3, atol=2e-3)


def test_greedy_step_unfused_fallback():
    cfg = _small_cfg()
    model = LM(cfg, fused_head=False)
    params = model.init(jax.random.PRNGKey(3))
    tokens = _batch(cfg, seed=3)["tokens"]
    _, cache = model.prefill(params, tokens[:, :8], max_len=16)
    tok, logits, cache = model.greedy_step(params, tokens[:, 8:9], cache)
    ref_logits, _ = LM(cfg, fused_head=False).decode_step(
        params, tokens[:, 8:9], jax.tree.map(lambda a: a, cache))
    assert (np.asarray(tok) == np.asarray(model.greedy_token(logits))).all()


def test_prefill_last_logits_match_unfused():
    cfg = _small_cfg()
    params = LM(cfg).init(jax.random.PRNGKey(4))
    tokens = _batch(cfg, seed=4)["tokens"]
    l0, _ = LM(cfg, fused_head=False).prefill(params, tokens)
    l1, _ = LM(cfg, fused_head=True).prefill(params, tokens)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# bugfix: labels >= vocab_size raise host-side (both CE paths)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused", [False, True])
def test_out_of_range_labels_raise_host_side(fused):
    cfg = _small_cfg()
    model = LM(cfg, fused_head=fused)
    params = model.init(jax.random.PRNGKey(5))
    batch = _batch(cfg, seed=5)
    bad = {"tokens": batch["tokens"].at[0, 3].set(cfg.vocab_size)}
    with pytest.raises(ValueError, match="labels out of range"):
        model.loss(params, bad)
    neg = {"tokens": batch["tokens"].at[1, 2].set(-1)}
    with pytest.raises(ValueError, match="labels out of range"):
        model.loss(params, neg)
    # in-range labels still fine, including vocab_size - 1
    ok = {"tokens": batch["tokens"].at[0, 3].set(cfg.vocab_size - 1)}
    loss, _ = model.loss(params, ok)
    assert jnp.isfinite(loss)


def test_train_loop_host_batch_guard():
    # the jitted train step sees tracers, so LM.loss's guard cannot fire
    # there — launch.train validates each HOST batch before device_put
    from repro.launch.train import validate_host_batch

    ok = np.random.RandomState(0).randint(0, 100, (2, 8))
    validate_host_batch(ok, 100)
    with pytest.raises(ValueError, match="out of range"):
        validate_host_batch(np.array([[1, 100]]), 100)
    with pytest.raises(ValueError, match="out of range"):
        validate_host_batch(np.array([[-1, 5]]), 100)


def test_label_guard_skipped_under_trace():
    # jitted steps see tracers: the guard must not break tracing (the data
    # pipeline / an eager first step owns validation there)
    cfg = _small_cfg()
    model = LM(cfg, fused_head=True)
    params = model.init(jax.random.PRNGKey(6))
    batch = _batch(cfg, seed=6)
    loss = jax.jit(lambda p, b: model.loss(p, b)[0])(params, batch)
    assert jnp.isfinite(loss)


# ---------------------------------------------------------------------------
# Tile(reduce=...) validation gaps flushed out by the multi-granularity op
# ---------------------------------------------------------------------------

def test_input_tile_rejects_output_only_declarations():
    def bad_stream(D):
        def body(ctx, x, y):
            y[...] = x[...]

        return Spec("bad_in_stream", grid=(2,),
                    inputs=[Tile("x", (8,), jnp.float32, block=(4,),
                                 stream=True)],
                    outputs=[Tile("y", (8,), jnp.float32, block=(4,))],
                    body=body)

    with pytest.raises(ValueError, match="output-only"):
        Device("jnp").build_kernel(bad_stream, {})

    def bad_reduce(D):
        def body(ctx, x, y):
            y[...] = x[...]

        return Spec("bad_in_reduce", grid=(2, 2), reduce_axes=(1,),
                    inputs=[Tile("x", (8,), jnp.float32, block=(4,),
                                 index=lambda i, r: (i,), reduce=(1,))],
                    outputs=[Tile("y", (8,), jnp.float32, block=(4,),
                                  index=lambda i, r: (i,))],
                    body=body)

    with pytest.raises(ValueError, match="output-only"):
        Device("jnp").build_kernel(bad_reduce, {})


def test_duplicate_reduce_axes_rejected():
    def bad(D):
        def body(ctx, x, y):
            y[...] = x[...]

        return Spec("dup_reduce", grid=(2, 2), reduce_axes=(1,),
                    inputs=[Tile("x", (8,), jnp.float32, block=(4,),
                                 index=lambda i, r: (i,))],
                    outputs=[Tile("y", (8,), jnp.float32, block=(4,),
                                  index=lambda i, r: (i,), reduce=(1, 1))],
                    body=body)

    with pytest.raises(ValueError, match="duplicate axes"):
        Device("jnp").build_kernel(bad, {})


def test_three_granularities_in_one_grid_all_backends():
    """A miniature of the lm_head shape: one grid (n, nv, nk) with outputs at
    reduce=(2,) (per-slot accumulation), reduce=(1, 2) (full row state) and
    the bwd pairing's transposed granularity — all agreeing with numpy."""
    def builder(D):
        def body(ctx, x, blk_sum, total):
            acc, = ctx.scratch

            @ctx.when(ctx.is_first)
            def _init_total():
                acc[...] = jnp.zeros(acc.shape, jnp.float32)

            @ctx.when(ctx.reduce_first(1))
            def _init_blk():
                blk_sum[...] = jnp.zeros(blk_sum.shape, jnp.float32)

            blk_sum[...] = blk_sum[...] + x[...].sum(-1, keepdims=True)
            acc[...] += x[...].sum(-1, keepdims=True)

            @ctx.when(ctx.is_last)
            def _fin():
                total[...] = acc[...]

        n, nv, nk, b = D.n, D.nv, D.nk, D.b
        return Spec(
            "three_gran", grid=(n, nv, nk), reduce_axes=(1, 2),
            scratch=[Scratch((b, 1), jnp.float32)],
            inputs=[Tile("x", (n * b, nv * nk), jnp.float32, block=(b, 1),
                         index=lambda i, v, k: (i, v * D.nk + k))],
            outputs=[
                Tile("blk_sum", (n * b, nv), jnp.float32, block=(b, 1),
                     index=lambda i, v, k: (i, v), reduce=(2,)),
                Tile("total", (n * b, 1), jnp.float32, block=(b, 1),
                     index=lambda i, v, k: (i, 0), reduce=(1, 2)),
            ],
            body=body)

    n, nv, nk, b = 2, 3, 2, 4
    x = np.random.RandomState(7).randn(n * b, nv * nk).astype(np.float32)
    want_blk = x.reshape(n * b, nv, nk).sum(-1)
    want_total = x.sum(-1, keepdims=True)
    for be in BACKENDS:
        blk, total = Device(be).build_kernel(
            builder, dict(n=n, nv=nv, nk=nk, b=b)).run(x)
        np.testing.assert_allclose(np.asarray(blk), want_blk,
                                   rtol=1e-5, atol=1e-5, err_msg=be)
        np.testing.assert_allclose(np.asarray(total), want_total,
                                   rtol=1e-5, atol=1e-5, err_msg=be)


# ---------------------------------------------------------------------------
# tune-winner adoption for the TRAIN shapes (ROADMAP item: train warmup)
# ---------------------------------------------------------------------------

def test_train_warmup_adopts_persisted_lm_head_winner(tmp_path, monkeypatch):
    from repro.launch.train import apply_tuned_winners
    from repro.launch.tuning import train_probes

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    cfg = dataclasses.replace(reduced(get_config("llama3_2_1b")), d_model=256)
    B, S = 2, 65                             # rows = 2 * 64 = 128
    op = registered_ops()["lm_head_ce"]
    monkeypatch.setattr(op, "sweep", {"block_r": [128], "block_v": [256, 512],
                                      "block_k": [256]})
    defaults_before = dict(op.defaults)
    try:
        structs, params = train_probes(cfg, B, S)["lm_head_ce"]
        rng = np.random.RandomState(0)
        args = tuple(
            jnp.asarray(rng.randint(0, cfg.vocab_size, s.shape), jnp.int32)
            if jnp.dtype(s.dtype) == jnp.int32 else
            jnp.asarray(rng.standard_normal(s.shape), s.dtype)
            for s in structs)
        r = op.tune(args, repeats=1, **params)
        assert not r.cached and r.trials
        applied = apply_tuned_winners(cfg, B, S)
        assert "lm_head_ce" in applied
        assert op.defaults["block_v"] == applied["lm_head_ce"]["block_v"]
        # second adoption is a pure cache hit and idempotent
        assert apply_tuned_winners(cfg, B, S)["lm_head_ce"] == \
            applied["lm_head_ce"]
    finally:
        op.defaults.clear()
        op.defaults.update(defaults_before)


def test_tune_cli_list_and_arch_mode(tmp_path, monkeypatch, capsys):
    from repro import tune_cli

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    assert tune_cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "lm_head_ce" in out and "block_v" in out
    # fleet pre-tune at real (reduced) train shapes: rows = 2*64 = 128,
    # d_model = 128, vpad = 512 — trim the sweep so the test stays fast
    op = registered_ops()["lm_head_ce"]
    monkeypatch.setattr(op, "sweep", {"block_r": [128], "block_v": [256, 512],
                                      "block_k": [128]})
    assert tune_cli.main(["--arch", "llama3_2_1b", "--reduced", "--train",
                          "--batch", "2", "--seq-len", "65",
                          "--repeats", "1"]) == 0
    out = capsys.readouterr().out
    assert "lm_head_ce: winner" in out
    assert list((tmp_path / "autotune").glob("*.json"))


def test_fit_block_prefers_lane_multiples():
    from repro.core import fit_block

    assert fit_block(512, 128256) == 501              # plain largest divisor
    assert fit_block(512, 128256, 128) == 384         # lane-aligned divisor
    assert fit_block(512, 32000, 128) == 256
    assert fit_block(200, 300, 128) == 150            # none aligned: plain
    assert fit_block(512, 384, 128) == 384            # whole axis


@pytest.mark.parametrize("d,vpad", [(4096, 49408), (3584, 32000),
                                    (2048, 128256)])
def test_bwd_blocks_stay_tpu_aligned(d, vpad):
    """At wide d the backward's VMEM fit shrinks vocab blocks only while
    they stay 128-aligned, then row blocks (8-aligned): a TPU block's last
    two dims must be (8, 128) multiples or span the axis."""
    from repro.kernels.lm_head.ops import _base_defines, _fit_bwd_vmem

    rows = 4 * 2048
    x = jax.ShapeDtypeStruct((rows, d), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((d, vpad), jnp.bfloat16)
    D = _base_defines(x, w, dict(lm_head_ce.defaults), op_name="lm_head_ce")
    assert D["block_v"] % 128 == 0 and D["block_k"] % 128 == 0
    b = _fit_bwd_vmem({k: D[k] for k in ("R", "d", "V", "vocab", "block_r",
                                         "block_v", "dtype")})
    assert b["block_v"] % 128 == 0 and b["block_r"] % 8 == 0
    assert vpad % b["block_v"] == 0 and rows % b["block_r"] == 0
