"""The declarative op front-end + masked grid cells, tested as a contract.

Covers the PR-2 surface: ``ctx.cell_when`` backend equivalence (including
fully-skipped blocks), a registry-wide property test sweeping every
``define_op``-registered op across jnp/loops/pallas against its oracle,
flash-attention forward (unified language) + bespoke-backward gradient
checks, the persistent autotune cache (a warm cache performs ZERO sweep
builds/timings), oracle-based autotune validation, and the Memory/Kernel
cross-device guards.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BACKENDS, Device, Op, Scratch, Spec, Tile, autotune,
                        default_device, registered_ops)
from repro.kernels.flash_attention import flash_attention, mha_ref
from repro.kernels.matmul import matmul

# importing repro.kernels registers every op
import repro.kernels  # noqa: F401


def run_all_backends(builder, defines, arrays):
    outs = {}
    for be in BACKENDS:
        k = Device(be).build_kernel(builder, defines)
        outs[be] = [np.asarray(o) for o in k.run(*[jnp.asarray(a) for a in arrays])]
    return outs


# ---------------------------------------------------------------------------
# ctx.cell_when: masked/predicated grid cells
# ---------------------------------------------------------------------------

def causal_tile_builder(D):
    """Attention-style tile masking: out[qi] accumulates block sums of x only
    for ki < qi — every (qi, ki >= qi) cell is WHOLE-BLOCK skipped, and the
    qi=0 row is fully skipped (its output comes from the is_last flush of a
    never-accumulated scratch)."""

    def body(ctx, x, out):
        acc, = ctx.scratch
        qi = ctx.outer_id(0)
        ki = ctx.reduce_id(0)

        @ctx.when(ctx.is_first)
        def _init():
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

        @ctx.cell_when(ki < qi)
        def _step():
            acc[...] += jnp.sum(x[...], keepdims=True)

        @ctx.when(ctx.is_last)
        def _fin():
            out[...] = acc[...]

    nq, bn = D.nq, D.bn
    return Spec(
        "causal_tiles", grid=(nq, nq), reduce_axes=(1,),
        scratch=[Scratch((1,), jnp.float32)],
        inputs=[Tile("x", (nq * bn,), jnp.float32, block=(bn,),
                     index=lambda qi, ki: (ki,))],
        outputs=[Tile("out", (nq,), jnp.float32, block=(1,),
                      index=lambda qi, ki: (qi,))],
        body=body)


def test_cell_when_backend_equivalence_with_fully_skipped_blocks():
    nq, bn = 5, 8
    x = np.random.RandomState(0).randn(nq * bn).astype(np.float32)
    bsums = x.reshape(nq, bn).sum(1)
    want = np.array([bsums[:qi].sum() for qi in range(nq)], np.float32)
    outs = run_all_backends(causal_tile_builder, dict(nq=nq, bn=bn), [x])
    for be, got in outs.items():
        np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5,
                                   err_msg=f"backend {be} diverged")


def test_cell_when_static_predicate():
    def builder(D):
        def body(ctx, x, o):
            o[...] = jnp.zeros(o.shape, jnp.float32)

            @ctx.cell_when(bool(D.on))
            def _maybe():
                o[...] = x[...]

        return Spec("static_cw", grid=(2,),
                    inputs=[Tile("x", (8,), jnp.float32, block=(4,))],
                    outputs=[Tile("o", (8,), jnp.float32, block=(4,))],
                    body=body)

    x = np.arange(8, dtype=np.float32)
    for on, want in [(1, x), (0, np.zeros(8, np.float32))]:
        outs = run_all_backends(builder, dict(on=on), [x])
        for be, got in outs.items():
            np.testing.assert_allclose(got[0], want, err_msg=f"on={on} {be}")


# ---------------------------------------------------------------------------
# registry-wide portability: every define_op op, all backends, vs oracle
# ---------------------------------------------------------------------------

def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=3e-4, atol=3e-4)


def test_registry_has_the_four_op_families():
    names = set(registered_ops())
    assert {"matmul", "rmsnorm", "ssm_scan", "flash_attention"} <= names


@pytest.mark.parametrize("name", sorted(registered_ops()))
@pytest.mark.parametrize("backend", BACKENDS)
def test_every_registered_op_matches_its_ref_on(name, backend):
    op = registered_ops()[name]
    assert isinstance(op, Op)
    assert op.example is not None, f"op {name} must declare example inputs"
    args, params = op.example(np.random.RandomState(0))
    args = tuple(jnp.asarray(a) for a in args)
    got = op(*args, backend=backend, **params)
    ref = op.reference(*args, **params)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        **_tol(got.dtype), err_msg=f"op {name} diverged from ref on {backend}")


# ---------------------------------------------------------------------------
# flash attention: unified fwd on all backends + bespoke bwd gradients
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(causal=True),
    dict(causal=True, window=16),
    dict(causal=True, prefix_len=24),
], ids=["causal", "window", "prefix"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_flash_fwd_unified_all_backends(kw, backend):
    b, h, hk, s, d = 1, 4, 2, 64, 32
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, hk, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, hk, s, d), jnp.float32)
    got = flash_attention(q, k, v, block_q=16, block_kv=16, backend=backend, **kw)
    ref = mha_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("kw", [
    dict(causal=True),
    dict(causal=True, window=16),
    dict(causal=True, prefix_len=24),
], ids=["causal", "window", "prefix"])
def test_flash_unified_fwd_bespoke_bwd_gradients(kw):
    b, h, s, d = 1, 2, 64, 32
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(b, h, s, d), jnp.float32) for _ in range(3))

    def loss_k(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_kv=16, **kw) ** 2).sum()

    def loss_r(q, k, v):
        return (mha_ref(q, k, v, **kw) ** 2).sum()

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} mismatch ({kw})")


# ---------------------------------------------------------------------------
# persistent autotune cache: warm cache -> zero sweep builds / timings
# ---------------------------------------------------------------------------

def test_persistent_tune_cache_skips_resweep(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(32, 32), jnp.float32)
    b = jnp.asarray(rng.randn(32, 32), jnp.float32)
    sweep = {"bm": [8, 16], "bn": [16]}

    # prune=False: this test pins the exact unpruned trial count
    r1 = matmul.tune((a, b), sweep=sweep, backend="jnp", repeats=1,
                     prune=False)
    assert not r1.cached and len(r1.trials) == 2
    files = list((tmp_path / "autotune").glob("*.json"))
    assert len(files) == 1
    saved = json.loads(files[0].read_text())
    assert saved["op"] == "matmul" and saved["winner"]["bm"] == r1["bm"]

    # "second process": cold kernel caches would rebuild — the persistent
    # cache must answer before any candidate is built or timed
    dev = default_device("jnp", None)
    builds_before, hits_before = dev.stats.builds, dev.stats.cache_hits
    r2 = matmul.tune((a, b), sweep=sweep, backend="jnp", repeats=1)
    assert r2.cached and r2.trials == [] and r2.skipped == []
    assert dev.stats.builds == builds_before
    assert dev.stats.cache_hits == hits_before
    assert r2["bm"] == r1["bm"] and r2["bn"] == r1["bn"]
    assert r2["M"] == 32  # winner merged over the full base defines

    # a different tuning problem (other shape) must miss the cache
    a2 = jnp.asarray(rng.randn(16, 16), jnp.float32)
    r3 = matmul.tune((a2, a2), sweep=sweep, backend="jnp", repeats=1)
    assert not r3.cached

    # so must a NARROWER sweep: candidate sets are part of the identity —
    # a cached winner outside the caller's candidates would be nonsense
    r4 = matmul.tune((a, b), sweep={"bm": [8], "bn": [16]}, backend="jnp",
                     repeats=1)
    assert not r4.cached and r4["bm"] == 8


def test_warm_tune_cache_skips_oracle_too(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    calls = {"n": 0}
    real_ref = matmul.ref

    def counting_ref(*a, **kw):
        calls["n"] += 1
        return real_ref(*a, **kw)

    monkeypatch.setattr(matmul, "ref", counting_ref)
    rng = np.random.RandomState(4)
    a = jnp.asarray(rng.randn(16, 16), jnp.float32)
    sweep = {"bm": [8, 16]}
    matmul.tune((a, a), sweep=sweep, backend="jnp", repeats=1)
    assert calls["n"] == 1  # cold: oracle evaluated once for validation
    r = matmul.tune((a, a), sweep=sweep, backend="jnp", repeats=1)
    assert r.cached and calls["n"] == 1  # warm: no sweep, no oracle


def test_ssm_scan_degradation_guard():
    from repro.kernels.ssm_scan import ssm_scan_pallas

    L = dm = 997  # prime: chunk and d_block would collapse to 1
    x = jnp.zeros((1, L, dm), jnp.float32)
    dt = jnp.zeros((1, L, dm), jnp.float32)
    A = -jnp.ones((dm, 4), jnp.float32)
    B = jnp.zeros((1, L, 4), jnp.float32)
    C = jnp.zeros((1, L, 4), jnp.float32)
    D = jnp.zeros((dm,), jnp.float32)
    with pytest.raises(ValueError, match="degraded"):
        ssm_scan_pallas(x, dt, A, B, C, D)


def test_duplicate_op_name_rejected():
    from repro.core import define_op

    with pytest.raises(ValueError, match="already registered"):
        define_op("matmul", builder=lambda D: None, ref=None,
                  derive_defines=lambda a, p: {})
    # register=False stays out of the registry and out of the collision check
    op = define_op("matmul", builder=lambda D: None, ref=None,
                   derive_defines=lambda a, p: {}, register=False)
    assert op is not registered_ops()["matmul"]


def test_op_tune_validates_against_oracle_and_finite_best_seconds():
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.randn(32, 24), jnp.float32)
    b = jnp.asarray(rng.randn(24, 16), jnp.float32)
    r = matmul.tune((a, b), sweep={"bm": [8, 32], "bk": [8, 24]},
                    backend="jnp", cache=False, repeats=0,  # repeats=0 bugfix
                    prune=False)  # all 4 trials: the repeats=0 path per trial
    assert np.isfinite(r.best_seconds)
    assert len(r.trials) == 4


def _copy_plus_bn_builder(D):
    """Deliberately block-size-dependent (wrong) kernel for validation tests."""

    def body(ctx, x, o):
        o[...] = x[...] + float(D.bn)

    return Spec("buggy", grid=(D.n // D.bn,),
                inputs=[Tile("x", (D.n,), jnp.float32, block=(D.bn,))],
                outputs=[Tile("o", (D.n,), jnp.float32, block=(D.bn,))],
                body=body)


def test_autotune_oracle_catches_first_candidate_bug():
    dev = Device("jnp")
    x = np.zeros(16, np.float32)
    # single candidate: the old first-candidate cross-check self-certifies
    r = autotune(dev, _copy_plus_bn_builder, dict(n=16), sweep={"bn": [4]},
                 args=(x,), repeats=1)
    assert r["bn"] == 4
    # with the oracle declared, the same sweep is rejected
    with pytest.raises(AssertionError):
        autotune(dev, _copy_plus_bn_builder, dict(n=16), sweep={"bn": [4]},
                 args=(x,), repeats=1, ref=lambda x_: x_)


# ---------------------------------------------------------------------------
# host-API guards: cross-device Memory, no-per-op-host-code acceptance
# ---------------------------------------------------------------------------

def test_memory_swap_rejects_cross_device_handles():
    d1, d2 = Device("jnp"), Device("loops")
    m1 = d1.malloc(np.ones(4, np.float32))
    m2 = d2.malloc(np.zeros(4, np.float32))
    with pytest.raises(ValueError, match="different devices"):
        m1.swap(m2)
    m3 = d1.malloc(np.zeros(4, np.float32))
    m1.swap(m3)  # same device still fine
    assert m1.to_host().sum() == 0


def test_kernel_rejects_cross_device_output_memory():
    def builder(D):
        def body(ctx, x, o):
            o[...] = x[...]

        return Spec("copy", grid=(1,),
                    inputs=[Tile("x", (4,), jnp.float32)],
                    outputs=[Tile("o", (4,), jnp.float32)],
                    body=body)

    d1, d2 = Device("jnp"), Device("jnp")
    k = d1.build_kernel(builder, {})
    x = d1.malloc(np.ones(4, np.float32))
    out_foreign = d2.malloc(np.zeros(4, np.float32))
    with pytest.raises(ValueError, match="output Memory belongs"):
        k(x, out_foreign)
    out = d1.malloc(np.zeros(4, np.float32))
    k(x, out)
    np.testing.assert_allclose(out.to_host(), 1.0)


def test_lowered_text_uses_prejitted_kernel():
    def builder(D):
        def body(ctx, x, o):
            o[...] = 2.0 * x[...]

        return Spec("dbl", grid=(1,),
                    inputs=[Tile("x", (4,), jnp.float32)],
                    outputs=[Tile("o", (4,), jnp.float32)],
                    body=body)

    k = Device("jnp").build_kernel(builder, {})
    txt = k.lowered_text(np.ones(4, np.float32))
    assert "module" in txt


def test_flash_bwd_uses_fitted_blocks():
    """Forward fits block sizes to the sequence; the backward must reuse the
    fitted sizes (regression: grad crashed on non-dividing shapes)."""
    # 80 % 64 != 0 -> fit_block degrades to 40 (non-causal: a causal
    # sequence would pad to the block instead)
    b, h, s, d = 1, 2, 80, 32
    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(b, h, s, d), jnp.float32) for _ in range(3))
    gk = jax.grad(lambda q_: (flash_attention(
        q_, k, v, causal=False, block_q=64, block_kv=64) ** 2).sum())(q)
    gr = jax.grad(lambda q_: (mha_ref(q_, k, v, causal=False) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                               rtol=2e-3, atol=2e-3)


def test_array_params_rejected_on_differentiable_path_but_jit_safe_on_raw():
    """h0 cannot thread through custom_vjp statics (regression: silently
    dropped from the backward / tracer-freeze under jit); the functional
    path accepts it, including under jit."""
    from repro.kernels.ssm_scan import (selective_scan_ref, ssm_scan,
                                        ssm_scan_pallas)

    rng = np.random.RandomState(2)
    bt, L, dm, n = 1, 32, 8, 4
    x = jnp.asarray(rng.randn(bt, L, dm), jnp.float32)
    dt = jnp.asarray(np.abs(rng.randn(bt, L, dm)) * 0.1, jnp.float32)
    A = -jnp.asarray(np.abs(rng.randn(dm, n)) + 0.1, jnp.float32)
    B = jnp.asarray(rng.randn(bt, L, n), jnp.float32)
    C = jnp.asarray(rng.randn(bt, L, n), jnp.float32)
    D = jnp.asarray(rng.randn(dm), jnp.float32)
    h = jnp.asarray(rng.randn(bt, dm, n), jnp.float32)

    with pytest.raises(ValueError, match="not differentiable"):
        ssm_scan(x, dt, A, B, C, D, h0=h)

    y, hT = jax.jit(lambda h0: ssm_scan_pallas(
        x, dt, A, B, C, D, h0=h0, chunk=16))(h)
    ref_y, ref_h = selective_scan_ref(x, dt, A, B, C, D, h0=h)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(ref_h),
                               rtol=2e-4, atol=2e-4)


def test_tune_cache_key_separates_interpret_modes():
    from repro.core import tune_cache_key

    d1, _ = tune_cache_key("op", dict(M=8), {"bm": [4]}, "pallas", False)
    d2, _ = tune_cache_key("op", dict(M=8), {"bm": [4]}, "pallas", True)
    assert d1 != d2  # debug sweeps must never answer for the compiled path


def test_unknown_params_rejected():
    with pytest.raises(TypeError, match="unexpected params"):
        matmul(jnp.ones((4, 4)), jnp.ones((4, 4)), blck_m=2)  # typo'd kwarg


def test_ops_are_declarations_not_wrappers():
    """matmul/rmsnorm/ssm_scan/flash_attention ARE Op instances — no per-op
    backend-dispatch or caching code survives in kernels/*/ops.py."""
    for name, op in registered_ops().items():
        assert isinstance(op, Op), name
        assert callable(op.builder) and callable(op.derive_defines), name


# ---------------------------------------------------------------------------
# purity: the tightened CI guard, mirrored as a test (word-boundary
# pallas_call under kernels/; jax.experimental.pallas only under core/)
# ---------------------------------------------------------------------------

def test_kernel_purity_and_pallas_import_containment():
    import pathlib
    import re

    root = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    bespoke, leaked = [], []
    for p in sorted(root.rglob("*.py")):
        rel = p.relative_to(root)
        text = p.read_text()
        # word boundary: catches `pl.pallas_call`, bare `pallas_call` and
        # `from jax.experimental.pallas import pallas_call as pc` aliasing
        if rel.parts[0] == "kernels" and re.search(r"\bpallas_call\b", text):
            bespoke.append(str(rel))
        if rel.parts[0] != "core" and "jax.experimental.pallas" in text:
            leaked.append(str(rel))
    assert bespoke == [], f"bespoke pallas_call sites: {bespoke}"
    assert leaked == [], \
        f"jax.experimental.pallas outside src/repro/core/: {leaked}"


# ---------------------------------------------------------------------------
# REPRO_BACKEND: the CI backend matrix's env pin for backend="auto"
# ---------------------------------------------------------------------------

def test_repro_backend_env_pins_auto(monkeypatch):
    rng = np.random.RandomState(9)
    a = jnp.asarray(rng.randn(14, 14), jnp.float32)  # unique shape: fresh build
    monkeypatch.setenv("REPRO_BACKEND", "loops")
    dev = default_device("loops", None)
    builds_before = dev.stats.builds
    got = matmul(a, a, block_m=7, block_n=7, block_k=14)  # backend="auto"
    assert dev.stats.builds == builds_before + 1  # built on the LOOPS device
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(matmul.reference(a, a)),
                               rtol=1e-4, atol=1e-4)
    # explicit backends are never overridden by the env pin
    got_j = matmul(a, a, block_m=7, block_n=7, block_k=14, backend="jnp")
    np.testing.assert_allclose(np.asarray(got_j), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_repro_backend_env_rejects_unknown(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "cuda")
    with pytest.raises(ValueError, match="REPRO_BACKEND"):
        matmul(jnp.ones((4, 4)), jnp.ones((4, 4)))


# ---------------------------------------------------------------------------
# stream-output validation (the ssm_scan-enabling language extension)
# ---------------------------------------------------------------------------

def test_stream_output_duplicate_block_rejected():
    def bad(D):
        def body(ctx, x, y):
            y[...] = x[...]

        return Spec("bad_stream", grid=(2, 2), reduce_axes=(1,),
                    inputs=[Tile("x", (4, 4), jnp.float32, block=(2, 2),
                                 index=lambda i, r: (i, r))],
                    outputs=[Tile("y", (4, 4), jnp.float32, block=(2, 2),
                                  index=lambda i, r: (i, 0), stream=True)],
                    body=body)

    with pytest.raises(ValueError, match="stream output.*more than once"):
        Device("jnp").build_kernel(bad, {})
