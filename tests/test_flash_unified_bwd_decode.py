"""The finished flash-attention port: unified backward + decode, plus the
language extension that enabled it.

Covers the PR-3 surface: ``Tile(reduce=...)`` per-output reduce granularity
(one kernel whose outputs accumulate over different subsets of the reduce
axes, on all three backends, plus its build-time validation),
``ctx.reduce_first/reduce_last``, flash-attention gradients through the ONE
fused dq/dk/dv unified kernel vs the oracle on jnp/loops/pallas,
``flash_decode`` edge cases (GQA head-group mapping, window smaller than a
kv block, non-dividing cache lengths, dynamic ``kv_len`` under jit), the
kernel-library purity contract (zero bespoke ``pallas_call`` sites), the
versioned autotune cache (stale/corrupt/mismatched entries are EVICTED, not
crashed on or reused), and the serving warmup that adopts persisted tune
winners through the op registry.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BACKENDS, Device, Op, SCHEMA_VERSION, Scratch, Spec,
                        Tile, default_device, registered_ops, tune_cache_key)
from repro.kernels.flash_attention import (decode_attention, decode_ref,
                                           flash_attention, flash_decode,
                                           mha_ref)

import repro.kernels  # noqa: F401 — registers every op


# ---------------------------------------------------------------------------
# Tile(reduce=...): per-output reduce granularity
# ---------------------------------------------------------------------------

def granularity_builder(D):
    """One kernel, three output granularities over grid (no, n0, n1) with
    reduce axes (1, 2): ``tot`` accumulates over both (scratch + is_last
    flush), ``per0`` keeps one block per n0 step and accumulates over n1 only
    (read-modify-write on its revisited block), ``strm`` streams one block
    per cell."""

    def body(ctx, x, tot, per0, strm):
        acc, = ctx.scratch
        s = x[...].sum()

        @ctx.when(ctx.is_first)
        def _init_tot():
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

        @ctx.when(ctx.reduce_first(1))
        def _init_per0():
            per0[...] = jnp.zeros(per0.shape, jnp.float32)

        acc[...] = acc[...] + s
        per0[...] = per0[...] + s
        strm[...] = jnp.full((1, 1, 1), s)

        @ctx.when(ctx.is_last)
        def _fin():
            tot[...] = acc[...]

    no, n0, n1, bn = D.no, D.n0, D.n1, D.bn
    return Spec(
        "granularity", grid=(no, n0, n1), reduce_axes=(1, 2),
        scratch=[Scratch((1,), jnp.float32)],
        inputs=[Tile("x", (no, n0, n1 * bn), jnp.float32, block=(1, 1, bn),
                     index=lambda o, a, b: (o, a, b))],
        outputs=[
            Tile("tot", (no,), jnp.float32, block=(1,),
                 index=lambda o, a, b: (o,)),
            Tile("per0", (no, n0), jnp.float32, block=(1, 1),
                 index=lambda o, a, b: (o, a), reduce=(2,)),
            Tile("strm", (no, n0, n1), jnp.float32, block=(1, 1, 1),
                 index=lambda o, a, b: (o, a, b), stream=True),
        ],
        body=body)


@pytest.mark.parametrize("backend", BACKENDS)
def test_per_output_reduce_granularity_matches_numpy(backend):
    no, n0, n1, bn = 2, 3, 4, 5
    x = np.random.RandomState(0).randn(no, n0, n1 * bn).astype(np.float32)
    k = Device(backend).build_kernel(granularity_builder,
                                     dict(no=no, n0=n0, n1=n1, bn=bn))
    tot, per0, strm = [np.asarray(o) for o in k.run(x)]
    x4 = x.reshape(no, n0, n1, bn)
    np.testing.assert_allclose(tot, x.sum(axis=(1, 2)), rtol=1e-5)
    np.testing.assert_allclose(per0, x4.sum(axis=(2, 3)), rtol=1e-5)
    np.testing.assert_allclose(strm, x4.sum(axis=3), rtol=1e-5)


def _one_out_spec(tile):
    def body(ctx, x, y):
        y[...] = x[...]

    return Spec("g", grid=(2, 2, 2), reduce_axes=(1, 2),
                inputs=[Tile("x", (2, 2, 2), jnp.float32, block=(1, 1, 1),
                             index=lambda o, a, b: (o, a, b))],
                outputs=[tile], body=body)


def test_tile_reduce_must_be_subset_of_reduce_axes():
    with pytest.raises(ValueError, match="not a subset"):
        _one_out_spec(Tile("y", (2, 2), jnp.float32, block=(1, 1),
                           index=lambda o, a, b: (o, a), reduce=(0,)))


def test_tile_reduce_conflicts_with_stream():
    with pytest.raises(ValueError, match="stream=True means reduce=()"):
        _one_out_spec(Tile("y", (2, 2), jnp.float32, block=(1, 1),
                           index=lambda o, a, b: (o, a), reduce=(1,),
                           stream=True))


def test_index_map_must_not_use_accumulated_axes():
    # y accumulates over axis 2 but its index map uses axis 2's id
    with pytest.raises(ValueError, match="depends on reduce"):
        _one_out_spec(Tile("y", (2, 2), jnp.float32, block=(1, 1),
                           index=lambda o, a, b: (o, b), reduce=(2,)))


def test_partial_reduce_blocks_must_cover_output():
    # y has 4 blocks but (outer x slot-axis) cells only visit 2 of them
    with pytest.raises(ValueError, match="blocks visited but"):
        _one_out_spec(Tile("y", (2, 4), jnp.float32, block=(1, 1),
                           index=lambda o, a, b: (o, a), reduce=(2,)))


def test_compiled_pallas_refuses_non_consecutive_accumulation():
    """Accumulating over an axis OUTER to the slot axis revisits a block on
    non-consecutive grid steps: exact on jnp/loops/interpret, but a compiled
    TPU pipeline never re-reads a written-back output block — the compiled
    expansion refuses it at build time (the old fused flash dk/dv pattern)."""
    from repro.core.lang import defines_namespace, expand

    bad = _one_out_spec(Tile("y", (2, 2), jnp.float32, block=(1, 1),
                             index=lambda o, a, b: (o, b), reduce=(1,)))
    D = defines_namespace({})
    expand(bad, D, "pallas", interpret=True)         # interpreter: fine
    with pytest.raises(ValueError, match="non-consecutively"):
        expand(bad, D, "pallas", interpret=False)
    good = granularity_builder(defines_namespace(dict(no=2, n0=3, n1=4,
                                                      bn=5)))
    expand(good, D, "pallas", interpret=False)       # inner accumulation ok


# ---------------------------------------------------------------------------
# flash backward: ONE fused dq/dk/dv kernel vs the oracle, every backend
# ---------------------------------------------------------------------------

def _grad_pair(kw, backend, *, h=2, hk=2, d=32, dv=32, s=64, dtype=jnp.float32,
               seed=7):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(1, h, s, d), dtype)
    k = jnp.asarray(rng.randn(1, hk, s, d), dtype)
    v = jnp.asarray(rng.randn(1, hk, s, dv), dtype)

    def loss_k(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_kv=16,
                                backend=backend, **kw) ** 2).sum()

    def loss_r(q, k, v):
        return (mha_ref(q, k, v, **kw) ** 2).sum()

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    return gk, gr


@pytest.mark.parametrize("kw", [
    dict(causal=True),
    dict(causal=True, window=16),
    dict(causal=True, prefix_len=24),
    dict(causal=False),
], ids=["causal", "window", "prefix", "dense"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_flash_bwd_unified_matches_oracle_all_backends(kw, backend):
    gk, gr = _grad_pair(kw, backend)
    for name, a, b_ in zip("qkv", gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} mismatch ({kw}, {backend})")


@pytest.mark.parametrize("h,hk,d,dv", [
    (4, 2, 32, 32),     # GQA group reduction
    (4, 1, 32, 32),     # MQA
    (2, 2, 64, 32),     # MLA dims (dqk != dv)
])
@pytest.mark.parametrize("backend", BACKENDS)
def test_flash_bwd_unified_gqa_and_mla_dims(h, hk, d, dv, backend):
    gk, gr = _grad_pair(dict(causal=True), backend, h=h, hk=hk, d=d, dv=dv)
    for name, a, b_ in zip("qkv", gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} mismatch ({backend})")


def test_flash_bwd_bf16():
    gk, gr = _grad_pair(dict(causal=True), "jnp", dtype=jnp.bfloat16)
    for name, a, b_ in zip("qkv", gk, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   rtol=5e-2, atol=5e-2,
                                   err_msg=f"d{name} bf16 mismatch")


# ---------------------------------------------------------------------------
# flash_decode: the registered op, edge cases, every backend
# ---------------------------------------------------------------------------

def test_flash_decode_is_a_registered_op():
    assert isinstance(registered_ops()["flash_decode"], Op)
    assert registered_ops()["flash_decode"] is flash_decode


@pytest.mark.parametrize("backend", BACKENDS)
def test_flash_decode_gqa_head_group_mapping(backend):
    b, h, hk, s, d = 2, 8, 2, 128, 32    # 4 query heads share each kv head
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, hk, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, hk, s, d), jnp.float32)
    got = decode_attention(q, k, v, block_kv=32, backend=backend)
    np.testing.assert_allclose(np.asarray(got), np.asarray(decode_ref(q, k, v)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_flash_decode_window_smaller_than_kv_block(backend):
    b, h, s, d = 1, 2, 128, 32
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    got = decode_attention(q, k, v, window=7, block_kv=64, backend=backend)
    ref = decode_ref(q, k, v, window=7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_flash_pads_ragged_causal_sequence(backend):
    """A causal self-attention longer than a block pads to the block inside
    the op (a TPU block must be (8, 128)-aligned or span the axis): the
    kernel sees 256 rows for 200, and output, lse and grads are exact."""
    from repro.kernels.flash_attention import flash_attention_fwd

    b, h, hk, s, d = 1, 4, 2, 200, 32
    rng = np.random.RandomState(12)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k, v = (jnp.asarray(rng.randn(b, hk, s, d), jnp.float32)
            for _ in range(2))
    kw = dict(block_q=64, block_kv=128, backend=backend)
    _, _, params = flash_attention._resolve(kw)
    (qp, kp, vp), defines, _ = flash_attention._prepare((q, k, v), params)
    assert qp.shape[2] == kp.shape[2] == 256
    assert (defines["block_q"], defines["block_kv"]) == (64, 128)
    o = flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(mha_ref(q, k, v)),
                               rtol=2e-4, atol=2e-4)
    o2, lse = flash_attention_fwd(q, k, v, **kw)
    assert o2.shape == o.shape and lse.shape == (b, h, s)
    got = jax.grad(lambda *a: (flash_attention(*a, **kw) ** 2).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (mha_ref(*a) ** 2).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("backend", BACKENDS)
def test_flash_decode_non_dividing_block_kv(backend):
    b, h, s, d = 1, 2, 96, 32            # 96 % 64 != 0 -> fit_block -> 48
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    got = decode_attention(q, k, v, block_kv=64, backend=backend)
    np.testing.assert_allclose(np.asarray(got), np.asarray(decode_ref(q, k, v)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_flash_decode_partial_cache_kv_len(backend):
    b, h, s, d = 1, 2, 128, 32
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    for n in (1, 33, 128):               # one token / mid-block / full
        got = decode_attention(q, k, v, kv_len=n, block_kv=32, backend=backend)
        ref = decode_ref(q, k[:, :, :n], v[:, :, :n])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4, err_msg=f"kv_len={n}")


def test_flash_decode_traced_kv_len_one_compiled_kernel():
    """The decode loop's growing length is a TRACED input, not a recompile."""
    b, h, s, d = 1, 2, 64, 16
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)

    @jax.jit
    def step(n):
        return decode_attention(q, k, v, kv_len=n, block_kv=16, backend="jnp")

    for n in (5, 17, 64):
        ref = decode_ref(q, k[:, :, :n], v[:, :, :n])
        np.testing.assert_allclose(np.asarray(step(jnp.int32(n))),
                                   np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_gqa_decode_layer_uses_registered_op():
    """The attention layer's pallas decode path equals its einsum path."""
    from repro.configs import get_config, reduced
    from repro.layers import attention as A
    from repro.layers.common import use_kernel_backend

    cfg = reduced(get_config("llama3_2_1b"))
    params = A.gqa_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    b, s = 2, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model))
    outs = {}
    for be in ("jnp", "pallas"):
        with use_kernel_backend(be):
            _, (k, v) = A.gqa_forward(params, x, cfg, return_kv=True)
            cache = A.gqa_prefill_cache(
                A.gqa_cache_init(cfg, b, s + 4, jnp.float32), k, v, cfg)
            ys, xt = [], x[:, -1:]
            for _ in range(3):
                yt, cache = A.gqa_decode(params, xt, cache, cfg)
                ys.append(yt)
            outs[be] = np.asarray(jnp.concatenate(ys, 1))
    np.testing.assert_allclose(outs["pallas"], outs["jnp"],
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# purity: the unified language is the only way to write a kernel
# ---------------------------------------------------------------------------

def test_kernel_library_has_zero_bespoke_pallas_calls():
    import pathlib

    root = pathlib.Path(repro.kernels.__file__).parent
    offenders = [str(p) for p in sorted(root.rglob("*.py"))
                 if "pl.pallas_call" in p.read_text()]
    assert offenders == [], f"bespoke pallas_call sites: {offenders}"


# ---------------------------------------------------------------------------
# autotune cache versioning + eviction
# ---------------------------------------------------------------------------

def _entry_path(tmp_path, op, args, sweep):
    """The cache file a tune of (op, args, sweep) reads/writes."""
    params = dict(op.defaults)
    defines = op.derive_defines(args, params)
    dev = default_device("jnp", None)
    digest, _ = tune_cache_key(op.name, defines, sweep, dev.backend,
                               dev.interpret)
    return tmp_path / "autotune" / f"{digest}.json"


def test_stale_schema_entries_evicted_not_reused(tmp_path, monkeypatch):
    from repro.kernels.matmul import matmul

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(32, 32), jnp.float32)
    sweep = {"bm": [8, 16]}
    r1 = matmul.tune((a, a), sweep=sweep, backend="jnp", repeats=1)
    assert not r1.cached
    path = _entry_path(tmp_path, matmul, (a, a), sweep)
    assert path.exists()

    # stamp an old schema version: the entry must be EVICTED (deleted) and
    # the tune re-swept — not crashed on, not silently reused
    entry = json.loads(path.read_text())
    entry["schema"] = SCHEMA_VERSION - 1
    entry["winner"] = {"bm": "bogus"}
    path.write_text(json.dumps(entry))
    assert matmul.cached_winner((a, a), sweep=sweep, backend="jnp") is None
    assert not path.exists()

    r2 = matmul.tune((a, a), sweep=sweep, backend="jnp", repeats=1,
                     prune=False)
    assert not r2.cached and len(r2.trials) == 2
    assert json.loads(path.read_text())["schema"] == SCHEMA_VERSION


def test_corrupt_and_mismatched_entries_evicted(tmp_path, monkeypatch):
    from repro.kernels.matmul import matmul

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.randn(16, 16), jnp.float32)
    sweep = {"bm": [8, 16]}
    matmul.tune((a, a), sweep=sweep, backend="jnp", repeats=1)
    path = _entry_path(tmp_path, matmul, (a, a), sweep)

    # corrupt JSON -> evicted
    path.write_text("{not json")
    assert matmul.cached_winner((a, a), sweep=sweep, backend="jnp") is None
    assert not path.exists()

    # winner missing a swept key -> evicted
    matmul.tune((a, a), sweep=sweep, backend="jnp", repeats=1)
    entry = json.loads(path.read_text())
    del entry["winner"]["bm"]
    path.write_text(json.dumps(entry))
    assert matmul.cached_winner((a, a), sweep=sweep, backend="jnp") is None
    assert not path.exists()

    # payload disagreeing with its digest (hand-edited file) -> evicted
    matmul.tune((a, a), sweep=sweep, backend="jnp", repeats=1)
    entry = json.loads(path.read_text())
    entry["defines"]["M"] = "999"
    path.write_text(json.dumps(entry))
    assert matmul.cached_winner((a, a), sweep=sweep, backend="jnp") is None
    assert not path.exists()


def test_cached_winner_is_a_pure_lookup(tmp_path, monkeypatch):
    from repro.kernels.matmul import matmul

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    rng = np.random.RandomState(2)
    a = jnp.asarray(rng.randn(32, 32), jnp.float32)
    sweep = {"bm": [8, 16]}
    assert matmul.cached_winner((a, a), sweep=sweep, backend="jnp") is None
    r = matmul.tune((a, a), sweep=sweep, backend="jnp", repeats=1)

    dev = default_device("jnp", None)
    builds = dev.stats.builds
    hits = dev.stats.cache_hits
    winner = matmul.cached_winner((a, a), sweep=sweep, backend="jnp")
    assert winner == {"bm": r["bm"]}
    assert dev.stats.builds == builds and dev.stats.cache_hits == hits


def test_serve_warmup_adopts_persisted_winner(tmp_path, monkeypatch):
    from repro.configs import get_config, reduced
    from repro.launch.serve import apply_tuned_winners

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    cfg = reduced(get_config("llama3_2_1b"))
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, plen, max_len = 2, 16, 256
    assert apply_tuned_winners(cfg, b, plen, max_len) == {}  # cold: no winners

    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(b, h, 1, hd), jnp.float32)
    k = jnp.asarray(rng.randn(b, hk, max_len, hd), jnp.float32)
    v = jnp.asarray(rng.randn(b, hk, max_len, hd), jnp.float32)
    old_default = flash_decode.defaults["block_kv"]
    try:
        r = flash_decode.tune((q, k, v), repeats=1)  # declared sweep, persisted
        adopted = apply_tuned_winners(cfg, b, plen, max_len)
        assert adopted["flash_decode"]["block_kv"] == r["block_kv"]
        assert flash_decode.defaults["block_kv"] == r["block_kv"]

        # the LAYER call path (decode_attention with no explicit block_kv)
        # must build with the adopted winner, not a wrapper-level hardcode
        derived = {}
        orig = flash_decode.derive_defines
        monkeypatch.setattr(
            flash_decode, "derive_defines",
            lambda a, p: derived.setdefault("D", orig(a, p)))
        decode_attention(q, k, v, backend="jnp")
        assert derived["D"]["block_kv"] == r["block_kv"]
    finally:
        flash_decode.defaults["block_kv"] = old_default
