"""The static cost model (PR 7): VMEM budgeting, bytes/FLOPs estimates,
and analysis-driven autotune pruning.

Golden values are closed-form where tractable (matmul) and pinned from the
model elsewhere (flash_decode, lm_head_ce) — a change to the cost rules must
consciously update them. Seeded-defect specs check that VMEM_OVERFLOW blocks
the build on every backend and that REDUNDANT_FETCH fires on a walk that
revisits blocks non-consecutively. The pruning tests assert the load-bearing
contract: pruned candidates are NEVER built, and pruning never changes the
winner (under a deterministic timer).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from types import SimpleNamespace

from repro.core import (BACKENDS, Device, Scratch, Spec, Tile, autotune,
                        estimate_cost, prune_candidates, registered_ops,
                        vmem_budget, vmem_footprint)
from repro.core.analyze import (AnalysisError, DEFAULT_VMEM_BUDGET,
                                NEAR_LIMIT_FRAC)
from repro.core.lang import defines_namespace
from repro.kernels.flash_attention.kernel import flash_decode_builder
from repro.kernels.lm_head.kernel import lm_head_builder
from repro.kernels.matmul import matmul, matmul_builder

import repro.kernels  # noqa: F401 — registers the op families


# ---------------------------------------------------------------------------
# golden bytes/FLOPs/footprint at fixed shapes
# ---------------------------------------------------------------------------

def _matmul_defines(n=64, b=32):
    return dict(M=n, K=n, N=n, bm=b, bk=b, bn=b, dtype="float32")


def test_matmul_golden_cost():
    # M=N=K=64, 32^3 blocks, f32. Closed forms:
    #   flops     = 2*M*N*K (dot) + M*N*(K/bk) (accumulate) = 532480
    #   bytes_in  = 4*M*N*K/bn + 4*M*N*K/bm   (a and b refetch per j / per i)
    #   bytes_out = 4*M*N                      (c written once per (i, j))
    #   vmem      = 2*(bm*bk + bk*bn + bm*bn)*4 + bm*bn*4 (f32 scratch)
    D = _matmul_defines()
    rep = estimate_cost(matmul_builder(defines_namespace(D)),
                        defines_namespace(D))
    assert rep.flops == 2 * 64**3 + 64 * 64 * 2 == 532480
    assert rep.bytes_in == 4 * 64**3 // 32 * 2 == 65536
    assert rep.bytes_out == 4 * 64 * 64 == 16384
    assert rep.vmem_bytes == 3 * 2 * 32 * 32 * 4 + 32 * 32 * 4 == 28672
    assert rep.hbm_bytes == rep.bytes_in + rep.bytes_out
    assert rep.intensity == pytest.approx(rep.flops / rep.hbm_bytes)
    assert rep.findings == []


def test_flash_decode_golden_cost():
    # one cell per (sequence, kv block) holding both KV heads: each K/V
    # block is fetched once (2 x 4 blocks of 2 x 128 x 32 f32 = 262144 B),
    # not once per query head
    D = dict(b=1, h=4, hk=2, hkc=2, skv=512, d=32, dv=32, block_kv=128,
             window=None, sm_scale=float(1 / np.sqrt(32)), dtype="float32")
    rep = estimate_cost(flash_decode_builder(defines_namespace(D)),
                        defines_namespace(D))
    assert rep.vmem_bytes == 137732
    assert rep.bytes_in == 264708
    assert rep.bytes_out == 512
    assert rep.flops == 273616
    assert rep.findings == []


def test_lm_head_ce_golden_cost():
    D = dict(R=256, d=128, V=512, vocab=500, block_r=128, block_v=256,
             block_k=128, emit_logits=False, dtype="float32")
    rep = estimate_cost(lm_head_builder(defines_namespace(D)),
                        defines_namespace(D))
    assert rep.vmem_bytes == 723968
    assert rep.bytes_in == 656384
    assert rep.bytes_out == 2048
    assert rep.flops == 34344448
    assert rep.findings == []


def test_registry_default_configs_cost_clean():
    """Every registered op's default derived config passes the cost model
    with zero findings — the shipped registry fits the default VMEM budget."""
    for name, op in sorted(registered_ops().items()):
        args, params = op.example(np.random.RandomState(0))
        _, _, params = op._resolve(params)
        _, defines, _ = op._prepare(tuple(args), params)
        rep = estimate_cost(op.builder(defines_namespace(defines)),
                            defines_namespace(defines))
        assert rep.findings == [], (name, rep.findings)
        assert rep.vmem_bytes <= NEAR_LIMIT_FRAC * DEFAULT_VMEM_BUDGET, name


# ---------------------------------------------------------------------------
# seeded defects: VMEM_OVERFLOW and REDUNDANT_FETCH
# ---------------------------------------------------------------------------

def _whole_array_builder(D):
    """One grid cell, whole-array tiles: footprint = 2 * n * n * 4 bytes."""
    def body(ctx, x, y):
        y[...] = x[...] * 2.0
    n = D.n
    return Spec(
        "whole", grid=(1,),
        inputs=[Tile("x", (n, n), jnp.float32, block=(n, n),
                     index=lambda i: (0, 0))],
        outputs=[Tile("y", (n, n), jnp.float32, block=(n, n),
                      index=lambda i: (0, 0))],
        body=body)


@pytest.mark.parametrize("backend", BACKENDS)
def test_seeded_vmem_overflow_rejected_on_build(backend):
    # 3000*3000*4 = 36 MB per tile, 72 MB resident > the 16 MB budget:
    # the BUILD must refuse on every backend, not just the pallas one.
    with pytest.raises(AnalysisError, match="VMEM_OVERFLOW"):
        Device(backend).build_kernel(_whole_array_builder, dict(n=3000))


def test_vmem_overflow_is_static():
    total, detail = vmem_footprint(
        _whole_array_builder(SimpleNamespace(n=3000)))
    assert total == 2 * 3000 * 3000 * 4
    assert set(detail) == {"x", "y"}
    rep = estimate_cost(_whole_array_builder(SimpleNamespace(n=3000)),
                        flops=False)
    assert [f.code for f in rep.findings] == ["VMEM_OVERFLOW"]
    assert rep.findings[0].severity == "error"


def test_vmem_budget_env_override(monkeypatch):
    monkeypatch.delenv("REPRO_VMEM_BUDGET", raising=False)
    assert vmem_budget() == DEFAULT_VMEM_BUDGET
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "128M")
    assert vmem_budget() == 128 * 2**20
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "2G")
    assert vmem_budget() == 2 * 2**30
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "4096")
    assert vmem_budget() == 4096
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "64K")
    assert vmem_budget() == 64 * 2**10
    for bad in ("garbage", "-1", "0", "1.5M"):
        monkeypatch.setenv("REPRO_VMEM_BUDGET", bad)
        with pytest.raises(ValueError):
            vmem_budget()


def test_raised_budget_admits_oversized_spec(monkeypatch):
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "128M")
    k = Device("jnp").build_kernel(_whole_array_builder, dict(n=3000))
    out, = k.run(jnp.ones((3000, 3000), jnp.float32))
    assert float(out[0, 0]) == 2.0


def _refetch_builder(D):
    """Reduce sweep kk = 0..3 whose input map revisits block kk % 2: each
    outer cell fetches blocks 0,1,0,1 — 4 runs over 2 distinct blocks — a
    seeded refetch (the map moves off a block it needs again)."""
    def body(ctx, x, y):
        y[...] = x[...][:1]
    return Spec(
        "refetch", grid=(2, 4), reduce_axes=(1,),
        inputs=[Tile("x", (8, 4), jnp.float32, block=(2, 4),
                     index=lambda i, kk: (kk % 2, 0))],
        outputs=[Tile("y", (2, 4), jnp.float32, block=(1, 4),
                      index=lambda i, kk: (i, 0))],
        body=body)


def test_seeded_redundant_fetch_flagged():
    rep = estimate_cost(_refetch_builder(SimpleNamespace()), flops=False)
    codes = [f.code for f in rep.findings]
    assert "REDUNDANT_FETCH" in codes
    # 8 runs of a 2x4 f32 block: the refetches are costed, not just flagged
    assert rep.bytes_in == 8 * 2 * 4 * 4


# ---------------------------------------------------------------------------
# autotune pruning: pruned candidates never build, the winner never changes
# ---------------------------------------------------------------------------

def _model_timer(kernel, args, *, warmup=1, repeats=3):
    """Deterministic stand-in for ``_time_once``: seconds proportional to the
    static model's cost terms. A dominated candidate (>= on both terms, one
    strict) always times strictly worse, so pruning must not change the
    winner — which is exactly the contract under test."""
    rep = estimate_cost(kernel.spec, defines_namespace(kernel.defines))
    out = kernel.run(*args)
    return (rep.hbm_bytes + (rep.flops or 0)) * 1e-12, out


def test_autotune_prunes_dominated_never_builds_them(monkeypatch):
    monkeypatch.setattr("repro.core.tune._time_once", _model_timer)
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(64, 64), jnp.float32)
    b = jnp.asarray(rng.randn(64, 64), jnp.float32)
    defines = _matmul_defines()
    sweep = dict(bm=[32, 64], bn=[32, 64], bk=[32, 64])

    dev = Device("jnp")
    r = autotune(dev, matmul_builder, defines, sweep=sweep, args=(a, b),
                 repeats=1, cache=False)
    # bk=32 costs extra accumulate flops at equal bytes, and bm=bn=32 moves
    # strictly more bytes: 5 of 8 combos are dominated. The three bk=64
    # combos with a 64 block on bm or bn tie exactly (same bytes AND flops),
    # so dominance must NOT prune them — ties race it out on the clock.
    assert r["bk"] == 64 and 64 in (r["bm"], r["bn"])
    assert len(r.pruned) == 5 and len(r.trials) == 3
    assert all("prune[DOMINATED]" in reason for _, reason in r.pruned)
    # pruned candidates were never built: only the kept three were
    assert dev.stats.builds == 3

    dev2 = Device("jnp")
    r2 = autotune(dev2, matmul_builder, defines, sweep=sweep, args=(a, b),
                  repeats=1, cache=False, prune=False)
    assert dev2.stats.builds == 8 and len(r2.trials) == 8
    assert {k: r2[k] for k in sweep} == {k: r[k] for k in sweep}


def test_autotune_all_pruned_is_a_clear_error():
    defines = _matmul_defines()
    with pytest.raises(ValueError, match="statically pruned"):
        autotune(Device("jnp"), matmul_builder, defines,
                 sweep=dict(bm=[32, 64], bn=[32, 64], bk=[32, 64]),
                 args=(jnp.zeros((64, 64)), jnp.zeros((64, 64))),
                 budget=1024)  # nothing fits a 1 KB budget


def test_prune_candidates_vmem_reasons():
    kept, pruned = prune_candidates(
        matmul_builder, _matmul_defines(),
        dict(bm=[32, 64], bn=[32], bk=[32]), budget=25000)
    # bm=64 needs 2*(64*32)*4*... > 25000; bm=32 fits (28672 > 25000? no --
    # recompute: bm=32 footprint is 28672, so BOTH overflow a 25 KB budget)
    assert kept == []
    assert len(pruned) == 2
    assert all("prune[VMEM_OVERFLOW]" in r for _, r in pruned)

    kept, pruned = prune_candidates(
        matmul_builder, _matmul_defines(),
        dict(bm=[32, 64], bn=[32], bk=[32]), budget=DEFAULT_VMEM_BUDGET)
    assert len(kept) == 1 and kept[0]["bm"] == 64
    assert len(pruned) == 1 and "prune[DOMINATED]" in pruned[0][1]


def test_op_tune_prunes_flash_decode_same_winner(monkeypatch):
    monkeypatch.setattr("repro.core.tune._time_once", _model_timer)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 4, 1, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 512, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 512, 32), jnp.float32)
    op = registered_ops()["flash_decode"]
    r = op.tune((q, k, v), backend="jnp", cache=False, repeats=1)
    r2 = op.tune((q, k, v), backend="jnp", cache=False, repeats=1,
                 prune=False)
    assert len(r.pruned) > 0
    assert len(r.trials) + len(r.pruned) >= len(r2.trials)
    assert r["block_kv"] == r2["block_kv"]


def test_op_tune_prunes_lm_head_ce_same_winner(monkeypatch):
    monkeypatch.setattr("repro.core.tune._time_once", _model_timer)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(256, 128), jnp.float32)
    w = jnp.asarray(rng.randn(128, 512), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 500, (256, 1)), jnp.int32)
    op = registered_ops()["lm_head_ce"]
    r = op.tune((x, w, labels), backend="jnp", cache=False, repeats=1,
                vocab=500)
    r2 = op.tune((x, w, labels), backend="jnp", cache=False, repeats=1,
                 vocab=500, prune=False)
    assert len(r.pruned) > 0
    assert {k: r[k] for k in op.sweep} == {k: r2[k] for k in op.sweep}


# ---------------------------------------------------------------------------
# winner hygiene: eviction and adoption under the budget
# ---------------------------------------------------------------------------

def test_lint_evicts_overflowing_persisted_winner(tmp_path, monkeypatch,
                                                  capsys):
    """A persisted winner whose footprint exceeds the CURRENT budget is
    flagged by ``tune_cli --lint`` and removed by ``--evict``."""
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(64, 64), jnp.float32)
    b = jnp.asarray(rng.randn(64, 64), jnp.float32)
    r = matmul.tune((a, b), backend="jnp", repeats=1,
                    sweep=dict(bm=[32], bn=[32], bk=[32]))
    assert r["bm"] == 32
    root = tmp_path / "autotune"
    assert len(list(root.glob("*.json"))) == 1

    from repro.tune_cli import main as tune_main
    assert tune_main(["--lint"]) == 0  # fits the default budget: clean

    monkeypatch.setenv("REPRO_VMEM_BUDGET", "16K")  # winner needs 28672 B
    assert tune_main(["--lint"]) == 1
    assert "VMEM_OVERFLOW" in capsys.readouterr().out
    assert tune_main(["--lint", "--evict"]) == 0
    assert list(root.glob("*.json")) == []


def test_adopt_winners_skips_overflowing_winner(tmp_path, monkeypatch):
    from repro.launch.tuning import adopt_winners

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(64, 64), jnp.float32)
    b = jnp.asarray(rng.randn(64, 64), jnp.float32)
    # the op's own sweep + backend key the cache entry cached_winner looks up
    r = matmul.tune((a, b), backend="jnp", repeats=1)
    import jax
    probe = jax.ShapeDtypeStruct
    probes = {"matmul": ((probe((64, 64), jnp.float32),
                          probe((64, 64), jnp.float32)),
                         dict(backend="jnp"))}
    saved = dict(matmul.defaults)
    try:
        applied = adopt_winners(probes)
        assert applied.get("matmul") == {k: r[k] for k in matmul.sweep}
        matmul.defaults.clear()
        matmul.defaults.update(saved)
        # every surviving 64^3 candidate needs > 16 KB resident VMEM: under
        # a 16K budget the persisted winner must NOT be adopted
        monkeypatch.setenv("REPRO_VMEM_BUDGET", "16K")
        applied = adopt_winners(probes)
        assert "matmul" not in applied
    finally:
        matmul.defaults.clear()
        matmul.defaults.update(saved)


# ---------------------------------------------------------------------------
# roofline report guards (satellite): corrupt artifacts, missing dirs
# ---------------------------------------------------------------------------

def test_roofline_skips_corrupt_artifacts(tmp_path, capsys):
    import json
    import sys
    sys.path.insert(0, ".")
    from benchmarks import roofline

    (tmp_path / "bad.json").write_text("{not json")
    (tmp_path / "list.json").write_text("[1, 2]")
    (tmp_path / "ok.json").write_text(json.dumps(dict(
        arch="llama3_2_1b", shape="decode_32k", mesh="1x1", kind="decode",
        chips=0, extrapolated=dict(flops=0.0, bytes_accessed=0.0,
                                   collective_total_bytes=0.0))))
    recs = roofline.load(str(tmp_path))
    assert len(recs) == 1
    out = capsys.readouterr().out
    assert "skipping" in out
    # zero chips + zero-byte terms: analyzed without a divide-by-zero crash
    a = roofline.analyze(recs[0])
    assert a["useful_ratio"] == 0.0 and a["roofline_fraction"] == 0.0
    md = roofline.markdown_table(recs)
    assert "llama3_2_1b" in md


def test_roofline_missing_dir_clear_exit(tmp_path, capsys):
    import sys
    sys.path.insert(0, ".")
    from benchmarks import roofline

    assert roofline.main(["--dir", str(tmp_path / "nope")]) == 1
    assert "no dry-run artifacts" in capsys.readouterr().out


def test_roofline_markdown_bare_filename(tmp_path, monkeypatch):
    import json
    import sys
    sys.path.insert(0, ".")
    from benchmarks import roofline

    (tmp_path / "a.json").write_text(json.dumps(dict(
        arch="llama3_2_1b", shape="decode_32k", mesh="1x1", kind="decode",
        chips=1, extrapolated=dict(flops=1e12, bytes_accessed=1e9,
                                   collective_total_bytes=0.0))))
    monkeypatch.chdir(tmp_path)
    # a bare filename has an empty dirname: must not crash on makedirs("")
    assert roofline.main(["--dir", str(tmp_path),
                          "--markdown", "out.md"]) == 0
    assert (tmp_path / "out.md").exists()
