"""Halo/overlap input tiles: the test matrix for ``Tile(halo=..., wrap=...)``.

The language fetches each input block plus a per-axis fringe (periodic wrap
or edge clamp) on ALL THREE backend expansions — the OCCA "shared memory with
halo" stencil pattern, portable by construction. The matrix covers: wrap vs
clamp correctness against a numpy oracle, halo radius larger than the block,
asymmetric halos, structural misuse (ValueError at Tile/Spec construction),
out-of-bounds halos (analyzer error on every backend), and the cost model's
halo amplification — pinned golden for the fd2d window bytes.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AnalysisError, BACKENDS, Device, Spec, Tile,
                        estimate_cost)
from repro.apps.fd2d import fd2d_builder


# ---------------------------------------------------------------------------
# oracle + a minimal halo kernel
# ---------------------------------------------------------------------------

def _pad_oracle(u, r, wrap):
    """numpy: the (h + 2r0, w + 2r1) padded field a halo fetch must see."""
    mode = "wrap" if wrap else "edge"
    return np.pad(np.asarray(u), [(ri, ri) for ri in r], mode=mode)


def window_sum_builder(D):
    """out[i, j] = sum of the (2 r0 + 1) x (2 r1 + 1) window around (i, j)."""
    r0, r1, bh, bw = D.r0, D.r1, D.bh, D.bw

    def body(ctx, u, out):
        win = u[...]                        # (bh + 2 r0, bw + 2 r1)
        acc = jnp.zeros((bh, bw), jnp.float32)
        for di in range(2 * r0 + 1):
            for dj in range(2 * r1 + 1):
                acc = acc + win[di:di + bh, dj:dj + bw]
        out[...] = acc

    return Spec(
        "window_sum", grid=(D.h // bh, D.w // bw),
        inputs=[Tile("u", (D.h, D.w), jnp.float32, block=(bh, bw),
                     halo=(r0, r1), wrap=D.wrap)],
        outputs=[Tile("out", (D.h, D.w), jnp.float32, block=(bh, bw))],
        body=body)


def window_sum_ref(u, r0, r1, wrap):
    pad = _pad_oracle(u, (r0, r1), wrap)
    h, w = u.shape
    acc = np.zeros((h, w), np.float32)
    for di in range(2 * r0 + 1):
        for dj in range(2 * r1 + 1):
            acc += pad[di:di + h, dj:dj + w]
    return acc


# ---------------------------------------------------------------------------
# correctness matrix: wrap x clamp x block shapes x radii, all backends
# ---------------------------------------------------------------------------

CASES = [
    # (h, w, bh, bw, r0, r1)
    (12, 16, 4, 8, 1, 1),     # blocks divide, symmetric small halo
    (12, 16, 4, 8, 2, 3),     # asymmetric halo
    (12, 16, 12, 16, 2, 2),   # single block (whole field) + halo
    (8, 8, 2, 4, 3, 1),       # r0 > bh: window wider than the block
    (6, 10, 3, 5, 5, 9),      # r == extent - 1 (max in-bounds radius)
    (9, 14, 3, 7, 1, 2),      # odd extents / non-power-of-two blocks
]


@pytest.mark.parametrize("wrap", [True, False], ids=["wrap", "clamp"])
@pytest.mark.parametrize("case", CASES, ids=lambda c: "x".join(map(str, c)))
def test_halo_matches_oracle_on_every_backend(case, wrap):
    h, w, bh, bw, r0, r1 = case
    u = np.random.default_rng(hash(case) % 2**31).standard_normal(
        (h, w)).astype(np.float32)
    want = window_sum_ref(u, r0, r1, wrap)
    defines = dict(h=h, w=w, bh=bh, bw=bw, r0=r0, r1=r1, wrap=wrap)
    for backend in BACKENDS:
        (got,) = Device(backend).build_kernel(
            window_sum_builder, defines).run(jnp.asarray(u))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5, err_msg=backend)


@pytest.mark.parametrize("wrap", [True, False], ids=["wrap", "clamp"])
def test_halo_bit_exact_across_backends(wrap):
    u = np.random.default_rng(7).standard_normal((12, 16)).astype(np.float32)
    defines = dict(h=12, w=16, bh=4, bw=8, r0=2, r1=2, wrap=wrap)
    outs = [np.asarray(Device(b).build_kernel(window_sum_builder, defines)
                       .run(jnp.asarray(u))[0]) for b in BACKENDS]
    for b, o in zip(BACKENDS[1:], outs[1:]):
        np.testing.assert_array_equal(outs[0], o, err_msg=b)


# ---------------------------------------------------------------------------
# structural misuse: rejected at Tile/Spec construction (backend-independent)
# ---------------------------------------------------------------------------

def test_halo_rank_mismatch_rejected():
    with pytest.raises(ValueError, match="halo"):
        Tile("u", (8, 8), jnp.float32, block=(4, 4), halo=(1,)).resolved_halo()


def test_negative_halo_rejected():
    with pytest.raises(ValueError, match="halo"):
        Tile("u", (8, 8), jnp.float32, block=(4, 4),
             halo=(-1, 0)).resolved_halo()


def test_halo_without_block_rejected():
    with pytest.raises(ValueError, match="block"):
        Tile("u", (8, 8), jnp.float32, halo=(1, 1)).resolved_halo()


def test_halo_on_output_rejected():
    def body(ctx, u, out):
        out[...] = u[...]

    with pytest.raises(ValueError, match="input-only"):
        Spec("bad", grid=(2,),
             inputs=[Tile("u", (8,), jnp.float32, block=(4,))],
             outputs=[Tile("out", (8,), jnp.float32, block=(4,), halo=(1,))],
             body=body)


# ---------------------------------------------------------------------------
# out-of-bounds halo: the analyzer rejects it on EVERY backend (BOUNDS_HALO)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_oob_halo_rejected_by_analyzer(backend):
    defines = dict(h=8, w=8, bh=4, bw=4, r0=9, r1=0, wrap=True)
    with pytest.raises(AnalysisError, match="BOUNDS_HALO"):
        Device(backend).build_kernel(window_sum_builder, defines)


def test_oob_halo_names_axis_and_extent():
    defines = dict(h=8, w=16, bh=4, bw=4, r0=0, r1=17, wrap=False)
    with pytest.raises(AnalysisError, match="halo radius 17 on axis 1"):
        Device("jnp").build_kernel(window_sum_builder, defines)


# ---------------------------------------------------------------------------
# cost model: halo amplification is charged, and pinned for fd2d
# ---------------------------------------------------------------------------

def _spec(builder, defines):
    from repro.core.lang import defines_namespace
    return builder(defines_namespace(defines))


def test_cost_charges_halo_window_bytes():
    spec = _spec(window_sum_builder,
                 dict(h=8, w=8, bh=4, bw=4, r0=2, r1=2, wrap=True))
    rep = estimate_cost(spec)
    # 4 grid cells, each fetching an (8, 8) float32 window: 4x amplification
    # over the bare (4, 4) blocks.
    assert rep.bytes_in == 4 * 8 * 8 * 4
    # the window is double-buffered in VMEM (multi-cell grid)
    assert rep.vmem_detail["u"] == 2 * 8 * 8 * 4


def test_fd2d_halo_amplification_golden():
    """Pinned golden: fd2d's per-step HBM traffic with halo tiles.

    32x32 field, 8x8 blocks, r=1: 16 cells fetch a 10x10 u1 window (1.5625x
    amplification), a bare 8x8 u2 block, and write an 8x8 u3 block — NOT
    16 whole-field fetches (the pre-halo builder cached the entire field
    per cell: 16 * 4096 B for u1 alone)."""
    defines = dict(w=32, h=32, bh=8, bw=8, r=1, dt=0.1, dx=0.0625,
                   weights=(1.0, -2.0, 1.0), dtype="float32")
    rep = estimate_cost(_spec(fd2d_builder, defines))
    cells = 16
    u1_window = 10 * 10 * 4
    bare = 8 * 8 * 4
    assert rep.bytes_in == cells * (u1_window + bare) == 10496
    assert rep.bytes_out == cells * bare == 4096
    assert rep.flops and rep.flops > 0  # body traces cleanly through the halo


def test_fallback_cost_counts_whole_array_inputs_once():
    """Regression: the no-walk fallback used to charge whole-array inputs
    once PER GRID CELL — a shared (nq, nq) dmat priced as if every cell
    re-fetched it, inflating bytes_in grid-fold and skewing prune choices."""

    def shared_builder(D):
        def body(ctx, x, dmat, out):
            out[...] = x[...] * dmat[0, 0]

        return Spec(
            "shared", grid=(D.n // D.bn,),
            inputs=[Tile("x", (D.n,), jnp.float32, block=(D.bn,)),
                    Tile("dmat", (4, 4), jnp.float32)],
            outputs=[Tile("out", (D.n,), jnp.float32, block=(D.bn,))],
            body=body)

    spec = _spec(shared_builder, dict(n=64, bn=8))
    walked = estimate_cost(spec, walk=True)
    fallback = estimate_cost(spec, walk=False)
    dmat_bytes = 4 * 4 * 4
    # both paths: x streamed once (64 floats), dmat counted ONCE
    assert walked.bytes_in == 64 * 4 + dmat_bytes
    assert fallback.bytes_in == walked.bytes_in
    assert fallback.bytes_out == walked.bytes_out == 64 * 4


def test_fallback_cost_still_amplifies_halo_blocks():
    spec = _spec(window_sum_builder,
                 dict(h=8, w=8, bh=4, bw=4, r0=2, r1=2, wrap=True))
    fallback = estimate_cost(spec, walk=False)
    assert fallback.bytes_in == 4 * 8 * 8 * 4  # 4 cells x full window


def test_pallas_halo_windows_are_tpu_aligned():
    """The compiled TPU expansion needs the trailing two block dims to be
    multiples of (sublanes, 128) or full: the pallas lowering widens fd2d's
    (bh + 2r, bw + 2r) = (34, 258) window to (40, 384), continuing the wrap,
    while jnp/loops keep the exact window."""
    from repro.core.lang import _lower_halo_tile

    t = Tile("u", (4096, 4096), jnp.float32, block=(32, 256), halo=(1, 1))
    exact, _ = _lower_halo_tile(t)
    assert exact.block == (34, 258)
    aligned, windowize = _lower_halo_tile(t, align=True)
    assert aligned.block == (40, 384)
    assert aligned.shape == (128 * 40, 16 * 384)
    u = np.arange(64 * 256, dtype=np.float32).reshape(64, 256)
    small = Tile("u", (64, 256), jnp.float32, block=(32, 128), halo=(1, 1))
    lowered, windowize = _lower_halo_tile(small, align=True)
    win = np.asarray(windowize(jnp.asarray(u)))[:lowered.block[0],
                                                 :lowered.block[1]]
    # block (0, 0): rows/cols -1 .. wrap around, then continue in order
    assert win[0, 0] == u[-1, -1] and win[1, 1] == u[0, 0]
    np.testing.assert_array_equal(win[1:34, 1:130], u[0:33, 0:129])
