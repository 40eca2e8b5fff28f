"""Mamba layers: mamba1 (falcon-mamba) and mamba2/SSD (zamba2).

Training uses memory-sane chunked scans (lax.scan over time chunks — nothing
(B, L, D, N)-shaped is ever materialized); both can route through the
fused Pallas ssm_scan kernel when ``kernel_backend("ssm_scan")`` says so
(only when pinned: the TPU compiler refuses it). Decode is a single-step
state update.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.ssm_scan import selective_scan_assoc, ssm_scan
from repro.parallel.context import shard_activation

from .common import dense_init, kernel_backend, silu, softplus

__all__ = [
    "mamba1_init", "mamba1_forward", "mamba1_cache_init", "mamba1_decode",
    "mamba2_init", "mamba2_forward", "mamba2_cache_init", "mamba2_decode",
    "ssd_ref",
]


def _causal_conv(x, w, b):
    """Depthwise causal conv. x: (B, L, C); w: (K, C); b: (C,)."""
    k = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = jnp.zeros_like(x)
    L = x.shape[1]
    for j in range(k):
        y = y + w[j] * jax.lax.dynamic_slice_in_dim(pad, j, L, axis=1)
    return y + b


def _rms_nw(x, eps=1e-6):
    """Weightless RMS normalization (falcon-mamba dt/B/C norm)."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)).astype(x.dtype)


# ===========================================================================
# mamba1
# ===========================================================================

def mamba1_init(rng, cfg, dtype):
    d, di = cfg.d_model, cfg.resolved_d_inner
    n, kc, r = cfg.ssm_state, cfg.ssm_conv, cfg.resolved_dt_rank
    keys = jax.random.split(rng, 6)
    dt_w = dense_init(keys[3], (r, di), jnp.float32, scale=r ** -0.5)
    # dt bias init so softplus(bias) spans [1e-3, 1e-1] (mamba convention)
    u = jax.random.uniform(keys[4], (di,), jnp.float32)
    dt0 = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    kx, kz = jax.random.split(keys[0])
    return {
        # x and z projections are SEPARATE weights: a fused (d, 2*di) weight
        # sharded over the model axis puts xi on shards 0..7 and z on 8..15,
        # and GSPMD reshards both halves with collective-permutes (§Perf it2)
        "in_x": dense_init(kx, (d, di), dtype),
        "in_z": dense_init(kz, (d, di), dtype),
        "conv_w": dense_init(keys[1], (kc, di), jnp.float32, scale=kc ** -0.5),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "x_proj": dense_init(keys[2], (di, r + 2 * n), dtype),
        "dt_w": dt_w,
        "dt_bias": dt_bias,
        "A_log": jnp.log(jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32),
                                          (di, n))),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(keys[5], (di, d), dtype),
    }


def _mamba1_dtbc(params, xi, cfg):
    n, r = cfg.ssm_state, cfg.resolved_dt_rank
    dbc = xi @ params["x_proj"]
    dt_r, Bm, Cm = (dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:])
    if cfg.ssm_bcdt_norm:
        dt_r, Bm, Cm = _rms_nw(dt_r), _rms_nw(Bm), _rms_nw(Cm)
    dt = softplus(dt_r @ params["dt_w"] + params["dt_bias"])
    return dt, Bm, Cm


def _chunked_scan_jnp(x, dt, A, Bm, Cm, D, *, chunk=128):
    """lax.scan over time chunks, associative scan within each chunk."""
    b, L, dm = x.shape
    n = A.shape[1]
    chunk = min(chunk, L)
    while L % chunk:
        chunk -= 1
    nc = L // chunk

    def body(h, args):
        xc, dtc, bc, cc = args
        y, hT = selective_scan_assoc(xc, dtc, A, bc, cc, D, h0=h)
        return hT, y

    resh = lambda a: a.reshape(b, nc, chunk, *a.shape[2:]).swapaxes(0, 1)
    hT, ys = jax.lax.scan(body, jnp.zeros((b, dm, n), jnp.float32),
                          (resh(x), resh(dt), resh(Bm), resh(Cm)))
    y = ys.swapaxes(0, 1).reshape(b, L, dm)
    return y, hT


def mamba1_forward(params, x, cfg):
    """x: (B, L, d_model) -> (B, L, d_model)."""
    di = cfg.resolved_d_inner
    xi = x @ params["in_x"]
    z = x @ params["in_z"]
    xi = shard_activation(xi, "act_btf")
    xi = silu(_causal_conv(xi, params["conv_w"], params["conv_b"]).astype(xi.dtype))
    dt, Bm, Cm = _mamba1_dtbc(params, xi, cfg)
    A = -jnp.exp(params["A_log"])
    if kernel_backend("ssm_scan") == "pallas":
        y = ssm_scan(xi, dt, A, Bm, Cm, params["D"])
    else:
        y, _ = _chunked_scan_jnp(xi, dt, A, Bm, Cm, params["D"])
    y = y * silu(z)
    y = shard_activation(y, "act_btf")
    out = (y @ params["out_proj"]).astype(x.dtype)
    return shard_activation(out, "act_btd")


def mamba1_cache_init(cfg, batch, dtype):
    di, n, kc = cfg.resolved_d_inner, cfg.ssm_state, cfg.ssm_conv
    return {
        "conv": jnp.zeros((batch, kc - 1, di), dtype),
        "h": jnp.zeros((batch, di, n), jnp.float32),
    }


def mamba1_decode(params, x, cache, cfg):
    """x: (B, 1, d_model) single-step update."""
    di = cfg.resolved_d_inner
    xi = x @ params["in_x"]                                  # (B,1,di)
    z = x @ params["in_z"]
    win = jnp.concatenate([cache["conv"], xi.astype(cache["conv"].dtype)], axis=1)
    conv_out = (win * params["conv_w"]).sum(axis=1, keepdims=True) + params["conv_b"]
    xi = silu(conv_out.astype(xi.dtype))
    dt, Bm, Cm = _mamba1_dtbc(params, xi, cfg)
    A = -jnp.exp(params["A_log"])
    dA = jnp.exp(dt[:, 0, :, None] * A)                      # (B,di,N)
    dBx = (dt[:, 0, :, None] * Bm[:, 0, None, :] * xi[:, 0, :, None]).astype(jnp.float32)
    h = dA * cache["h"] + dBx
    y = (h * Cm[:, 0, None, :]).sum(-1) + params["D"] * xi[:, 0].astype(jnp.float32)
    y = (y[:, None].astype(x.dtype)) * silu(z)
    new_cache = {"conv": win[:, 1:], "h": h}
    return y @ params["out_proj"], new_cache


# ===========================================================================
# mamba2 (SSD) — zamba2 backbone; ngroups=1, scalar A per head
# ===========================================================================

def mamba2_init(rng, cfg, dtype):
    d, di = cfg.d_model, cfg.resolved_d_inner
    n, kc, p = cfg.ssm_state, cfg.ssm_conv, cfg.ssm_head_dim
    h = di // p
    keys = jax.random.split(rng, 4)
    conv_dim = di + 2 * n
    u = jax.random.uniform(keys[2], (h,), jnp.float32)
    dt0 = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    kz, kxbc, kdt = jax.random.split(keys[0], 3)
    return {
        # separate projections (see mamba1_init: avoids cross-shard slicing)
        "in_z": dense_init(kz, (d, di), dtype),
        "in_xbc": dense_init(kxbc, (d, di + 2 * n), dtype),
        "in_dt": dense_init(kdt, (d, h), dtype),
        "conv_w": dense_init(keys[1], (kc, conv_dim), jnp.float32, scale=kc ** -0.5),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.ones((h,), jnp.float32) * 1.0
                         + jax.random.uniform(keys[2], (h,), jnp.float32) * 15.0),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "D": jnp.ones((h,), jnp.float32),
        "norm_w": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(keys[3], (di, d), dtype),
    }


def _ssd_chunk(S, xc, dtc, A, bc, cc):
    """One SSD chunk. S: (B,H,P,N) carry; xc: (B,c,H,P); dtc: (B,c,H);
    bc/cc: (B,c,N). Returns (S', y (B,c,H,P))."""
    a = dtc * A                                              # (B,c,H) (negative)
    cs = jnp.cumsum(a, axis=1)                               # inclusive
    # intra-chunk: G[b,h,i,j] = exp(cs_i - cs_j) dt_j (C_i . B_j), j <= i
    scores = jnp.einsum("bin,bjn->bij", cc.astype(jnp.float32),
                        bc.astype(jnp.float32))              # (B,c,c)
    decay = jnp.exp(cs[:, :, None, :] - cs[:, None, :, :])   # (B,i,j,H)
    c_len = xc.shape[1]
    tri = jnp.tril(jnp.ones((c_len, c_len), bool))
    G = jnp.where(tri[None, :, :, None], scores[:, :, :, None] * decay
                  * dtc[:, None, :, :], 0.0)                 # (B,i,j,H)
    y_intra = jnp.einsum("bijh,bjhp->bihp", G, xc.astype(jnp.float32))
    # inter-chunk: exp(cs_i) * C_i . S
    y_inter = jnp.exp(cs)[..., None] * jnp.einsum(
        "bin,bhpn->bihp", cc.astype(jnp.float32), S)
    # state update
    w = jnp.exp(cs[:, -1:, :] - cs) * dtc                    # (B,c,H)
    S_new = (jnp.exp(cs[:, -1])[:, :, None, None] * S
             + jnp.einsum("bjh,bjn,bjhp->bhpn", w, bc.astype(jnp.float32),
                          xc.astype(jnp.float32)))
    return S_new, y_intra + y_inter


def ssd_ref(x, dt, A, Bm, Cm):
    """Sequential oracle. x: (B,L,H,P); dt: (B,L,H); A: (H,); Bm/Cm: (B,L,N)."""
    b, L, h, p = x.shape
    n = Bm.shape[-1]

    def step(S, args):
        xt, dtt, bt, ct = args
        dA = jnp.exp(dtt * A)                                # (B,H)
        S = dA[:, :, None, None] * S + dtt[:, :, None, None] * \
            jnp.einsum("bn,bhp->bhpn", bt, xt)
        y = jnp.einsum("bn,bhpn->bhp", ct, S)
        return S, y

    S0 = jnp.zeros((b, h, p, n), jnp.float32)
    sw = lambda a: a.swapaxes(0, 1).astype(jnp.float32)
    _, ys = jax.lax.scan(step, S0, (sw(x), sw(dt), sw(Bm), sw(Cm)))
    return ys.swapaxes(0, 1)                                  # (B,L,H,P)


def ssd_chunked(x, dt, A, Bm, Cm, *, chunk=128, h0=None):
    b, L, h, p = x.shape
    n = Bm.shape[-1]
    chunk = min(chunk, L)
    while L % chunk:
        chunk -= 1
    nc = L // chunk
    S0 = jnp.zeros((b, h, p, n), jnp.float32) if h0 is None else h0

    def body(S, args):
        xc, dtc, bc, cc = args
        return _ssd_chunk(S, xc, dtc, A, bc, cc)

    resh = lambda a: a.reshape(b, nc, chunk, *a.shape[2:]).swapaxes(0, 1)
    ST, ys = jax.lax.scan(body, S0, (resh(x.astype(jnp.float32)),
                                     resh(dt.astype(jnp.float32)),
                                     resh(Bm), resh(Cm)))
    return ys.swapaxes(0, 1).reshape(b, L, h, p), ST


def mamba2_forward(params, x, cfg, *, return_state=False, h0=None):
    b, L, _ = x.shape
    di, n, p = cfg.resolved_d_inner, cfg.ssm_state, cfg.ssm_head_dim
    h = di // p
    z = x @ params["in_z"]
    xBC = x @ params["in_xbc"]
    dt = (x @ params["in_dt"]).astype(jnp.float32)            # (B,L,H)
    xBC = silu(_causal_conv(xBC, params["conv_w"], params["conv_b"]).astype(xBC.dtype))
    xi = xBC[..., :di]
    Bm = xBC[..., di:di + n]
    Cm = xBC[..., di + n:]
    dt = softplus(dt + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    if kernel_backend("ssm_scan") == "pallas" and not return_state:
        # mamba2 maps EXACTLY onto the fused per-channel kernel (ngroups=1):
        # per-head dt/A/D broadcast to their channels, B/C stay shared —
        # the same recurrence the SSD form factorizes per head.
        dt_ch = jnp.repeat(dt, p, axis=-1)                     # (B,L,di)
        A_ch = jnp.broadcast_to(jnp.repeat(A, p)[:, None], (di, n))
        y = ssm_scan(xi, dt_ch, A_ch, Bm, Cm, jnp.repeat(params["D"], p))
        y = y.reshape(b, L, di).astype(x.dtype)                # D-skip in-kernel
        ST = None
    else:
        xh = xi.reshape(b, L, h, p)
        y, ST = ssd_chunked(xh, dt, A, Bm, Cm, h0=h0)
        y = y + params["D"][:, None] * xh.astype(jnp.float32)
        y = y.reshape(b, L, di).astype(x.dtype)
    # gated RMSNorm (mamba2)
    y = y * silu(z)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt((yf * yf).mean(-1, keepdims=True) + cfg.norm_eps)
         * params["norm_w"]).astype(x.dtype)
    y = shard_activation(y, "act_btf")
    out = (y @ params["out_proj"]).astype(x.dtype)
    out = shard_activation(out, "act_btd")
    if return_state:
        return out, ST
    return out


def mamba2_cache_init(cfg, batch, dtype):
    di, n, kc, p = (cfg.resolved_d_inner, cfg.ssm_state, cfg.ssm_conv,
                    cfg.ssm_head_dim)
    h = di // p
    return {
        "conv": jnp.zeros((batch, kc - 1, di + 2 * n), dtype),
        "h": jnp.zeros((batch, h, p, n), jnp.float32),
    }


def mamba2_decode(params, x, cache, cfg):
    b = x.shape[0]
    di, n, p = cfg.resolved_d_inner, cfg.ssm_state, cfg.ssm_head_dim
    h = di // p
    z = x @ params["in_z"]
    xBC = x @ params["in_xbc"]
    dt = (x @ params["in_dt"]).astype(jnp.float32)
    win = jnp.concatenate([cache["conv"], xBC.astype(cache["conv"].dtype)], axis=1)
    conv_out = (win * params["conv_w"]).sum(axis=1, keepdims=True) + params["conv_b"]
    xBC = silu(conv_out.astype(xBC.dtype))
    xi = xBC[..., :di]
    Bm = xBC[..., di:di + n].astype(jnp.float32)
    Cm = xBC[..., di + n:].astype(jnp.float32)
    dt = softplus(dt + params["dt_bias"])[:, 0]               # (B,H)
    A = -jnp.exp(params["A_log"])
    xh = xi.reshape(b, h, p).astype(jnp.float32)
    dA = jnp.exp(dt * A)                                      # (B,H)
    S = dA[:, :, None, None] * cache["h"] + dt[:, :, None, None] * \
        jnp.einsum("bn,bhp->bhpn", Bm[:, 0], xh)
    y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0], S) + params["D"][:, None] * xh
    y = y.reshape(b, 1, di).astype(x.dtype) * silu(z)
    yf = y.astype(jnp.float32)
    y = (yf * jax.lax.rsqrt((yf * yf).mean(-1, keepdims=True) + cfg.norm_eps)
         * params["norm_w"]).astype(x.dtype)
    new_cache = {"conv": win[:, 1:], "h": S}
    return y @ params["out_proj"], new_cache
