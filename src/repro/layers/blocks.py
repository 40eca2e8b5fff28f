"""Block compositions: pre-norm transformer blocks (dense/MoE, GQA/MLA) and
mamba blocks, each with train / prefill / decode entry points.

Every entry point returns a uniform aux vector [moe_lb_loss, moe_z_loss]
(zeros for non-MoE blocks) so layer stacks scan homogeneously.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.parallel.context import shard_activation

from . import attention as attn
from . import mamba as mb
from .common import rmsnorm
from .mlp import mlp_forward, mlp_init
from .moe import moe_forward, moe_init

__all__ = [
    "tblock_init", "tblock_forward", "tblock_prefill", "tblock_decode",
    "tblock_cache_init", "tblock_paged_decode", "tblock_paged_cache_init",
    "mamba_block_init", "mamba_block_forward", "mamba_block_prefill",
    "mamba_block_decode", "mamba_block_cache_init",
    "ZERO_AUX",
]

ZERO_AUX = jnp.zeros(2, jnp.float32)


def _aux_vec(aux: dict | None):
    if not aux:
        return ZERO_AUX
    return jnp.stack([aux["moe_lb_loss"], aux["moe_z_loss"]]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# transformer block (attention + mlp/moe)
# ---------------------------------------------------------------------------

def tblock_init(rng, cfg, dtype, *, moe: bool):
    import jax
    k0, k1 = jax.random.split(rng)
    params = {
        "norm1": jnp.ones((cfg.d_model,), jnp.float32),
        "norm2": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.attn_type == "mla":
        params["attn"] = attn.mla_init(k0, cfg, dtype)
    else:
        params["attn"] = attn.gqa_init(k0, cfg, dtype)
    if moe:
        params["moe"] = moe_init(k1, cfg, dtype)
    else:
        params["mlp"] = mlp_init(k1, cfg.d_model, cfg.d_ff, dtype)
    return params


def _ffn(params, x, cfg, moe, dispatch):
    h = rmsnorm(x, params["norm2"], eps=cfg.norm_eps)
    if moe:
        y, aux = moe_forward(params["moe"], h, cfg, dispatch=dispatch)
        return y, _aux_vec(aux)
    return mlp_forward(params["mlp"], h), ZERO_AUX


def tblock_forward(params, x, cfg, *, moe=False, prefix_len=0,
                   dispatch="einsum", positions=None):
    h = rmsnorm(x, params["norm1"], eps=cfg.norm_eps)
    if cfg.attn_type == "mla":
        a = attn.mla_forward(params["attn"], h, cfg, positions=positions)
    else:
        a = attn.gqa_forward(params["attn"], h, cfg, positions=positions,
                             prefix_len=prefix_len)
    x = x + a
    x = shard_activation(x, "act_btd")
    y, aux = _ffn(params, x, cfg, moe, dispatch)
    return x + y, aux


def tblock_cache_init(cfg, batch, max_len, dtype):
    if cfg.attn_type == "mla":
        return attn.mla_cache_init(cfg, batch, max_len, dtype)
    return attn.gqa_cache_init(cfg, batch, max_len, dtype)


def tblock_prefill(params, x, cfg, *, moe=False, max_len=None, prefix_len=0,
                   dispatch="einsum", cache_dtype=None):
    s = x.shape[1]
    max_len = max_len or s
    cache_dtype = cache_dtype or x.dtype
    h = rmsnorm(x, params["norm1"], eps=cfg.norm_eps)
    if cfg.attn_type == "mla":
        a, latent = attn.mla_forward(params["attn"], h, cfg, return_latent=True)
        cache = attn.mla_cache_init(cfg, x.shape[0], max_len, cache_dtype)
        cache = attn.mla_prefill_cache(cache, latent, cfg)
    else:
        a, kv = attn.gqa_forward(params["attn"], h, cfg, prefix_len=prefix_len,
                                 return_kv=True)
        cache = attn.gqa_cache_init(cfg, x.shape[0], max_len, cache_dtype)
        cache = attn.gqa_prefill_cache(cache, kv[0].astype(cache_dtype),
                                       kv[1].astype(cache_dtype), cfg)
    x = x + a
    y, aux = _ffn(params, x, cfg, moe, dispatch)
    return x + y, aux, cache


def tblock_decode(params, x, cache, cfg, *, moe=False, dispatch="einsum"):
    h = rmsnorm(x, params["norm1"], eps=cfg.norm_eps)
    if cfg.attn_type == "mla":
        a, cache = attn.mla_decode(params["attn"], h, cache, cfg)
    else:
        a, cache = attn.gqa_decode(params["attn"], h, cache, cfg)
    x = x + a
    y, aux = _ffn(params, x, cfg, moe, dispatch)
    return x + y, cache


def tblock_paged_decode(params, x, cache, cfg, *, moe=False, dispatch="einsum",
                        layer, table, lens, pos_pages, page_ids, offs):
    """``tblock_decode`` of layer ``layer`` over the layer-stacked paged KV
    pools (GQA only — MLA's latent cache is gated off upstream by
    ``LM.init_paged_cache``)."""
    h = rmsnorm(x, params["norm1"], eps=cfg.norm_eps)
    a, cache = attn.gqa_paged_decode(params["attn"], h, cache, cfg,
                                     layer=layer, table=table, lens=lens,
                                     pos_pages=pos_pages,
                                     page_ids=page_ids, offs=offs)
    x = x + a
    y, aux = _ffn(params, x, cfg, moe, dispatch)
    return x + y, cache


def tblock_paged_cache_init(cfg, num_pages, page_size, dtype):
    return attn.gqa_paged_cache_init(cfg, num_pages, page_size, dtype)


# ---------------------------------------------------------------------------
# mamba blocks (mamba1 / mamba2)
# ---------------------------------------------------------------------------

def mamba_block_init(rng, cfg, dtype):
    init = mb.mamba1_init if cfg.ssm_type == "mamba1" else mb.mamba2_init
    return {
        "norm": jnp.ones((cfg.d_model,), jnp.float32),
        "mixer": init(rng, cfg, dtype),
    }


def mamba_block_forward(params, x, cfg):
    h = rmsnorm(x, params["norm"], eps=cfg.norm_eps)
    if cfg.ssm_type == "mamba1":
        y = mb.mamba1_forward(params["mixer"], h, cfg)
    else:
        y = mb.mamba2_forward(params["mixer"], h, cfg)
    return x + y, ZERO_AUX


def mamba_block_cache_init(cfg, batch, dtype):
    init = mb.mamba1_cache_init if cfg.ssm_type == "mamba1" else mb.mamba2_cache_init
    return init(cfg, batch, dtype)


def mamba_block_prefill(params, x, cfg, *, cache_dtype=None):
    """Forward + cache extraction (final ssm state + conv tail)."""
    import jax.numpy as jnp_

    cache_dtype = cache_dtype or x.dtype
    h = rmsnorm(x, params["norm"], eps=cfg.norm_eps)
    p = params["mixer"]
    di = cfg.resolved_d_inner
    kc = cfg.ssm_conv
    if cfg.ssm_type == "mamba1":
        xi = h @ p["in_x"]
        z = h @ p["in_z"]
        conv_tail = xi[:, -(kc - 1):, :].astype(cache_dtype)
        xi = mb.silu(mb._causal_conv(xi, p["conv_w"], p["conv_b"]).astype(xi.dtype))
        dt, Bm, Cm = mb._mamba1_dtbc(p, xi, cfg)
        A = -jnp_.exp(p["A_log"])
        y, hT = mb._chunked_scan_jnp(xi, dt, A, Bm, Cm, p["D"])
        y = y * mb.silu(z)
        out = x + (y @ p["out_proj"])
        cache = {"conv": conv_tail, "h": hT}
        return out, ZERO_AUX, cache
    # mamba2
    xBC_raw = h @ p["in_xbc"]
    conv_tail = xBC_raw[:, -(kc - 1):, :].astype(cache_dtype)
    y, ST = _mamba2_forward_with_state(p, h, cfg)
    out = x + y
    cache = {"conv": conv_tail, "h": ST}
    return out, ZERO_AUX, cache


def _mamba2_forward_with_state(p, h, cfg):
    out, ST = mb.mamba2_forward(p, h, cfg, return_state=True)
    return out, ST


def mamba_block_decode(params, x, cache, cfg):
    h = rmsnorm(x, params["norm"], eps=cfg.norm_eps)
    if cfg.ssm_type == "mamba1":
        y, cache = mb.mamba1_decode(params["mixer"], h, cache, cfg)
    else:
        y, cache = mb.mamba2_decode(params["mixer"], h, cache, cfg)
    return x + y, cache
