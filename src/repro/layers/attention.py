"""Attention layers: GQA (covers MHA/MQA/SWA/prefix-LM) and MLA (deepseek).

Each variant provides init / forward (train+prefill) / cache init / decode.
The perf-critical realization follows ``kernel_backend()`` (the platform:
kernels on a TPU, references elsewhere; ``use_kernel_backend`` pins one):
"pallas" -> repro.kernels flash kernels, "jnp" -> oracle paths (mha_ref for
short, mha_chunked for long sequences). Decode
under "pallas" runs the registered ``flash_decode`` op against the
preallocated cache for EVERY layout — dynamic ``kv_len`` masks the unfilled
tail, and rolling-window caches pass their rotated-slot position map as the
``slot_pos`` input tile; only the "jnp" path uses masked grouped einsums.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (decode_attention, decode_ref,
                                           flash_attention, mha_chunked,
                                           mha_ref, paged_decode_attention,
                                           paged_decode_ref,
                                           ring_flash_attention)
from repro.parallel.context import (current_rules, shard_activation,
                                    shard_kernel)
from repro.parallel.rules import ring_axis_for

from .common import dense_init, kernel_backend, rmsnorm
from .rope import apply_rope

__all__ = [
    "gqa_init", "gqa_forward", "gqa_cache_init", "gqa_prefill_cache",
    "gqa_decode", "gqa_paged_cache_init", "gqa_paged_decode",
    "mla_init", "mla_forward", "mla_cache_init", "mla_prefill_cache",
    "mla_decode",
]

_CHUNKED_THRESHOLD = 8192  # jnp path switches to q-block-chunked beyond this
# kernel calls on a mesh: batch over the batch axes, heads over the model
# axis (see ``shard_kernel``)
_BHSD = ("batch", "model", None, None)
_HEADS = (None, "model", None, None)


def _ring_target(seq_len):
    """(mesh, axis) when the ambient rules declare sequence-parallel ring
    attention for this sequence length, else (None, None). Callers opt in
    via ``Rules(ring_axis=...)`` (e.g. ``build_prefill_step(ring=True)``);
    the divisibility guard keeps ragged shapes on the GSPMD path."""
    rules = current_rules()
    if rules is None or rules.ring_axis is None or rules.mesh is None:
        return None, None
    ax = ring_axis_for(rules.mesh, seq_len, model_axis=rules.ring_axis)
    if ax is None:
        return None, None
    return rules.mesh, ax


def _causal_flash(q, k, v, **kw):
    """Causal flash attention, per shard on a mesh (``shard_kernel``); the
    op pads a ragged sequence to its block."""
    return shard_kernel(
        lambda q, k, v: flash_attention(q, k, v, causal=True, **kw),
        (q, k, v), (_BHSD,) * 3, _BHSD)


# ===========================================================================
# GQA (MHA when Hk == H, MQA when Hk == 1, SWA via cfg.window)
# ===========================================================================

def gqa_init(rng, cfg, dtype):
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    k0, k1, k2, k3 = jax.random.split(rng, 4)
    return {
        "wq": dense_init(k0, (d, h * hd), dtype),
        "wk": dense_init(k1, (d, hk * hd), dtype),
        "wv": dense_init(k2, (d, hk * hd), dtype),
        "wo": dense_init(k3, (h * hd, d), dtype),
    }


def _qkv(params, x, cfg):
    b, s, d = x.shape
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = (x @ params["wk"]).reshape(b, s, hk, hd).transpose(0, 2, 1, 3)
    v = (x @ params["wv"]).reshape(b, s, hk, hd).transpose(0, 2, 1, 3)
    return q, k, v


def gqa_forward(params, x, cfg, *, positions=None, prefix_len=0,
                return_kv=False):
    """Full-sequence (train / prefill) attention. x: (B, S, d_model)."""
    b, s, _ = x.shape
    q, k, v = _qkv(params, x, cfg)
    if positions is None:
        positions = jnp.arange(s)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard_activation(q, "act_bhsd")
    k = shard_activation(k, "act_bhsd")

    ring_mesh, ring_ax = _ring_target(s)
    if ring_mesh is not None:
        # declared ring schedule: kv chunks rotate by ppermute inside
        # shard_map — no GSPMD-inferred collectives around the kernel
        o = ring_flash_attention(
            q, k, shard_activation(v, "act_bhsd"), mesh=ring_mesh,
            mesh_axis=ring_ax, causal=True, window=cfg.window,
            prefix_len=prefix_len,
            backend="auto" if kernel_backend() == "pallas" else "jnp")
    elif kernel_backend() == "pallas":
        o = _causal_flash(q, k, v, window=cfg.window, prefix_len=prefix_len)
    elif s > _CHUNKED_THRESHOLD:
        o = mha_chunked(q, k, v, causal=True, window=cfg.window,
                        prefix_len=prefix_len)
    else:
        o = mha_ref(q, k, v, causal=True, window=cfg.window,
                    prefix_len=prefix_len)
    y = o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ params["wo"]
    if return_kv:
        return y, (k, v)
    return y


def gqa_cache_init(cfg, batch, max_len, dtype):
    hk, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    m = min(max_len, cfg.window) if cfg.window else max_len
    cache = {
        "k": jnp.zeros((batch, hk, m, hd), dtype),
        "v": jnp.zeros((batch, hk, m, hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.window:
        cache["slot_pos"] = jnp.full((m,), -1, jnp.int32)
    return cache


def gqa_prefill_cache(cache, k, v, cfg):
    """Fill cache from prefill k/v (B, Hk, S, hd); returns updated cache."""
    s = k.shape[2]
    m = cache["k"].shape[2]
    if cfg.window and s > m:
        # rolling window keeps the last W tokens; slot = pos % W
        last_pos = jnp.arange(s - m, s)
        slots = last_pos % m
        kk = k[:, :, -m:]
        vv = v[:, :, -m:]
        cache = dict(cache)
        cache["k"] = cache["k"].at[:, :, slots].set(kk)
        cache["v"] = cache["v"].at[:, :, slots].set(vv)
        cache["slot_pos"] = cache["slot_pos"].at[slots].set(last_pos)
        cache["pos"] = jnp.asarray(s, jnp.int32)
        return cache
    cache = dict(cache)
    n = min(s, m)
    cache["k"] = jax.lax.dynamic_update_slice(cache["k"], k[:, :, :n], (0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(cache["v"], v[:, :, :n], (0, 0, 0, 0))
    if cfg.window:
        cache["slot_pos"] = cache["slot_pos"].at[:n].set(jnp.arange(n))
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return cache


def gqa_decode(params, x, cache, cfg):
    """One-token decode. x: (B, 1, d_model). Returns (y, new_cache)."""
    b = x.shape[0]
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pos = cache["pos"]                      # tokens already in cache
    q, k1, v1 = _qkv(params, x, cfg)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, pos, cfg.rope_theta)
        k1 = apply_rope(k1, pos, cfg.rope_theta)

    m = cache["k"].shape[2]
    cache = dict(cache)
    if cfg.window:
        slot = pos % m
        cache["k"] = jax.lax.dynamic_update_slice(cache["k"], k1, (0, 0, slot, 0))
        cache["v"] = jax.lax.dynamic_update_slice(cache["v"], v1, (0, 0, slot, 0))
        cache["slot_pos"] = jax.lax.dynamic_update_slice(
            cache["slot_pos"], pos[None], (slot,))
        kv_len = pos + 1
    else:
        # clamp so the traced write stays in bounds; decoding PAST the cache
        # is rejected host-side (LM.decode_step / launch.serve.generate)
        write = jnp.minimum(pos, m - 1)
        cache["k"] = jax.lax.dynamic_update_slice(cache["k"], k1, (0, 0, write, 0))
        cache["v"] = jax.lax.dynamic_update_slice(cache["v"], v1, (0, 0, write, 0))
        kv_len = write + 1
    cache["pos"] = pos + 1

    if kernel_backend() == "pallas":
        # the registered flash_decode op on EVERY cache layout: one compiled
        # kernel for the whole decode loop, the growing valid length passed
        # as a traced kv_len. Rolling-window caches store ROTATED slots
        # (slot = pos % W); their data-dependent mask rides in as the
        # slot_pos input tile — the grouped-einsum fallback is gone.
        slot_pos = cache["slot_pos"] if cfg.window else None
        o = shard_kernel(
            lambda q, k, v, n, sp: decode_attention(
                q, k, v, kv_len=n, window=cfg.window if cfg.window else None,
                slot_pos=sp, sm_scale=hd ** -0.5),
            (q, cache["k"], cache["v"], kv_len, slot_pos),
            (_BHSD, _BHSD, _BHSD, (), (None,)), _BHSD)
    else:
        # the slot_pos-aware oracle covers BOTH layouts with one grouped
        # masked einsum (no kv replication in HBM; the cache is consumed in
        # its storage dtype) — positional caches pass the identity map
        o = decode_ref(q, cache["k"], cache["v"], kv_len=kv_len,
                       window=cfg.window if cfg.window else None,
                       slot_pos=(cache["slot_pos"] if cfg.window
                                 else jnp.arange(m)),
                       sm_scale=hd ** -0.5)
    y = o.transpose(0, 2, 1, 3).reshape(b, 1, -1) @ params["wo"]
    return y, cache


def gqa_paged_cache_init(cfg, num_pages, page_size, dtype):
    """Per-layer paged KV pools. Page 0 is the NULL page: inactive batch
    slots' block tables point at it and their per-step writes land there,
    so one compiled decode step serves any mix of live/idle slots."""
    hk, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "kp": jnp.zeros((num_pages, hk, page_size, hd), dtype),
        "vp": jnp.zeros((num_pages, hk, page_size, hd), dtype),
    }


def gqa_paged_decode(params, x, cache, cfg, *, layer, table, lens, pos_pages,
                     page_ids, offs):
    """One-token decode of layer ``layer`` over a PAGED cache.
    x: (B, 1, d_model).

    ``cache`` holds the KV pools of every layer of the stack, stacked by
    layer: {kp, vp} of (L, P, Hk, page, hd). The pools are shared by every
    sequence; ``table`` ((B, n_seq_pages) i32) names each sequence's pages
    in logical order, ``lens`` ((B,) i32) its current length (the new
    token's position), ``pos_pages`` ((P, page) i32) the pool-slot ->
    absolute-position map (already including the new token, shared by all
    layers), and ``page_ids``/``offs`` ((B,) each) the pool coordinates of
    the write — derived once per step by the model, not per layer.

    The pools are carried, not sliced: the new token's K/V land with one
    scatter at ``[layer, page_ids, :, offs]``, which XLA does in place when
    the caller carries the pools through its layer loop, and attention reads
    layer ``layer``'s pages where they lie — the stack flattened to
    (L*P, ...) (a bitcast) and read through ``table + layer*P``
    (``flash_decode_paged``'s ``kv_table``; no gather on any backend but
    the XLA reference's). Returns (y, the updated stacked {kp, vp})."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k1, v1 = _qkv(params, x, cfg)
    if cfg.pos_embed == "rope":
        p = lens[:, None, None]                 # per-sequence positions
        q = apply_rope(q, p, cfg.rope_theta)
        k1 = apply_rope(k1, p, cfg.rope_theta)
    kp, vp = cache["kp"], cache["vp"]
    n_layers, npages, hk = kp.shape[:3]
    # one row of hd per (sequence, kv head): the scatter's window is then
    # the pools' minor dim, so XLA keeps their layout (a window over heads
    # too would relayout — copy — the whole pool around the scatter)
    at = (layer, page_ids[:, None], jnp.arange(hk)[None, :], offs[:, None])
    kp = kp.at[at].set(k1[:, :, 0].astype(kp.dtype))
    vp = vp.at[at].set(v1[:, :, 0].astype(vp.dtype))
    kflat = kp.reshape(n_layers * npages, *kp.shape[2:])
    vflat = vp.reshape(n_layers * npages, *vp.shape[2:])
    kv_table = table + layer * npages
    kv_len = lens + 1
    if kernel_backend() == "pallas":
        # the pools have no batch dim: every shard holds all pages of its
        # kv heads, and the control state replicates
        o = shard_kernel(
            lambda q, kp, vp, t, kt, n, pp: paged_decode_attention(
                q, kp, vp, block_table=t, kv_table=kt, kv_len=n,
                pos_pages=pp, window=cfg.window if cfg.window else None,
                sm_scale=hd ** -0.5),
            (q, kflat, vflat, table, kv_table, kv_len, pos_pages),
            (_HEADS, _HEADS, _HEADS, (None, None), (None, None), (None,),
             (None, None)),
            _HEADS)
    else:
        o = paged_decode_ref(q, kflat, vflat, block_table=table,
                             kv_table=kv_table, kv_len=kv_len,
                             pos_pages=pos_pages,
                             window=cfg.window if cfg.window else None,
                             sm_scale=hd ** -0.5)
    y = o.transpose(0, 2, 1, 3).reshape(b, 1, -1) @ params["wo"]
    return y, {"kp": kp, "vp": vp}


# ===========================================================================
# MLA (deepseek-v2): latent-compressed KV; absorbed decode
# ===========================================================================

def mla_init(rng, cfg, dtype):
    d, h = cfg.d_model, cfg.n_heads
    nope, rope, dv, lora = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    k0, k1, k2, k3 = jax.random.split(rng, 4)
    return {
        "wq": dense_init(k0, (d, h * (nope + rope)), dtype),
        "wkv_a": dense_init(k1, (d, lora + rope), dtype),
        "kv_norm": jnp.ones((lora,), jnp.float32),
        "wkv_b": dense_init(k2, (lora, h * (nope + dv)), dtype),
        "wo": dense_init(k3, (h * dv, d), dtype),
    }


def _mla_qkr(params, x, cfg, positions):
    """Project to per-head q and the shared latent (c_kv, k_rope)."""
    b, s, _ = x.shape
    h = cfg.n_heads
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    lora = cfg.kv_lora_rank
    q = (x @ params["wq"]).reshape(b, s, h, nope + rope).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    kv_a = x @ params["wkv_a"]                          # (B,S,lora+rope)
    c_kv = rmsnorm(kv_a[..., :lora], params["kv_norm"], eps=cfg.norm_eps)
    k_rope = kv_a[..., None, lora:].transpose(0, 2, 1, 3)  # (B,1,S,rope)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


def mla_forward(params, x, cfg, *, positions=None, return_latent=False):
    b, s, _ = x.shape
    h = cfg.n_heads
    nope, rope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(s)
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(params, x, cfg, positions)
    kv = (c_kv @ params["wkv_b"]).reshape(b, s, h, nope + dv).transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)       # (B,H,S,nope+rope)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (b, h, s, rope))], axis=-1)
    q = shard_activation(q, "act_bhsd")
    k = shard_activation(k, "act_bhsd")
    if kernel_backend() == "pallas":
        o = _causal_flash(q, k, v)
    elif s > _CHUNKED_THRESHOLD:
        o = mha_chunked(q, k, v, causal=True)
    else:
        o = mha_ref(q, k, v, causal=True)
    y = o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ params["wo"]
    if return_latent:
        return y, (c_kv, k_rope[:, 0])                   # (B,S,lora), (B,S,rope)
    return y


def mla_cache_init(cfg, batch, max_len, dtype):
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def mla_prefill_cache(cache, latent, cfg):
    c_kv, k_rope = latent
    s = c_kv.shape[1]
    cache = dict(cache)
    cache["ckv"] = jax.lax.dynamic_update_slice(
        cache["ckv"], c_kv.astype(cache["ckv"].dtype), (0, 0, 0))
    cache["krope"] = jax.lax.dynamic_update_slice(
        cache["krope"], k_rope.astype(cache["krope"].dtype), (0, 0, 0))
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return cache


def mla_decode(params, x, cache, cfg):
    """Absorbed-matmul decode: scores/outputs computed in latent space —
    the cache stays (lora+rope)-wide, W_uk/W_uv are folded into q / output."""
    b = x.shape[0]
    h = cfg.n_heads
    nope, rope, dv, lora = (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
                            cfg.kv_lora_rank)
    pos = cache["pos"]
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(params, x, cfg, pos)
    # write the new token's latent into the cache
    m = cache["ckv"].shape[1]
    write = jnp.minimum(pos, m - 1)
    cache = dict(cache)
    cache["ckv"] = jax.lax.dynamic_update_slice(
        cache["ckv"], c_kv.astype(cache["ckv"].dtype), (0, write, 0))
    cache["krope"] = jax.lax.dynamic_update_slice(
        cache["krope"], k_rope[:, 0].astype(cache["krope"].dtype), (0, write, 0))
    cache["pos"] = pos + 1

    wkv_b = params["wkv_b"].reshape(lora, h, nope + dv)
    w_uk = wkv_b[..., :nope]                              # (lora, H, nope)
    w_uv = wkv_b[..., nope:]                              # (lora, H, dv)
    # absorb W_uk into q: q_lat (B,H,lora). The latent cache is consumed in
    # its storage dtype (f32 MXU accumulation) — no f32 cache copy.
    cache_dt = cache["ckv"].dtype
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, :, 0], w_uk,
                       preferred_element_type=jnp.float32)
    sm_scale = (nope + rope) ** -0.5
    s = (jnp.einsum("bhl,bml->bhm", q_lat.astype(cache_dt), cache["ckv"],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bmr->bhm", q_rope[:, :, 0].astype(cache_dt),
                      cache["krope"], preferred_element_type=jnp.float32))
    s = s * sm_scale
    mask = jnp.arange(m) <= write
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhm,bml->bhl", p.astype(cache_dt), cache["ckv"],
                       preferred_element_type=jnp.float32)  # (B,H,lora)
    o = jnp.einsum("bhl,lhd->bhd", o_lat.astype(x.dtype), w_uv,
                   preferred_element_type=jnp.float32)
    y = o.reshape(b, 1, h * dv).astype(x.dtype) @ params["wo"]
    return y, cache
