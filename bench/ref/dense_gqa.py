"""Weights from the seed, and the plain float32 reference, for a dense
decoder with grouped-query attention (InternLM2, Llama-style blocks).

The reference follows the published block: RMSNorm before attention and
before the MLP, rotary positions (``rotate_half`` pairing, ``rope_theta``),
grouped-query attention with ``num_attention_heads / num_key_value_heads``
query heads per key/value head, softmax scaled by ``head_dim ** -0.5``, a
SwiGLU MLP, a final RMSNorm and an untied output head; no biases. It is
written from the configuration's keys alone and imports nothing of the
program: every matrix product states its precision.

The weights are made here, from the seed, on the device, in one jitted
call, in the type they are served in (bf16 matrices, float32 norm scales),
in the layout the system under test loads (its checkpoint format):

    {"embed": (Vpad, d), "head": (d, Vpad), "final_norm": (d,),
     "stacks": [{"norm1", "norm2": (L, d),
                 "attn": {"wq": (L, d, H*hd), "wk", "wv": (L, d, Hk*hd),
                          "wo": (L, H*hd, d)},
                 "mlp": {"w_gate", "w_up": (L, d, f), "w_down": (L, f, d)}}]}

``Vpad`` is the vocabulary rounded up to 256 rows; the rows past the
vocabulary are zero and no token id reaches them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // h)
    v = cfg["vocab_size"]
    return {"d": d, "h": h, "hk": cfg["num_key_value_heads"], "hd": hd,
            "f": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "v": v, "vpad": -(-v // 256) * 256,
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"])}


def _init(cfg: dict, key):
    n = dims(cfg)
    d, h, hk, hd, f, L = n["d"], n["h"], n["hk"], n["hd"], n["f"], n["L"]
    ks = iter(jax.random.split(key, 16))
    bf16 = jnp.bfloat16

    def mat(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * fan_in ** -0.5).astype(bf16)

    def norm(shape):      # scales near 1, not 1: a norm whose scale is
        return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

    rows = jnp.arange(n["vpad"]) < n["v"]
    embed = mat((n["vpad"], d), d) * rows[:, None].astype(bf16)
    head = mat((d, n["vpad"]), d) * rows[None, :].astype(bf16)
    return {
        "embed": embed, "head": head, "final_norm": norm((d,)),
        "stacks": [{
            "norm1": norm((L, d)), "norm2": norm((L, d)),
            "attn": {"wq": mat((L, d, h * hd), d),
                     "wk": mat((L, d, hk * hd), d),
                     "wv": mat((L, d, hk * hd), d),
                     "wo": mat((L, h * hd, d), h * hd)},
            "mlp": {"w_gate": mat((L, d, f), d), "w_up": mat((L, d, f), d),
                    "w_down": mat((L, f, d), f)},
        }],
    }


def init_weights(cfg: dict, key):
    """The weights of ``key``, made on the device in one jitted call."""
    return jax.jit(lambda k: _init(cfg, k))(key)


def weight_shapes(cfg: dict):
    return jax.eval_shape(lambda k: _init(cfg, k), jax.random.key(0))


# ----------------------------------------------------------------- reference
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, heads, hd); rotate_half pairing of dims i and i + hd/2."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _q8(x, axis):
    """Symmetric int8 rounding along ``axis`` (a scale per other index),
    returned dequantised: the arithmetic of an int8 product with exact
    integer accumulation."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, int8):
    """x (rows, k) @ w (k, n) in float32 at the highest precision; with
    ``int8``, activations per row and weights per output column rounded to
    int8 first (W8A8)."""
    if int8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer(x, lw, n, int8):
    f32 = jnp.float32
    s = x.shape[0]
    h, hk, hd = n["h"], n["hk"], n["hd"]
    a = _rms(x, lw["norm1"], n["eps"])
    q = _mm(a, lw["wq"].astype(f32), int8).reshape(s, h, hd)
    k = _mm(a, lw["wk"].astype(f32), int8).reshape(s, hk, hd)
    v = _mm(a, lw["wv"].astype(f32), int8).reshape(s, hk, hd)
    q, k = _rope(q, n["theta"]), _rope(k, n["theta"])
    if int8:                             # an int8 KV cache
        k, v = _q8(k, -1), _q8(v, -1)
    g = h // hk
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(s, -1)
    x = x + _mm(o, lw["wo"].astype(f32), int8)
    m = _rms(x, lw["norm2"], n["eps"])
    gate = _mm(m, lw["w_gate"].astype(f32), int8)
    up = _mm(m, lw["w_up"].astype(f32), int8)
    return x + _mm(jax.nn.silu(gate) * up, lw["w_down"].astype(f32), int8)


def logits(cfg: dict, w, tokens, *, int8: bool = False):
    """Float32 logits (S, vocab) of one token sequence (S,), layer by layer:
    each layer's weights are widened to float32 only while it runs."""
    n = dims(cfg)
    x = w["embed"][tokens].astype(jnp.float32)
    for st in w["stacks"]:
        layers = {"norm1": st["norm1"], "norm2": st["norm2"], **st["attn"],
                  **st["mlp"]}
        x, _ = jax.lax.scan(lambda x, lw: (_layer(x, lw, n, int8), None),
                            x, layers)
    x = _rms(x, w["final_norm"], n["eps"])
    return _mm(x, w["head"].astype(jnp.float32), int8)[:, :n["v"]]


def served_gaps(cfg: dict, w, tokens, *, int8: bool = False):
    """For each position p of ``tokens`` (S,): how far the logit of
    ``tokens[p + 1]`` lies below the reference's best at p. With ``int8``,
    also the gap of the token that the int8 control puts first. Returns
    ``(gap_served (S,), gap_control (S,) or None)``; the last position has
    no next token and reads 0."""
    ref = logits(cfg, w, tokens)
    best = ref.max(-1)
    nxt = jnp.concatenate([tokens[1:], tokens[-1:]])
    gap = best - jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
    gap = gap.at[-1].set(0.0)
    if not int8:
        return gap, None
    pick = jnp.argmax(logits(cfg, w, tokens, int8=True), -1)
    ctl = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return gap, ctl
