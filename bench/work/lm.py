"""Necessary operations of a dense GQA decoder, from its configuration:
what the model has to compute, whatever computes it.

A token's matrix products cost 2 operations per weight of the layers it
passes. The output head is needed only where logits are read: every decode
token, and the last token of a prefill. Attention costs 4 operations per
head dimension and per position attended (scores and values), over the live
context and never over padding or empty pages.
"""

from __future__ import annotations


def layer_weights(n: dict) -> int:
    d, h, hk, hd, f = n["d"], n["h"], n["hk"], n["hd"], n["f"]
    return d * h * hd + 2 * d * hk * hd + h * hd * d + 3 * d * f


def head_weights(n: dict) -> int:
    return n["d"] * n["v"]


def decode_flops(n: dict, ctx: int) -> float:
    """One decode token that attends to ``ctx`` positions (itself
    included)."""
    per_layer = 2 * layer_weights(n) + 4 * n["h"] * n["hd"] * ctx
    return float(n["L"] * per_layer + 2 * head_weights(n))


def prefill_flops(n: dict, length: int) -> float:
    """A causal prefill of ``length`` tokens: position i attends to i + 1
    positions, so attention sums to ``length * (length + 1) / 2``."""
    attn = 4 * n["h"] * n["hd"] * length * (length + 1) / 2
    return float(n["L"] * (2 * layer_weights(n) * length + attn)
                 + 2 * head_weights(n))
