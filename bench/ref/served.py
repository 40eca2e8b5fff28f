"""The check of a served model: how far the tokens it served lie below the
reference's best.

For each sampled request, the reference runs once over its prompt and the
tokens the engine served, and reads at each served position the gap between
the reference's largest logit and the logit of the token that was served.
Greedy decoding serves the program's own largest logit, so where the program
computes what the reference does, the gap is rounding; a wrong page, mask,
position or token puts it far below. The number compared is the mean gap
over all served tokens of the sample. The widest gap is read too (by the
calibration), but it swings too much from seed to seed to part the program
from the int8 control by the factor of three a limit needs (PERF.md).
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _gaps_fn(ref, cfg_key, int8):
    import jax

    cfg = dict(cfg_key)
    return jax.jit(lambda w, t: ref.served_gaps(cfg, w, t, int8=int8))


def gaps(ref, cfg: dict, weights, seqs, width: int, *, int8: bool = False):
    """Per sequence: the served positions' gaps (and the int8 control's).
    Sequences are right-padded to ``width`` so one program serves all; the
    attention is causal, so padding changes nothing before it."""
    import jax.numpy as jnp

    fn = _gaps_fn(ref, tuple(sorted((k, v) for k, v in cfg.items()
                                    if not isinstance(v, (dict, list)))),
                  int8)
    out, ctl = [], []
    for toks, plen in seqs:
        pad = np.zeros(width, np.int32)
        pad[:len(toks)] = toks
        g, c = fn(weights, jnp.asarray(pad))
        sl = slice(plen - 1, len(toks) - 1)      # positions that served
        out.append(np.asarray(g)[sl])
        if int8:
            ctl.append(np.asarray(c)[sl])
    return out, ctl


def readings(g) -> dict:
    """The numbers compared, from per-sequence gaps; no tokens read inf."""
    flat = np.concatenate(list(g) or [np.zeros(0)])
    if not len(flat):
        return {"mean": float("inf"), "tokens": 0}
    return {"mean": float(flat.mean()), "tokens": int(len(flat))}


def compare(ref, cfg: dict, weights, seqs, *, width: int, mean_limit: float,
            min_tokens: int, control: bool = False) -> dict:
    """The numbers compared, each beside its limit: of the served tokens,
    or with ``control`` of the int8 control's tokens in their place."""
    prog, ctl = gaps(ref, cfg, weights, seqs, width, int8=control)
    r = readings(ctl if control else prog)
    return {
        "served_gap_mean": {"value": r["mean"], "limit": mean_limit,
                            "ok": r["mean"] <= mean_limit},
        "served_tokens": {"value": r["tokens"], "limit": min_tokens,
                          "ok": r["tokens"] >= min_tokens},
    }
