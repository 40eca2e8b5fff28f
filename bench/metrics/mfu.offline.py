"""Model FLOPs of the traced window's prefilled and decoded tokens
(``bench/work/lm.py``: 2 per weight, attention over the live context), over
the traced window times the chip's bf16 peak."""

from bench import trace
from bench.work import lm


def read(rec):
    if not rec["trace"] or not rec["steps"]:
        return None
    n = rec["dims"]
    flops = sum(lm.prefill_flops(n, p) for s in rec["steps"]
                for p in s["admit"])
    flops += sum(lm.decode_flops(n, c) for s in rec["steps"]
                 for c in s["decode"])
    peak = rec["peaks"]["bf16_flops_per_s"] * len(rec["trace"]["devices"])
    return 100.0 * flops / (trace.window_s(rec["trace"]) * peak)
