"""The fd2d kernel's share of its roofline: the least time one step's
necessary work takes at the chip's peaks (``bench/work/fd2d.py``), over the
kernel's mean device time per call in the traced window. The bf16 matrix
peak bounds any arithmetic rate, so the least time stays a lower bound."""

from bench import trace
from bench.work import fd2d


def read(rec):
    if not rec["trace"]:
        return None
    calls = trace.kernel_calls(rec["trace"], "fd2d")
    if not calls:
        return None
    c, pk = rec["cfg"], rec["peaks"]
    flops, nbytes = fd2d.work(c["height"], c["width"], c["radius"])
    least = max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / (1e-9 * sum(calls) / len(calls))
