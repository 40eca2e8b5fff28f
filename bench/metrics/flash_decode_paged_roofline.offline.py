"""The paged decode kernel's share of its roofline: the least time its
necessary work takes at the chip's peaks (``bench/work/
flash_decode_paged.py``, over the live contexts of each traced step, once
per layer), over the kernel's device time in the traced window."""

from bench import trace
from bench.work import flash_decode_paged


def read(rec):
    if not rec["trace"]:
        return None
    calls = trace.kernel_calls(rec["trace"], "flash_decode_paged")
    steps = [s["decode"] for s in rec["steps"] if s["decode"]]
    if not calls or not steps:
        return None
    n, pk = rec["dims"], rec["peaks"]
    least = 0.0
    for ctx in steps:
        flops, nbytes = flash_decode_paged.work(n, ctx)
        least += n["L"] * max(flops / pk["bf16_flops_per_s"],
                              nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / (1e-9 * sum(calls))
