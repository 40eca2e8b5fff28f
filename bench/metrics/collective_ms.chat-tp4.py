"""Device time on chip 0 of the collective operations (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute, with their
async ``-start`` / ``-done`` halves, and the TPU compiler's
``async-collective-start`` / ``-done`` fusions) inside each execution of
the program that holds the paged decode kernel, per execution. An
operation counts by its HLO name or by its opcode. An asynchronous
collective counts only the time its own operations take on the device, not
the transfer that overlaps other work."""

import bisect
import re

from bench import trace

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")
NAMES = KINDS + ("async-collective",)
_OPCODE = re.compile(r"\b(?:%s)(?:-start|-done)?\(" % "|".join(KINDS))


def collective(name: str) -> bool:
    return trace.op_name(name).startswith(NAMES) or bool(_OPCODE.search(name))


def read(rec):
    dev = rec["trace"]["devices"][0]
    w = rec["trace"]["window"]
    kernels = sorted(s for _, s, _, k in dev["ops"]
                     if k == "flash_decode_paged")
    steps = []
    for _, s, d in dev["modules"]:
        i = bisect.bisect_left(kernels, s)
        if w[0] <= s and s + d <= w[1] and i < len(kernels) \
                and kernels[i] < s + d:
            steps.append((s, s + d))
    if not steps:
        return None
    steps.sort()
    starts = [lo for lo, _ in steps]
    total = 0
    for name, s, d, k in dev["ops"]:
        if k is None and collective(name):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < steps[i][1]:
                total += d
    return 1e-6 * total / len(steps)
