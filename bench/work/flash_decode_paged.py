"""Necessary work of one ``flash_decode_paged`` call (one layer, the whole
batch): read each sequence's live keys and values once, read the query and
write the output. Pages, padding and idle slots add nothing: the same call
on a contiguous cache needs the same work.
"""

from __future__ import annotations


def work(n: dict, contexts: list[int], itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one call; ``contexts`` holds the positions
    each live sequence attends to."""
    h, hk, hd = n["h"], n["hk"], n["hd"]
    live = sum(contexts)
    flops = 4.0 * h * hd * live
    kv = 2.0 * hk * hd * live * itemsize
    qo = 2.0 * len(contexts) * h * hd * itemsize
    return flops, kv + qo
