"""The serving engine's own spans over a run's window, for the metrics of
the engine's host loop.

The program records them in memory (``repro.runtime.spans``), in the
process of the run; the readers run in that process once the window and
its check are done, and read them there. A window's spans are those of
the newest engine, from the ``engine.queue`` span of the window's first
request (the smallest request id in ``rec["meta"]``; warm-up requests
have lower ids) onward. A program that records no spans gives ``None``.
"""

from __future__ import annotations


def spans(rec) -> list[dict] | None:
    try:
        from repro.runtime import spans as recorder
    except ImportError:                 # a program without the recorder
        return None
    if not rec.get("meta"):
        return None
    mine = [r for r in recorder.records()
            if r["name"].startswith("engine.") and "engine" in r["attrs"]]
    if not mine:
        return None
    newest = max(r["attrs"]["engine"] for r in mine)
    mine = [r for r in mine if r["attrs"]["engine"] == newest]
    first = min(rec["meta"])
    starts = [r["start_ns"] for r in mine if r["name"] == "engine.queue"
              and r["attrs"]["rid"] == first]
    if not starts:
        return None
    t0 = min(starts)
    return [r for r in mine if r["start_ns"] >= t0]


def ms(r: dict) -> float:
    return (r["end_ns"] - r["start_ns"]) / 1e6


def first_queue_waits(rec) -> list[float] | None:
    """Each window request's first wait in the engine's queue (its first
    ``engine.queue`` span: from submission to the start of its admission),
    in ms."""
    mine = spans(rec)
    if mine is None:
        return None
    first = {}
    for r in mine:
        rid = r["attrs"].get("rid")
        if r["name"] == "engine.queue" and rid in rec["meta"] and (
                rid not in first or r["start_ns"] < first[rid]["start_ns"]):
            first[rid] = r
    return [ms(r) for r in first.values()] or None
