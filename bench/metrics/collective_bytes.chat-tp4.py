"""Bytes that the collectives of one decode step bring into one chip:
the program's counter ``engine.collective_bytes`` (each decode step adds
what the compiled step's collectives move, read once from the compiled
program) over the ``engine.decode`` spans recorded in the run's process.
A program without the counter gives ``None``."""


def read(rec):
    try:
        from repro.runtime import spans as recorder
    except ImportError:                 # a program without the recorder
        return None
    total = recorder.counters().get("engine.collective_bytes")
    records = recorder.records()
    steps = sum(1 for r in records if r["name"] == "engine.decode")
    if total is None or not steps or len(records) >= recorder.RING:
        return None                     # no counter, or spans dropped out
    return total / steps
