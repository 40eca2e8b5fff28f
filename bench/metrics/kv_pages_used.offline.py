"""Share of the KV page pool that running requests hold at each decode
step, in %: the mean over the window's ``engine.decode`` spans of
``pages_used / pages_total`` (the null page left out), read from the
program's spans. What the step's copy of the whole pool carries beyond
this share is pages no sequence uses."""

from bench import engine_window


def read(rec):
    spans = engine_window.spans(rec)
    if spans is None:
        return None
    used = [r["attrs"]["pages_used"] / r["attrs"]["pages_total"]
            for r in spans if r["name"] == "engine.decode"]
    return 100.0 * sum(used) / len(used) if used else None
