"""Host self time per engine step, in ms: the mean over the window's
``engine.step`` spans of each one's duration less its ``engine.fetch`` and
``engine.first_token`` spans (the waits for the device), read from the
program's spans. The device has nothing queued for about that long in
every step."""

from bench import engine_window

WAITS = ("engine.fetch", "engine.first_token")


def read(rec):
    spans = engine_window.spans(rec)
    if spans is None:
        return None
    parent = {r["id"]: r["parent"] for r in spans}
    steps = {r["id"]: engine_window.ms(r) for r in spans
             if r["name"] == "engine.step"}
    for r in spans:
        if r["name"] not in WAITS:
            continue
        p = r["parent"]
        while p is not None and p not in steps:
            p = parent.get(p)
        if p is not None:
            steps[p] -= engine_window.ms(r)
    return sum(steps.values()) / len(steps) if steps else None
