"""Mean over the window's requests of each one's first wait in the
engine's queue (its first ``engine.queue`` span: from submission to the
start of its admission), in ms, read from the program's spans: the time to
first token that requests lose to full slots, carried by the few that find
every slot taken."""

from bench import engine_window


def read(rec):
    waits = engine_window.first_queue_waits(rec)
    return sum(waits) / len(waits) if waits else None
