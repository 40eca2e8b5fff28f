"""Share of the traced window in which no operation ran on the device (chip
0): 1 - the union of the operations' intervals over the window."""

from bench import trace


def read(rec):
    return trace.idle_share(rec["trace"]) if rec["trace"] else None
