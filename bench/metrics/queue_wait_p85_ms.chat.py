"""85th percentile over the window's requests of each one's first wait in
the engine's queue (its first ``engine.queue`` span: from submission to
the start of its admission), in ms, read from the program's spans. It
stays at its floor, the host code between a submission and the next
admission, while fewer than 15% of requests find every slot full;
``queue_wait_mean_ms.chat`` sees those few."""

from bench import engine_window
from bench.harness import percentile


def read(rec):
    waits = engine_window.first_queue_waits(rec)
    return percentile(waits, 85) if waits else None
