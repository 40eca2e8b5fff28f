"""Device time on chip 0 per execution of the program that holds the
paged decode kernel (the engine's tensor-parallel decode step), found by
the kernel's name."""

from bench import trace


def read(rec):
    runs = trace.programs_with(rec["trace"], "flash_decode_paged")
    return 1e-6 * sum(runs) / len(runs) if runs else None
