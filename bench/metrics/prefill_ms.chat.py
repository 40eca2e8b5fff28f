"""Device time per execution of the program that holds the flash attention
forward kernel: one admission's prefill."""

from bench import trace


def read(rec):
    runs = trace.programs_with(rec["trace"], "flash_attention_fwd")
    return 1e-6 * sum(runs) / len(runs) if runs else None
