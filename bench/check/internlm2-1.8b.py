"""``correct`` for internlm2-1.8b: the served tokens of a sample of
finished requests against the float32 reference (``bench/ref/served.py``).

The limit sits between two readings on the chip at the cells' own sizes
(PERF.md, "How correct is decided"): the largest mean gap that sound runs
of the program read over a dozen seeds and more (9.5e-4), and the least
that the int8 control reads (1.12e-2).
"""

from bench.ref import dense_gqa, served

SAMPLE = 8              # requests compared: the longest and 7 drawn
GAP_MEAN_LIMIT = 0.004  # logits: program <= 9.5e-4, int8 control >= 1.12e-2
MIN_TOKENS = 200        # served tokens the sample must hold


def compare(cfg, weights, seqs, control=False):
    """The served tokens' numbers beside their limits; with ``control``,
    the int8 control's tokens in the program's place."""
    return served.compare(dense_gqa, cfg, weights, seqs,
                          width=cfg["serving"]["max_len"],
                          mean_limit=GAP_MEAN_LIMIT, min_tokens=MIN_TOKENS,
                          control=control)
