"""``correct`` for internlm2-20b, served tensor-parallel over four chips:
the served tokens of a sample of finished requests against the float32
reference (``bench/ref/served.py``), run over the sharded weights once the
engine's pool is freed. The reference widens one layer's weights to
float32 at a time (``bench/ref/dense_gqa.py``) and leaves out no part of
the block.

The limit sits between two readings on the chip at this configuration
(PERF.md, "How correct is decided"): the largest mean gap that sound runs
of the program read over six seeds (9.7e-4), and the least that the int8
control reads on the sequences of five of them (1.17e-2).
"""

from bench.ref import dense_gqa, served

SAMPLE = 8              # requests compared: the longest and 7 drawn
GAP_MEAN_LIMIT = 0.004  # logits: program <= 9.7e-4, int8 control >= 1.17e-2
MIN_TOKENS = 200        # served tokens the sample must hold


def compare(cfg, weights, seqs, control=False):
    """The served tokens' numbers beside their limits; with ``control``,
    the int8 control's tokens in the program's place."""
    return served.compare(dense_gqa, cfg, weights, seqs,
                          width=cfg["serving"]["max_len"],
                          mean_limit=GAP_MEAN_LIMIT, min_tokens=MIN_TOKENS,
                          control=control)
