"""95th percentile of every gap between consecutive output tokens of every
request that arrived in the window."""

import numpy as np

from bench.harness import percentile


def read(rec):
    gaps = np.concatenate([np.diff(m["times"]) for m in rec["meta"].values()
                           if len(m["times"]) > 1] or [np.zeros(0)])
    return 1e3 * percentile(gaps.tolist(), 95) if len(gaps) else None
