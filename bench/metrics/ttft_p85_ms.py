"""85th percentile over every request that arrived in the window of the
time from its scheduled arrival to its first token. The chat cell's window
holds about 73 arrivals, so the 85th percentile is the highest that keeps
ten samples beyond it."""

from bench.harness import percentile


def read(rec):
    ttft = [m["times"][0] - m["arrival"] for m in rec["meta"].values()
            if m["times"]]
    return 1e3 * percentile(ttft, 85) if ttft else None
