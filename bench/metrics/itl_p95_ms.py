"""95th percentile of every gap between consecutive output tokens of
every request in the window, pooled."""
from bench.readers import p95


def read(rec):
    v = p95([g for r in rec.requests for g in r["gaps_s"]])
    return None if v is None else 1e3 * v
