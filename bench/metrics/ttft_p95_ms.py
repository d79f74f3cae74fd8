"""95th percentile of time to first token over every request due in the
window, from its scheduled arrival; one still unanswered at the close
counts at its age then."""
from bench.readers import p95


def read(rec):
    v = p95([r["ttft_s"] for r in rec.requests if r["ttft_s"] is not None])
    return None if v is None else 1e3 * v
