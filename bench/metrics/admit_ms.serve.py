"""Host ms per admitted request: the spans around ``Engine._admit``
(batch-1 prefill, cache splice, first-token pull) over the requests they
admitted."""
from bench.readers import span_total


def read(rec):
    n = rec.counters.get("admitted", 0)
    return 1e3 * span_total(rec, "admit") / n if n else None
