"""Host ms per ``Engine.tick``, less the admission span inside it: the
decode step, the argmax and the per-slot token pulls."""
from bench.readers import span_total


def read(rec):
    n = len(rec.spans.get("tick", []))
    if not n:
        return None
    return 1e3 * (span_total(rec, "tick") - span_total(rec, "admit")) / n
