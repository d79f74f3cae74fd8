"""Required FLOPs of the tokens decoded in the window (attention over
each slot's live positions only), over window x chips x bf16 peak (%)."""
from bench.readers import peak_share_pct


def read(rec):
    return peak_share_pct(rec, rec.flops.get("decode", 0.0))
