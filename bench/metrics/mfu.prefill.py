"""Required FLOPs of the prompts prefilled in the window, over window x
chips x bf16 peak (%)."""
from bench.readers import peak_share_pct


def read(rec):
    return peak_share_pct(rec, rec.flops.get("prefill", 0.0))
