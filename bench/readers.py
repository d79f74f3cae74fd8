"""Arithmetic shared by the metric readers in ``bench/metrics/``."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def p95(values: Sequence[float]) -> Optional[float]:
    """95th percentile (linear between order statistics); None if empty."""
    return float(np.percentile(values, 95)) if len(values) else None


def span_total(rec, name: str) -> float:
    return sum(b - a for a, b in rec.spans.get(name, []))


def peak_share_pct(rec, flops: float) -> Optional[float]:
    """``flops`` done over the window, as a share of the chips' bf16 peak."""
    if not flops or rec.window_s <= 0:
        return None
    return 100.0 * flops / (rec.window_s * rec.cell.chips
                            * rec.peak["bf16_flops"])


def idle_pct(rec) -> Optional[float]:
    s = rec.trace_summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
