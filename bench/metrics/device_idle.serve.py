"""1 - device busy / traced window (%), busy being the union of the
device's operations in the profiler trace."""
from bench.readers import idle_pct


def read(rec):
    return idle_pct(rec)
