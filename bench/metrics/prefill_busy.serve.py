"""Share (%) of the traced window in which the chip ran the engine's
prefill programs (``jit_serve_prefill#...``, one per prompt length), from
the breakdown's ``device_ops``.  Open-loop traffic fixes the prompts
prefilled in the window, so a faster prefill reads lower.

The breakdown keeps the ten programs with the most device time: a prefill
program outside them is not counted, and the share is then a lower
bound."""

PROGRAM = "jit_serve_prefill"


def read(rec):
    s = rec.trace_summary
    if s is None or s.window_s <= 0:
        return None
    busy = sum(t for name, t in s.device_ops
               if name.split("#")[0] == PROGRAM)
    return 100.0 * busy / s.window_s
