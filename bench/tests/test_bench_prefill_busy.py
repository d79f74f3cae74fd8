"""``prefill_busy.serve`` on the recorded engine trace
(``data/trace_serve_chat_engine.json``, which holds two prefill runs) and
on a few events by hand."""
import json
import pathlib
import types

import pytest

from bench import harness, tracing
from bench.tracing import Event

FIXTURE = pathlib.Path(__file__).resolve().parent / "data" \
    / "trace_serve_chat_engine.json"
METRIC = harness.load_module("metrics", "prefill_busy.serve")


def _rec(events):
    return types.SimpleNamespace(trace_summary=tracing.summarize(events))


def test_recorded_trace_counts_the_prefill_runs():
    events = [Event(**e) for e in json.loads(FIXTURE.read_text())]
    win = [e for e in events if e.name == tracing.WINDOW_SPAN][0]
    want = sum(min(e.end_ns, win.end_ns) - max(e.start_ns, win.start_ns)
               for e in events if tracing.is_device_plane(e.plane)
               and e.name.startswith("jit_serve_prefill("))
    assert want > 0
    assert METRIC.read(_rec(events)) == pytest.approx(
        100.0 * want / win.dur_ns, rel=1e-9)


def test_by_hand_and_without_a_trace():
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [Event(host, "python3", "bench.window", 0.0, 100.0),
              Event(dev, tracing.DEVICE_LINE, "jit_serve_prefill(1)", 10, 20),
              Event(dev, tracing.DEVICE_LINE, "jit_serve_prefill(2)", 40, 5),
              Event(dev, tracing.DEVICE_LINE, "jit_serve_prefill_x(3)", 50, 9),
              Event(dev, tracing.DEVICE_LINE, "jit_serve_decode(4)", 60, 30)]
    assert METRIC.read(_rec(events)) == pytest.approx(25.0)
    assert METRIC.read(types.SimpleNamespace(trace_summary=None)) is None
