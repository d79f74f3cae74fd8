"""The trace reduction: by hand on a few events, and on a recorded trace
(``data/trace_serve_docs.json``: 400 ms of program runs and host spans
from a v5e run of qwen2-0.5b.serve-docs) against a brute-force count."""
import json
import pathlib

import numpy as np
import pytest

from bench import tracing
from bench.tracing import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(plane, name, start, dur):
    line = tracing.DEVICE_LINE if plane == DEV else "python3"
    return Event(plane, line, name, float(start), float(dur))


def test_by_hand():
    events = [
        _ev(HOST, "bench.window", 0, 100),
        _ev(HOST, "bench.tick", 0, 60),
        _ev(HOST, "bench.admit", 0, 30),
        _ev(HOST, "bench.wait", 60, 40),
        _ev(DEV, "jit_f(1)", -5, 15),      # clipped to [0, 10]
        _ev(DEV, "jit_g(2)", 20, 5),
        _ev(DEV, "jit_f(1)", 22, 10),      # overlaps the one before
        _ev(DEV, "jit_f(1)", 50, 20),
        _ev(DEV, "jit_g(2)", 120, 5),      # after the window
    ]
    s = tracing.summarize(events)
    assert s.window_s == pytest.approx(100e-9)
    busy = 10 + (32 - 20) + 20
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert dict(s.device_ops) == pytest.approx(
        {"jit_f#1": 40e-9, "jit_g#2": 5e-9})
    # idle: [10, 20] and [32, 50] in admit/tick, [70, 100] in wait/tick
    assert dict(s.idle_gaps) == pytest.approx(
        {"admit": 10e-9, "tick": 18e-9, "wait": 30e-9})


def test_no_window_or_no_device_reads_nothing():
    assert tracing.summarize([_ev(DEV, "jit_f(1)", 0, 5)]) is None
    assert tracing.summarize([_ev(HOST, "bench.window", 0, 5)]) is None


FIXTURE = pathlib.Path(__file__).resolve().parent / "data" \
    / "trace_serve_docs.json"


def _brute(events):
    """Busy and idle time at 1 us resolution, from scratch."""
    win = [e for e in events if e.name == tracing.WINDOW_SPAN][0]
    t0 = int(win.start_ns // 1000)
    n = int(win.end_ns // 1000) - t0
    busy = np.zeros(n, bool)
    for e in events:
        if tracing.is_device_plane(e.plane):
            a = max(0, int(e.start_ns // 1000) - t0)
            b = min(n, int(e.end_ns // 1000) - t0)
            busy[a:b] = True
    return busy.sum() * 1e-6, n * 1e-6


def test_recorded_trace_against_brute_force():
    raw = json.loads(FIXTURE.read_text())
    events = [Event(**e) for e in raw]
    s = tracing.summarize(events)
    busy, window = _brute(events)
    assert s.window_s == pytest.approx(window, abs=2e-6)
    assert s.busy_s == pytest.approx(busy, abs=2e-6 * max(1, len(events)))
    idle = s.window_s - s.busy_s
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(idle, rel=1e-6)
    assert 0 < s.busy_s < s.window_s
