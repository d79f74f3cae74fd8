"""Host spans around calls into each layer, and the profiler trace's
reduction to device busy time, the costliest device operations and the
longest idle gaps (each named by the host span it fell in).

Spans are recorded from the benchmark's side of each call: the program's
code is not touched.  With tracing on, each span is also a
``jax.profiler.TraceAnnotation``, so it lands on the trace's host plane
on the device's clock.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import pathlib
import shutil
import time
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


class Spans:
    """(start, end) host times by span name, kept in memory."""

    def __init__(self, annotate: bool = False):
        self.by_name: Dict[str, List[Tuple[float, float]]] = \
            collections.defaultdict(list)
        self._annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self._annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.by_name[name].append((t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def wrap(self, obj, attr: str, name: Optional[str] = None):
        """Record a span around every call of ``obj.attr`` (an instance
        attribute shadows the method; the class is left alone)."""
        fn = getattr(obj, attr)
        spans = self

        def wrapped(*a, **k):
            with spans.span(name or attr):
                return fn(*a, **k)

        setattr(obj, attr, wrapped)
        return fn

    def within(self, t0: float, t1: float) -> Dict[str, List[tuple]]:
        return {k: [s for s in v if s[0] >= t0 and s[1] <= t1]
                for k, v in self.by_name.items()}


# --------------------------------------------------------------------------
# profiler trace -> events -> summary
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[12:].isdigit()


# one event per program run on the device (a jitted function's executable);
# "XLA Ops" holds every operation inside them, a million events a second
DEVICE_LINE = "XLA Modules"


def extract(trace_dir: pathlib.Path) -> List[Event]:
    """Program runs on the device and the benchmark's host spans of the one
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return []
    pd = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in pd.planes:
        dev = is_device_plane(plane.name)
        for line in plane.lines:
            if dev and line.name != DEVICE_LINE:
                continue
            for ev in line.events:
                if dev or ev.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def _merge(intervals: Iterable[Tuple[float, float]]):
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def program_name(event_name: str) -> str:
    """``jit_f(1234)`` -> ``jit_f#1234``: the jitted function and, since
    anonymous functions share a name, the fingerprint that tells them
    apart."""
    return event_name.replace("(", "#").rstrip(")")


@dataclasses.dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    def breakdown(self):
        return {"device_ops": [list(x) for x in self.device_ops],
                "idle_gaps": [list(x) for x in self.idle_gaps]}


def summarize(events: List[Event], top: int = 10) -> Optional[TraceSummary]:
    """Busy time (union of the programs' intervals on each chip, averaged
    over the chips) inside the ``bench.window`` span, the programs that
    took most of it, and the idle time by the host span it fell in
    (longest first); None when the trace holds no window or no program."""
    windows = [e for e in events if e.name == WINDOW_SPAN]
    ops = [e for e in events if is_device_plane(e.plane)]
    if not windows or not ops:
        return None
    w0 = min(e.start_ns for e in windows)
    w1 = max(e.end_ns for e in windows)
    planes = sorted({e.plane for e in ops})
    busy_ns, gaps = 0.0, []
    per_op: Dict[str, float] = collections.defaultdict(float)
    for plane in planes:
        ivs = []
        for e in ops:
            if e.plane != plane:
                continue
            a, b = max(e.start_ns, w0), min(e.end_ns, w1)
            if b > a:
                ivs.append((a, b))
                per_op[program_name(e.name)] += (b - a) / len(planes)
        merged = _merge(ivs)
        busy_ns += sum(b - a for a, b in merged) / len(planes)
        if plane == planes[0]:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    host = [e for e in events if e.name.startswith(SPAN_PREFIX)
            and e.name != WINDOW_SPAN]
    by_span: Dict[str, float] = collections.defaultdict(float)
    for a, b in gaps:
        by_span[_host_span(host, a, b)] += (b - a) / 1e9
    ops_top = sorted(per_op.items(), key=lambda x: -x[1])[:top]
    return TraceSummary(
        busy_s=busy_ns / 1e9, window_s=(w1 - w0) / 1e9,
        device_ops=[(n, ns / 1e9) for n, ns in ops_top],
        idle_gaps=sorted(by_span.items(), key=lambda x: -x[1])[:top])


def _host_span(host: List[Event], a: float, b: float) -> str:
    """The host span that overlaps [a, b] most (the innermost on a tie)."""
    best, best_key = "host (no span)", (0.0, 0.0)
    for e in host:
        ov = min(b, e.end_ns) - max(a, e.start_ns)
        if ov <= 0:
            continue
        key = (ov, -e.dur_ns)
        if key > best_key:
            best, best_key = e.name[len(SPAN_PREFIX):], key
    return best


class Profiler:
    """``jax.profiler`` trace of part of a run, read back into a summary."""

    def __init__(self, trace_dir: pathlib.Path):
        self.dir = pathlib.Path(trace_dir)

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no event per Python call
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self) -> Optional[TraceSummary]:
        import jax
        jax.profiler.stop_trace()
        events = extract(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        return summarize(events)
