"""A recorded trace of the instrumented engine
(``data/trace_serve_chat_engine.json``: 400 ms of program runs, the
benchmark's host spans and the engine's own ``engine.*`` spans from a v5e
run of qwen2-0.5b.serve-chat): the reduction reads it as it reads any
trace, the engine's programs carry stable names, and the engine's spans
lie on the same clock as the benchmark's, nested inside them."""
import json
import pathlib

import pytest

from bench import tracing
from bench.tests.test_bench_trace import _brute
from bench.tracing import Event

FIXTURE = pathlib.Path(__file__).resolve().parent / "data" \
    / "trace_serve_chat_engine.json"


@pytest.fixture(scope="module")
def events():
    return [Event(**e) for e in json.loads(FIXTURE.read_text())]


def test_engine_trace_against_brute_force(events):
    s = tracing.summarize(events)
    busy, window = _brute(events)
    assert s.window_s == pytest.approx(window, abs=2e-6)
    assert s.busy_s == pytest.approx(busy, abs=2e-6 * len(events))
    idle = s.window_s - s.busy_s
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(idle, rel=1e-6)
    # the engine's spans are not the benchmark's: idle still goes to these
    assert {k for k, _ in s.idle_gaps} <= {"tick", "admit", "wait",
                                           "host (no span)"}


def test_engine_programs_have_stable_names(events):
    names = {tracing.program_name(e.name).split("#")[0] for e in events
             if tracing.is_device_plane(e.plane)}
    assert {"jit_serve_decode", "jit_serve_prefill"} <= names
    assert not any(n.startswith("jit__lambda") for n in names)


def _inside(e, outer):
    return outer.start_ns <= e.start_ns and e.end_ns <= outer.end_ns


PARENT = {"engine.tick": "bench.tick", "engine.admit": "engine.tick",
          "engine.decode": "engine.tick", "engine.device_wait": "engine.tick",
          "engine.pull": "engine.tick", "engine.prefill": "engine.admit",
          "engine.splice": "engine.admit",
          "engine.first_token": "engine.admit"}


def test_engine_spans_nest_inside_the_benchmarks(events):
    host = [e for e in events if not tracing.is_device_plane(e.plane)
            and e.name != tracing.WINDOW_SPAN]
    by = {n: [e for e in host if e.name == n] for n in PARENT.values()}
    assert by["engine.tick"] and by["engine.admit"]
    assert any(e.name == "engine.pull" for e in host)
    for e in host:
        if e.name not in PARENT:
            continue
        parents = by[PARENT[e.name]]
        # the slice keeps whole events only: the tick running at its end
        # is cut, and its children start after every whole tick
        assert any(_inside(e, p) for p in parents) or \
            all(p.end_ns <= e.start_ns for p in parents), e
    assert any(_inside(a, b) for a in by["engine.admit"]
               for b in host if b.name == "bench.admit")
    # on one thread, any two spans nest or are apart
    for a in host:
        for b in host:
            if a is not b and a.start_ns < b.end_ns and b.start_ns < a.end_ns:
                assert _inside(a, b) or _inside(b, a)
