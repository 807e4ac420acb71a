"""The span readers on a hand-built ring and tick list: the decomposition
of the Tick driver's residual, spans outside the window's ticks left
out, and nothing to read when the ring lost spans from the window."""

from types import SimpleNamespace

import pytest

import harness
import spans
from doorman_tpu.obs import trace

E = trace._EPOCH  # perf_counter at the tracer's zero
NEW = ["tick_partition_ms.tick", "tick_offload_ms.tick",
       "tick_flightrec_ms.tick", "tick_gc_ms.tick",
       "engine_unlapped_ms.tick", "tick_unattributed_ms.tick"]


def ev(span_id, parent, name, t0, dur, cat="tick"):
    """A complete event starting t0 seconds after the tracer's zero."""
    return trace.Span(name, cat, "X", 1, span_id, parent, t0 * 1e6,
                      dur * 1e6, 0, None)


def one_tick(base, t0, part, off, eng, laps, rec, gc_in, gc_top, persist):
    """server.tick's events in one tick, ids from `base`."""
    return [
        ev(base + 1, base + 0, "tick.partition", t0 + 0.001, part),
        ev(base + 2, base + 1, spans.GC, t0 + 0.002, gc_in, "gc"),
        ev(base + 4, base + 3, "fused", t0 + 0.020, laps[0],
           "phase:resident"),
        ev(base + 5, base + 3, "apply", t0 + 0.021, laps[1],
           "phase:resident"),
        ev(base + 3, base + 6, "tick.engine", t0 + 0.019, eng),
        ev(base + 6, base + 0, "tick.offload", t0 + 0.018, off),
        ev(base + 7, base + 0, "tick.persist", t0 + 0.060, persist),
        ev(base + 8, base + 0, "tick.flightrec", t0 + 0.061, rec),
        ev(base + 9, base + 0, spans.GC, t0 + 0.090, gc_top, "gc"),
        ev(base + 0, 0, "server.tick", t0 + 0.0005, 0.095),
    ]


@pytest.fixture
def run(monkeypatch):
    """Two window ticks, 100 and 120 ms, with spans around them."""
    ring = (
        # Before the window: a tick the window does not hold.
        one_tick(100, 0.5, 0.05, 0.05, 0.04, (0.01, 0.01), 0.05, 0.01,
                   0.01, 0.01)
        + one_tick(200, 1.0, 0.010, 0.030, 0.020, (0.002, 0.003), 0.025,
                   0.004, 0.001, 0.0005)
        # Between the two ticks (the writer's time).
        + [ev(300, 0, "tick.partition", 1.150, 0.009),
           ev(301, 0, spans.GC, 1.160, 0.007, "gc")]
        + one_tick(400, 1.2, 0.012, 0.036, 0.028, (0.004, 0.0), 0.027,
                   0.0, 0.0, 0.0)
    )
    state = {"ring": ring, "capacity": 65536}
    monkeypatch.setattr(spans, "ring",
                        lambda: (state["ring"], state["capacity"]))
    ticks = [
        {"start": E + 1.0, "wall_s": 0.100,
         "phases": {"fused": 0.002, "apply": 0.003}},
        {"start": E + 1.2, "wall_s": 0.120,
         "phases": {"fused": 0.004, "apply": 0.0}},
    ]
    return SimpleNamespace(ticks=ticks), state


def read(name, r):
    return harness.reader(name)(r)


def test_span_readers_decompose_the_residual(run):
    r, _ = run
    got = {name: read(name, r) for name in NEW}
    want = {
        "tick_partition_ms.tick": (10 + 12) / 2,
        "tick_offload_ms.tick": ((30 - 20) + (36 - 28)) / 2,
        "tick_flightrec_ms.tick": (25 + 27) / 2,
        "tick_gc_ms.tick": ((4 + 1) + 0) / 2,
        "engine_unlapped_ms.tick": ((20 - 5) + (28 - 4)) / 2,
        # wall - (partition + offload + persist + flightrec)
        "tick_unattributed_ms.tick": ((100 - 10 - 30 - 0.5 - 25)
                                      + (120 - 12 - 36 - 0 - 27)) / 2,
    }
    for name in NEW:
        assert got[name] == pytest.approx(want[name], rel=1e-9), name
    persist = (0.5 + 0) / 2
    server = read("tick_server_ms.tick", r)
    assert server == pytest.approx(
        got["tick_partition_ms.tick"] + got["tick_offload_ms.tick"]
        + got["tick_flightrec_ms.tick"] + got["engine_unlapped_ms.tick"]
        + persist + got["tick_unattributed_ms.tick"], rel=1e-9)


def test_span_readers_skip_spans_outside_window_ticks(run):
    r, state = run
    before = {name: read(name, r) for name in NEW}
    # Only spans outside the window's ticks go: nothing moves.
    state["ring"] = [e for e in state["ring"]
                     if not (e.span_id < 200 or 300 <= e.span_id < 400)]
    assert {name: read(name, r) for name in NEW} == before


def test_span_readers_none_when_the_ring_lost_window_spans(run):
    r, state = run
    ring = state["ring"]
    # Full, but its oldest span ended before the window: nothing lost.
    state["capacity"] = len(ring)
    assert read("tick_partition_ms.tick", r) is not None
    # Full, and the oldest left starts inside the window's first tick.
    state["ring"] = ring[11:]
    state["capacity"] = len(state["ring"])
    for name in NEW:
        assert read(name, r) is None, name


def test_span_readers_none_without_tick_spans(run):
    r, state = run
    state["ring"] = [e for e in state["ring"] if e.name != "server.tick"]
    for name in NEW:
        assert read(name, r) is None, name
    state["ring"] = []
    assert read("tick_gc_ms.tick", r) is None
    assert read("tick_gc_ms.tick", SimpleNamespace(ticks=[])) is None
