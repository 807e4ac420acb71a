"""The Tick driver's section spans: the span tree one batch tick records,
what it records with the tracer off, and what it records under a
jax.profiler capture (the ring, the gc pauses, and the same names on the
capture's host timeline, nested in the caller's annotation)."""

import asyncio
import gc
import glob
import os
import subprocess
import sys
import time

import pytest

import tests.conftest  # noqa: F401

from doorman_tpu import native
from doorman_tpu.obs import trace as trace_mod
from doorman_tpu.server.config import parse_yaml_config
from doorman_tpu.server.election import TrivialElection
from doorman_tpu.server.server import CapacityServer

CONFIG = """
resources:
- identifier_glob: "*"
  capacity: 100
  algorithm: {kind: PROPORTIONAL_SHARE, lease_length: 60, refresh_interval: 1,
              learning_mode_duration: 0}
"""

# The direct children of server.tick (stream.fanout only with
# subscribers, tick.admission only with admission control).
CHILDREN = {
    "tick.partition", "tick.offload", "tick.persist", "stream.fanout",
    "tick.audit", "tick.flightrec", "tick.admission",
}

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native engine unavailable"
)


@pytest.fixture
def tracer():
    """The process-global tracer, cleared before and after, and left
    disabled (other tests must see it disabled and empty)."""
    tr = trace_mod.default_tracer()
    tr.disable()
    tr.clear()
    yield tr
    tr.disable()
    tr.clear()


def run_ticks(n_ticks, before_tick=None):
    """A native batch server (resident path, depth 1, admission on) with
    four leases on two resources; runs n_ticks ticks. `before_tick(i)`
    may return a context manager to enter around tick i."""
    from doorman_tpu.admission import Admission
    from doorman_tpu.proto import doorman_pb2 as pb

    async def body():
        server = CapacityServer(
            "spans", TrivialElection(), minimum_refresh_interval=0.0,
            mode="batch", native_store=True, tick_pipeline_depth=1,
            admission=Admission(),
        )
        await server.load_config(parse_yaml_config(CONFIG))
        await asyncio.sleep(0)
        for c in range(4):
            req = pb.GetCapacityRequest(client_id=f"c{c}")
            r = req.resource.add()
            r.resource_id = f"r{c % 2}"
            r.wants = 10.0 * (c + 1)
            await server.GetCapacity(req, None)
        try:
            for i in range(n_ticks):
                cm = before_tick(i) if before_tick else None
                if cm is None:
                    await server.tick_once()
                else:
                    with cm:
                        await server.tick_once()
        finally:
            await server.stop()

    asyncio.run(body())


def tree(events):
    by_id = {e.span_id: e for e in events}
    kids = {}
    for e in events:
        kids.setdefault(e.parent_id, []).append(e)
    return by_id, kids


def test_tick_span_tree(tracer):
    tracer.enable()
    run_ticks(2)
    events = tracer.snapshot()
    assert tracer.open_spans() == []
    by_id, kids = tree(events)
    ticks = [e for e in events if e.name == "server.tick"]
    assert len(ticks) == 2
    for tick in ticks:
        children = [e for e in kids[tick.span_id] if e.ph == "X"]
        names = {e.name for e in children}
        assert names <= CHILDREN, names
        assert names >= CHILDREN - {"stream.fanout"}, names
        for e in children:
            assert tick.ts <= e.ts and e.ts + e.dur <= tick.ts + tick.dur
        assert sum(e.dur for e in children) <= tick.dur
        (partition,) = [e for e in children if e.name == "tick.partition"]
        assert partition.span_id not in kids
        (offload,) = [e for e in children if e.name == "tick.offload"]
        (engine,) = kids[offload.span_id]
        assert engine.name == "tick.engine"
        assert engine.tid != offload.tid  # the executor thread
        stages = {e.name: e for e in kids[engine.span_id]}
        assert set(stages) == {"engine.collect", "engine.dispatch"}
    # The phase laps sit in the engine's two stages.
    laps = [e for e in events if e.cat == "phase:resident"]
    assert {"fused", "download", "apply"} <= {e.name for e in laps}
    for lap in laps:
        assert by_id[lap.parent_id].name in ("engine.collect",
                                             "engine.dispatch")
    # The first tick builds the lane partition, the second reuses it.
    partitions = [e for e in events if e.name == "tick.partition"]
    assert [e.args for e in partitions] == [{"rebuilt": 1}, {"rebuilt": 0}]


def test_flightrec_digest_span_reads_the_bulk_gather(tracer):
    """The store digest is a child of tick.flightrec, not a section of
    its own, and on the native store it gathers in one engine call."""
    tracer.enable()
    run_ticks(2)
    by_id, _ = tree(tracer.snapshot())
    digests = [e for e in by_id.values() if e.name == "flightrec.digest"]
    assert len(digests) == 2
    for e in digests:
        assert by_id[e.parent_id].name == "tick.flightrec"
        assert e.args == {"path": "bulk", "resources": 2}


def test_tick_records_nothing_without_tracer_or_capture(tracer):
    assert not trace_mod.capture_active()
    run_ticks(2)
    assert tracer.snapshot() == []
    assert tracer.open_spans() == []
    assert tracer._gc_hook not in gc.callbacks


def test_tick_spans_under_profiler_capture(tracer, tmp_path):
    """A capture alone (tracer disabled) records the tick's spans into
    the ring, each gc pause as a `gc` event, and the same span names on
    the capture's host timeline inside the caller's annotation."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    def around(i):
        if i == 0:
            return None  # the compiling tick stays out of the capture

        class Captured:
            def __enter__(self):
                jax.profiler.start_trace(str(tmp_path))
                self.ann = TraceAnnotation("outer.tick")
                self.ann.__enter__()
                with tracer.span("tick.audit", cat="tick") as span:
                    gc.collect()
                spans.append(span)

            def __exit__(self, *exc):
                self.ann.__exit__(None, None, None)
                jax.profiler.stop_trace()

        return Captured()

    spans = []
    run_ticks(2, around)
    assert not tracer.enabled
    events = tracer.snapshot()
    names = {e.name for e in events}
    assert {"server.tick", "tick.partition", "tick.offload", "tick.engine",
            "tick.flightrec", "engine.dispatch", "fused"} <= names
    (pause,) = [e for e in events if e.name == trace_mod.GC_EVENT
                and e.parent_id == spans[0].span_id]
    assert pause.args == {"generation": 2} and pause.dur > 0
    assert tracer.open_spans() == []
    # Off again: the next check after the capture unhooks the gc watch.
    with tracer.span("tick.audit"):
        pass
    assert tracer._gc_hook not in gc.callbacks
    assert len(tracer.snapshot()) == len(events)

    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    host = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                host.append((ev.name, ev.start_ns, ev.duration_ns))
    (outer,) = [h for h in host if h[0] == "outer.tick"]
    lo, hi = outer[1], outer[1] + outer[2]
    for name in ("server.tick", "tick.partition", "tick.offload",
                 "tick.flightrec", "tick.engine"):
        got = [h for h in host if h[0] == name]
        assert len(got) == 1, (name, got)
        assert lo <= got[0][1] and got[0][1] + got[0][2] <= hi, name


def test_capture_grows_the_ring(tmp_path):
    """A capture grows a small ring to CAPTURE_CAPACITY, so it keeps more
    spans than the ring held before, and they stay readable after it."""
    import jax

    tr = trace_mod.Tracer(capacity=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(10):
            with tr.span("server.tick"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert tr.capacity == trace_mod.CAPTURE_CAPACITY
    assert [e.name for e in tr.snapshot()] == ["server.tick"] * 10
    with tr.span("server.tick"):  # off again: the ring keeps its size
        pass
    assert tr.capacity == trace_mod.CAPTURE_CAPACITY
    assert len(tr.snapshot()) == 10
    assert tr._gc_hook not in gc.callbacks


def test_trace_module_needs_no_jax():
    """obs/trace.py stays importable without jax, and without it no
    capture is running."""
    code = (
        "import sys; import doorman_tpu.obs.trace as t; "
        "assert 'jax' not in sys.modules; "
        "assert not t.capture_active(); "
        "tr = t.Tracer(); "
        "assert tr.span('server.tick') is t.NOOP_SPAN; "
        "assert 'jax' not in sys.modules"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-c", code], cwd=root, check=True,
                   timeout=60)


def test_capture_spans_and_gc_events_across_threads(tracer, tmp_path):
    """Under a capture, threads that open spans while collections run
    all the time (a gc event recorded from inside the tracer's locked
    block, on a thread it has not seen yet, among them) neither
    deadlock nor leak a span, with a short switch interval."""
    import threading

    import jax

    def work():
        for _ in range(100):
            tracer.open_spans()
            with tracer.span("tick.engine"):
                junk = [[i] for i in range(20)]
                del junk

    old = sys.getswitchinterval()
    threshold = gc.get_threshold()
    sys.setswitchinterval(1e-5)
    jax.profiler.start_trace(str(tmp_path))
    gc.set_threshold(1)  # collect on almost every allocation
    try:
        threads = [threading.Thread(target=work, daemon=True)
                   for _ in range(12)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 60
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        assert not any(t.is_alive() for t in threads)
    finally:
        gc.set_threshold(*threshold)
        jax.profiler.stop_trace()
        sys.setswitchinterval(old)
    events = tracer.snapshot()
    assert sum(e.name == "tick.engine" for e in events) == 12 * 100
    assert any(e.name == trace_mod.GC_EVENT for e in events)
    assert tracer.open_spans() == []
