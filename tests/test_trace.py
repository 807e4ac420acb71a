"""End-to-end tick tracing tests: span nesting, trace-context
propagation over loopback gRPC (the client's refresh span must be an
ancestor of the server's handler span), Chrome trace-event export
schema, per-phase histogram exposition, unclosed-span detection, the
/debug/traces + /debug index routes, the chaos virtual-time export, and
the tracer's overhead budget (disabled = no-op; enabled = microseconds).
"""

import asyncio
import json
import time
import urllib.request

import pytest

import tests.conftest  # noqa: F401

from doorman_tpu.chaos.trace_export import chrome_trace, write_chrome_trace
from doorman_tpu.client import Client
from doorman_tpu.obs import DebugServer, default_registry
from doorman_tpu.obs import trace as trace_mod
from doorman_tpu.server.config import parse_yaml_config
from doorman_tpu.server.election import TrivialElection
from doorman_tpu.server.server import CapacityServer

CONFIG = """
resources:
- identifier_glob: "*"
  capacity: 100
  safe_capacity: 5
  algorithm: {kind: PROPORTIONAL_SHARE, lease_length: 60, refresh_interval: 1,
              learning_mode_duration: 0}
"""


@pytest.fixture
def tracer():
    """The process-global tracer, enabled for the test and restored
    after (other tests must see it disabled and empty)."""
    tr = trace_mod.default_tracer()
    tr.clear()
    tr.enable()
    yield tr
    tr.disable()
    tr.clear()


def fetch(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as resp:
        return resp.status, resp.read().decode()


# ----------------------------------------------------------------------
# Tracer core
# ----------------------------------------------------------------------


def test_span_nesting_and_instants(tracer):
    with tracer.span("outer", cat="t") as outer:
        tracer.instant("marker", cat="t")
        with tracer.span("inner", cat="t") as inner:
            pass
    events = {e.name: e for e in tracer.snapshot()}
    assert events["inner"].parent_id == outer.span_id
    assert events["inner"].trace_id == outer.trace_id
    assert events["marker"].parent_id == outer.span_id
    assert events["outer"].parent_id == 0
    assert events["outer"].dur >= events["inner"].dur >= 0.0


def test_disabled_tracer_records_nothing():
    tr = trace_mod.Tracer()
    assert not tr.enabled
    # One shared no-op context manager: no allocation per call.
    assert tr.span("a") is tr.span("b")
    with tr.span("a"):
        tr.instant("i")
        tr.add_complete("c", 0.0, 1.0)
    assert tr.snapshot() == []
    assert tr.open_spans() == []
    # Disabled tracer + no ambient span -> no metadata on the wire.
    assert trace_mod.grpc_metadata() == ()


def test_error_marks_span(tracer):
    with pytest.raises(ValueError):
        with tracer.span("boom"):
            raise ValueError("x")
    (ev,) = tracer.snapshot()
    assert ev.args["error"] == "ValueError"
    assert tracer.open_spans() == []


def test_unclosed_span_detection(tracer):
    cm = tracer.span("leaky")
    cm.__enter__()
    assert [s.name for s in tracer.open_spans()] == ["leaky"]
    cm.__exit__(None, None, None)
    assert tracer.open_spans() == []


def test_metadata_round_trip(tracer):
    with tracer.span("root"):
        md = trace_mod.grpc_metadata()
        assert md and md[0][0] == trace_mod.TRACE_METADATA_KEY
        ctx = trace_mod.parent_from_metadata(md)
        cur = trace_mod.current_context()
        assert ctx == cur
    # Garbage values parse to None, never raise.
    assert trace_mod.parent_from_metadata(
        ((trace_mod.TRACE_METADATA_KEY, "not-hex"),)
    ) is None
    assert trace_mod.parent_from_metadata(()) is None
    assert trace_mod.parent_from_grpc_context(None) is None


def test_ring_buffer_drops_oldest():
    tr = trace_mod.Tracer(capacity=4).enable()
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    names = [e.name for e in tr.snapshot()]
    assert names == ["s6", "s7", "s8", "s9"]


def test_jax_capture_noop_without_dir():
    with trace_mod.jax_capture(None):
        pass
    with trace_mod.jax_capture(""):
        pass


# ----------------------------------------------------------------------
# Chrome export schema
# ----------------------------------------------------------------------


def test_chrome_export_schema(tracer):
    with tracer.span("a", cat="x"):
        with tracer.span("b", cat="x"):
            pass
    tracer.instant("mark", cat="x")
    doc = tracer.chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    body = [e for e in events if e["ph"] not in ("M",)]
    assert body, "no span events exported"
    for ev in events:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
    for ev in body:
        assert ev["ts"] >= 0.0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
            assert "span_id" in ev["args"]
    # ts is monotonic non-decreasing in export order.
    ts = [e.get("ts", 0.0) for e in events]
    assert ts == sorted(ts)
    # The whole document is valid JSON (what Perfetto loads).
    json.loads(tracer.chrome_json())


# ----------------------------------------------------------------------
# Overhead budget (tier-1 keeps instrumentation honest)
# ----------------------------------------------------------------------


@pytest.mark.perf
def test_trace_overhead_budget():
    tr = trace_mod.Tracer()

    def cost(n=1000):
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("hot"):
                pass
        return (time.perf_counter() - t0) / n

    # Disabled: the shared no-op; generous 2 µs bound (it is one method
    # call and one `with`).
    disabled = min(cost() for _ in range(3))
    assert disabled < 2e-6, f"disabled span costs {disabled * 1e6:.2f} µs"

    tr.enable()
    # Enabled: ring-buffer append budget is ~10 µs; asserted loosely
    # (5x) so a noisy CI box cannot flake it while a 100 µs regression
    # still fails.
    enabled = min(cost() for _ in range(3))
    assert enabled < 50e-6, f"enabled span costs {enabled * 1e6:.2f} µs"


# ----------------------------------------------------------------------
# Loopback gRPC propagation + phase histograms + debug routes
# ----------------------------------------------------------------------


def test_loopback_trace_propagation_and_debug_pages(tracer):
    """The acceptance-criterion run: a real client refreshing against a
    real batch server over loopback gRPC, tracing enabled. The export
    must contain client refresh -> server GetCapacity parented across
    the hop, solver ticks with upload/solve/download/apply children
    (native store -> device-resident tick path; the python-store batch
    path's pack/solve/apply is covered by the same assertions when the
    native engine is unavailable), /metrics must expose per-phase
    histograms with non-zero counts, and no instrumented path may leak
    an open span."""
    from doorman_tpu import native

    native_store = native.native_available()
    component = "resident" if native_store else "batch"
    # The resident path runs the fused one-launch tick by default: the
    # device window is one "fused" phase span (round-trip mode would
    # emit upload/solve — see tests/test_fused_tick.py).
    phases = (
        ("fused", "download", "apply")
        if native_store
        else ("pack", "solve", "apply")
    )

    async def body():
        server = CapacityServer(
            "trace-server", TrivialElection(),
            minimum_refresh_interval=0.0, mode="batch",
            native_store=native_store,
        )
        port = await server.start(0, host="127.0.0.1")
        await server.load_config(parse_yaml_config(CONFIG))
        await asyncio.sleep(0)
        server.current_master = f"127.0.0.1:{port}"

        debug = DebugServer(host="127.0.0.1")
        debug.add_server(server, asyncio.get_running_loop())
        dport = debug.start()

        client = await Client.connect(
            f"127.0.0.1:{port}", "trace-client",
            minimum_refresh_interval=0.0,
        )
        res = await client.resource("r0", wants=40)
        cap = await asyncio.wait_for(res.capacity().get(), timeout=5)
        assert cap == 40.0
        # Two ticks: the resident path pipelines, so download/apply of
        # tick 1's grants land during tick 2's collect.
        await server.tick_once()
        await server.tick_once()

        loop = asyncio.get_running_loop()
        status, text = await loop.run_in_executor(
            None, fetch, dport, "/metrics"
        )
        status_traces, traces_page = await loop.run_in_executor(
            None, fetch, dport, "/debug/traces"
        )
        status_chrome, chrome = await loop.run_in_executor(
            None, fetch, dport, "/debug/traces?format=chrome"
        )
        status_index, index = await loop.run_in_executor(
            None, fetch, dport, "/debug"
        )

        await client.close()
        debug.stop()
        await server.stop()
        return (status, text, status_traces, traces_page,
                status_chrome, chrome, status_index, index)

    (status, text, status_traces, traces_page,
     status_chrome, chrome, status_index, index) = asyncio.run(body())

    # -- span parentage across the gRPC hop ---------------------------
    by_name = {}
    for ev in tracer.snapshot():
        by_name.setdefault(ev.name, []).append(ev)
    refresh = by_name["client.refresh"][0]
    rpc = by_name["client.GetCapacity"][0]
    handler = by_name["server.GetCapacity"][0]
    assert rpc.parent_id == refresh.span_id
    assert handler.parent_id == rpc.span_id
    assert handler.trace_id == refresh.trace_id

    # -- the solver tick spans hold the phase laps --------------------
    # (nested in the tick's engine spans: tick.offload > tick.engine >
    # engine.collect / engine.dispatch on the resident path)
    tick_ids = {t.span_id for t in by_name["server.tick"]}
    by_id = {ev.span_id: ev for ev in tracer.snapshot()}
    for phase in phases:
        assert phase in by_name, phase
        ev = by_name[phase][0]
        up = ev
        while up.parent_id and up.parent_id not in tick_ids:
            up = by_id[up.parent_id]
        assert up.parent_id in tick_ids, phase
        assert ev.cat == f"phase:{component}"

    # -- no instrumented path leaks an open span ----------------------
    assert tracer.open_spans() == []

    # -- /metrics: per-phase histograms with non-zero counts ----------
    assert status == 200
    for phase in phases:
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith(
                "doorman_tick_phase_seconds_count"
                f'{{component="{component}",phase="{phase}"}}'
            )
        )
        assert int(line.rsplit(" ", 1)[1]) > 0, line
    assert (
        f'doorman_tick_phase_last_seconds{{component="{component}"'
        in text
    )

    # -- /debug/traces + index + chrome download ----------------------
    assert status_traces == 200
    assert "tracer enabled" in traces_page
    assert "server.GetCapacity" in traces_page
    assert status_index == 200
    assert "/debug/traces" in index and "/metrics" in index
    assert status_chrome == 200
    doc = json.loads(chrome)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"client.refresh", "server.GetCapacity", "server.tick",
            "fused" if native_store else "solve"} <= names


def test_direct_handler_call_tolerates_no_context(tracer):
    """Tests and tooling drive handlers with context=None; the tracing
    wrapper must not assume gRPC invocation metadata exists."""
    from doorman_tpu.proto import doorman_pb2 as pb

    async def body():
        server = CapacityServer(
            "nc-server", TrivialElection(), minimum_refresh_interval=0.0
        )
        await server.load_config(parse_yaml_config(CONFIG))
        await asyncio.sleep(0)
        req = pb.GetCapacityRequest(client_id="c1")
        r = req.resource.add()
        r.resource_id = "r0"
        r.wants = 10.0
        out = await server.GetCapacity(req, None)
        assert out.response[0].gets.capacity == 10.0
        await server.stop()

    asyncio.run(body())
    assert [e.name for e in tracer.snapshot()
            if e.name == "server.GetCapacity"]
    assert tracer.open_spans() == []


def test_resident_phase_spans_and_histograms(tracer):
    """The device-resident tick path emits its phase laps (the fused
    device window by default, upload/solve in round-trip mode) as
    spans nested under the ambient tick span, and as per-phase
    histograms in the default registry. Both modes step so both
    vocabularies land."""
    from doorman_tpu import native

    if not native.native_available():
        pytest.skip("native engine unavailable")
    import numpy as np

    from doorman_tpu.core.resource import Resource
    from doorman_tpu.proto import doorman_pb2 as pb
    from doorman_tpu.solver.resident import ResidentDenseSolver

    engine = native.StoreEngine()
    tpl = pb.ResourceTemplate(
        identifier_glob="r0", capacity=100.0,
        algorithm=pb.Algorithm(
            kind=pb.Algorithm.PROPORTIONAL_SHARE,
            lease_length=60, refresh_interval=5,
        ),
    )
    res = Resource("r0", tpl, store_factory=engine.store)
    for c in range(4):
        res.store.assign(f"c{c}", 60.0, 5.0, 0.0, 10.0 * (c + 1), 1)
    solver = ResidentDenseSolver(
        engine, dtype=np.float64, rotate_ticks=1
    )
    with tracer.span("server.tick", cat="tick") as tick:
        solver.step([res])  # fused (default): one "fused" device lap
    solver.fused_tick = False
    res.store.assign("c0", 60.0, 5.0, 0.0, 15.0, 1)
    with tracer.span("server.tick", cat="tick") as tick2:
        solver.step([res])  # round-trip: upload + solve laps
    by_name = {}
    for ev in tracer.snapshot():
        by_name.setdefault(ev.name, []).append(ev)
    for phase, parent in (
        ("sweep", tick), ("drain", tick), ("pack", tick),
        ("fused", tick), ("download", tick), ("apply", tick),
        ("rebuild", tick), ("upload", tick2), ("solve", tick2),
    ):
        assert phase in by_name, phase
        ev = by_name[phase][0]
        assert ev.parent_id == parent.span_id, phase
        assert ev.cat == "phase:resident"
    assert tracer.open_spans() == []
    text = default_registry().expose()
    for phase in ("fused", "upload"):
        assert (
            'doorman_tick_phase_seconds_count{component="resident",'
            f'phase="{phase}"}}' in text
        )


# ----------------------------------------------------------------------
# Chaos: virtual-time Chrome export + fault/violation counters
# ----------------------------------------------------------------------


def test_chaos_chrome_export(tmp_path):
    verdict = {
        "plan": "unit",
        "tick_interval": 0.5,
        "event_log": [
            [2, "fault", "grpc_drop", "link:s0", 4],
            [3, "master", ["s1"]],
            [5, "violation", "capacity", "r0", "over by 1"],
            [6, "degraded"],
            [9, "converged", 3],
        ],
    }
    doc = chrome_trace(verdict)
    events = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert len(events) == 5
    fault = next(e for e in events if e["ph"] == "X")
    assert fault["name"] == "grpc_drop(link:s0)"
    assert fault["ts"] == 2 * 0.5 * 1e6
    assert fault["dur"] == 4 * 0.5 * 1e6
    for ev in events:
        assert {"name", "ph", "pid", "tid", "ts"} <= set(ev)
    ts = [e.get("ts", 0.0) for e in doc["traceEvents"]]
    assert ts == sorted(ts)
    out = tmp_path / "chaos_trace.json"
    write_chrome_trace(verdict, str(out))
    json.loads(out.read_text())


def test_chaos_counters_in_default_registry():
    from doorman_tpu.chaos.plan import FaultEvent, FaultPlan
    from doorman_tpu.chaos.runner import ChaosRunner

    plan = FaultPlan(name="unit-counters", seed=0, setup={})
    runner = ChaosRunner(plan)
    before = runner._faults_counter.value("grpc_drop")
    runner._apply_event(
        FaultEvent(kind="grpc_drop", target="link:s0", at_tick=5,
                   duration_ticks=2),
        tick=5,
    )
    assert runner._faults_counter.value("grpc_drop") == before + 1
    from doorman_tpu.chaos.invariants import Violation

    vbefore = runner._violations_counter.value("capacity")
    runner._record_violation(Violation(1, "capacity", "r0", "x"))
    assert runner._violations_counter.value("capacity") == vbefore + 1
    text = default_registry().expose()
    assert "doorman_chaos_faults_injected" in text
    assert "doorman_chaos_invariant_violations" in text
