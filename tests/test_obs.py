"""Observability tests: metrics registry exposition, server/client
instrumentation, and the debug HTTP pages (capability parity with
reference status_test.go:42-70 — pages served over real HTTP)."""

import asyncio
import urllib.request

import tests.conftest  # noqa: F401

from doorman_tpu.client import Client
from doorman_tpu.obs import (
    DebugServer,
    Registry,
    add_status_part,
    instrument_server,
)
from doorman_tpu.obs.metrics import instrument_client
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


def fetch(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as resp:
        return resp.status, resp.read().decode()


def test_counter_gauge_exposition():
    reg = Registry()
    c = reg.counter("requests_total", "Total requests.", labels=("method",))
    c.inc("GetCapacity")
    c.inc("GetCapacity")
    c.inc("Release", by=3)
    g = reg.gauge("temperature", "Now.")
    g.set(36.5)
    text = reg.expose()
    assert "# TYPE requests_total counter" in text
    assert 'requests_total{method="GetCapacity"} 2' in text
    assert 'requests_total{method="Release"} 3' in text
    assert "# HELP temperature Now." in text
    assert "temperature 36.5" in text


def test_request_log_stamps_via_injected_clock():
    # The clock is an injectable seam (doormanlint seeded-determinism):
    # a chaos-driven server's samples must carry VIRTUAL time, and the
    # explicit `when` override must win over the clock.
    from doorman_tpu.obs.requests import RequestLog

    t = [1000.0]
    log = RequestLog(clock=lambda: t[0])
    log.record("GetCapacity", "c1", ["r0"], 5.0, 0.01, False)
    t[0] = 2000.0
    log.record("Release", "c1", ["r0"], 0.0, 0.01, False, when=42.0)
    newest, oldest = log.snapshot()
    assert oldest.when == 1000.0
    assert newest.when == 42.0


def test_histogram_exposition():
    reg = Registry()
    h = reg.histogram("latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.expose()
    assert 'latency_bucket{le="0.1"} 1' in text
    assert 'latency_bucket{le="1"} 2' in text
    assert 'latency_bucket{le="+Inf"} 3' in text
    assert "latency_count 3" in text
    assert abs(h.sum() - 5.55) < 1e-9


def test_registry_dedupes_by_name():
    reg = Registry()
    a = reg.counter("x")
    b = reg.counter("x")
    assert a is b


def test_label_escaping():
    reg = Registry()
    c = reg.counter("c", labels=("v",))
    c.inc('say "hi"\n')
    assert 'c{v="say \\"hi\\"\\n"} 1' in reg.expose()


def test_instrumented_server_and_debug_pages():
    async def body():
        server = CapacityServer(
            "obs-server", TrivialElection(), minimum_refresh_interval=0.0
        )
        reg = Registry()
        instrument_server(server, reg)
        port = await server.start(0, host="127.0.0.1")
        await server.load_config(parse_yaml_config(CONFIG))
        await asyncio.sleep(0)
        server.current_master = f"127.0.0.1:{port}"

        debug = DebugServer(host="127.0.0.1", registry=reg)
        debug.add_server(server, asyncio.get_running_loop())
        dport = debug.start()
        add_status_part("test-part", lambda: "<p>part-content-xyz</p>")

        client = await Client.connect(
            f"127.0.0.1:{port}", "client-1", minimum_refresh_interval=0.0
        )
        instrument_client(client, reg)
        res = await client.resource("r0", wants=40)
        cap = await asyncio.wait_for(res.capacity().get(), timeout=5)
        assert cap == 40.0

        loop = asyncio.get_running_loop()
        status, text = await loop.run_in_executor(
            None, fetch, dport, "/metrics"
        )
        assert status == 200
        assert (
            'doorman_server_requests_count{method="GetCapacity"} 1' in text
        )
        assert "doorman_server_requests_durations_bucket" in text
        assert 'doorman_server_resource_wants{resource="r0"} 40' in text
        assert "doorman_server_is_master 1" in text
        assert "doorman_client_requests_durations_count" in text

        status, page = await loop.run_in_executor(
            None, fetch, dport, "/debug/status"
        )
        assert status == 200
        assert "obs-server" in page
        assert "r0" in page
        assert "part-content-xyz" in page

        status, page = await loop.run_in_executor(
            None, fetch, dport, "/debug/resources?resource=r0"
        )
        assert status == 200
        assert "client-1" in page

        # The request sample ring renders the RPC we just made.
        status, page = await loop.run_in_executor(
            None, fetch, dport, "/debug/requests"
        )
        assert status == 200
        assert "GetCapacity" in page
        assert "client-1" in page
        assert "r0" in page
        sample = server.request_log.snapshot(1)[0]
        assert sample.method == "GetCapacity"
        assert sample.wants == 40.0
        assert not sample.error

        status, _ = await loop.run_in_executor(None, fetch, dport, "/healthz")
        assert status == 200

        await client.close()
        debug.stop()
        await server.stop()

    asyncio.run(body())


def test_debug_frontend_page_renders_inline_pool():
    """/debug/frontend renders the serving-plane pool's liveness, held
    streams, and per-worker pump counters; ?format=json mirrors the
    pool's status dict. Servers without a pool say so instead of 500."""
    import json

    from doorman_tpu.proto import doorman_stream_pb2 as spb

    async def body():
        server = CapacityServer(
            "fe-obs", TrivialElection(), minimum_refresh_interval=0.0,
            mode="immediate", stream_push=True, stream_shards=4,
        )
        pool = server.attach_frontend(2)
        port = await server.start(0, host="127.0.0.1")
        await server.load_config(parse_yaml_config(CONFIG))
        await asyncio.sleep(0)
        server.current_master = f"127.0.0.1:{port}"

        bare = CapacityServer(
            "fe-none", TrivialElection(), minimum_refresh_interval=0.0
        )

        req = spb.WatchCapacityRequest(client_id="w1")
        rr = req.resource.add()
        rr.resource_id = "r0"
        rr.wants = 5.0
        sub = server._streams.subscribe(req)
        server._stream_match_add(sub)
        pool.pump_all()

        debug = DebugServer(host="127.0.0.1", registry=Registry())
        loop = asyncio.get_running_loop()
        debug.add_server(server, loop)
        debug.add_server(bare, loop)
        dport = debug.start()
        try:
            status, page = await loop.run_in_executor(
                None, fetch, dport, "/debug/frontend"
            )
            assert status == 200
            assert "mode: inline" in page
            assert "workers live: 2/2" in page
            assert "held: 1" in page
            assert "no frontend pool attached" in page  # fe-none
            status, text = await loop.run_in_executor(
                None, fetch, dport, "/debug/frontend?format=json"
            )
            assert status == 200
            st = json.loads(text)
            assert st["fe-none"] is None
            assert st["fe-obs"]["held"] == 1
            assert st["fe-obs"]["live"] == [0, 1]
            assert sum(
                w["frames"] for w in st["fe-obs"]["per_worker"]
            ) >= 1
        finally:
            debug.stop()
            await server.stop()

    asyncio.run(body())


def test_batch_tick_profiler_trace(tmp_path):
    """--profile-dir writes a JAX profiler trace of `profile_ticks`
    ticks, started and stopped between ticks after the first applied
    one, and the capture holds the program's tick spans."""
    import jax

    jax.config.update("jax_enable_x64", True)

    async def body():
        server = CapacityServer(
            "prof-server", TrivialElection(), minimum_refresh_interval=0.0,
            mode="batch", profile_dir=str(tmp_path), profile_ticks=1,
        )
        await server.load_config(parse_yaml_config(CONFIG))
        await asyncio.sleep(0)
        from doorman_tpu.proto import doorman_pb2 as pb

        req = pb.GetCapacityRequest()
        req.client_id = "c1"
        r = req.resource.add()
        r.resource_id = "r0"
        r.wants = 10.0
        await server.GetCapacity(req, None)
        await server.tick_once()
        assert server._profiling  # started after the first tick
        await server.tick_once()
        assert not server._profiling

    asyncio.run(body())
    (path,) = tmp_path.rglob("*.xplane.pb")
    from jax.profiler import ProfileData

    names = [
        ev.name
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for ev in line.events
    ]
    # The second tick alone, with its sections.
    assert names.count("server.tick") == 1
    assert names.count("tick.partition") == 1
