"""Full-scale live drive: a CapacityServer holding 1M leases across 10k
resources ticks through the device-resident path on the real TPU while
200 gRPC clients keep refreshing. Measures tick wall time and request
latency under concurrent load. The server's own tick loop is parked
(huge tick_interval) so the measured manual ticks are the only ones —
double-ticking would inflate the latencies via the tick lock."""

import asyncio
import time

import numpy as np

from _common import pin_platform_in_process, NUM_RES, load_1m


async def main():
    import grpc
    from doorman_tpu.proto import doorman_pb2 as pb
    from doorman_tpu.proto.grpc_api import CapacityStub
    from doorman_tpu.server.config import parse_yaml_config
    from doorman_tpu.server.election import TrivialElection
    from doorman_tpu.server.server import CapacityServer

    server = CapacityServer(
        "live1m", TrivialElection(), mode="batch", tick_interval=3600.0,
        minimum_refresh_interval=0.0, native_store=True,
    )
    port = await server.start(0, host="127.0.0.1")
    await server.load_config(parse_yaml_config("""
resources:
- identifier_glob: "*"
  capacity: 50000
  algorithm: {kind: PROPORTIONAL_SHARE, lease_length: 600,
              refresh_interval: 16, learning_mode_duration: 0}
"""))
    await asyncio.sleep(0)
    server.current_master = f"127.0.0.1:{port}"

    t0 = time.perf_counter()
    load_1m(server)
    print(f"loaded 1M leases in {time.perf_counter()-t0:.1f}s", flush=True)

    # Warm up the resident pipeline (compile).
    t0 = time.perf_counter()
    await server.tick_once()
    print(f"first tick (compile) {time.perf_counter()-t0:.1f}s", flush=True)
    for _ in range(4):
        await server.tick_once()

    # Live load: 200 clients refresh continuously for 30s while ticks
    # run on the event loop's executor.
    lat = []
    stop_at = time.time() + 30.0

    async def client_loop(i):
        rid = f"res{i * 37 % NUM_RES}"
        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as ch:
            stub = CapacityStub(ch)
            has = 0.0
            while time.time() < stop_at:
                req = pb.GetCapacityRequest(client_id=f"c{i}")
                rr = req.resource.add()
                rr.resource_id = rid
                rr.wants = 50.0
                rr.has.capacity = has
                t = time.perf_counter()
                out = await stub.GetCapacity(req)
                lat.append(time.perf_counter() - t)
                has = out.response[0].gets.capacity
                await asyncio.sleep(0.05)

    async def tick_loop():
        times = []
        while time.time() < stop_at:
            t = time.perf_counter()
            await server.tick_once()
            times.append(time.perf_counter() - t)
            await asyncio.sleep(max(0.0, 1.0 - (time.perf_counter() - t)))
        return times

    tick_task = asyncio.create_task(tick_loop())
    await asyncio.gather(*(client_loop(i) for i in range(200)))
    ticks = await tick_task

    lat_ms = np.array(lat) * 1000.0
    tick_ms = np.array(ticks) * 1000.0
    print(
        f"requests={len(lat_ms)} "
        f"p50={np.percentile(lat_ms,50):.1f}ms "
        f"p99={np.percentile(lat_ms,99):.1f}ms max={lat_ms.max():.1f}ms"
    )
    print(
        f"ticks={len(tick_ms)} median={np.median(tick_ms):.1f}ms "
        f"p90={np.percentile(tick_ms,90):.1f}ms"
    )
    # Regression rails, not records (the rails predate the v5e chip
    # and are not yet measured there).
    assert np.percentile(lat_ms, 50) < 150.0, "request p50 too high"
    assert np.percentile(lat_ms, 99) < 600.0, "request p99 too high"
    assert np.median(tick_ms) < 100.0, "tick over the target at 1M live"
    print("LIVE 1M OK")
    await server.stop()


pin_platform_in_process()
asyncio.run(main())
