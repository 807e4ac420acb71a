"""1M leases, live traffic, then: a 100x capacity cut (must reach
grants within ~2 ticks) and a mastership flip (fresh engine, recovery).
The server's own tick loop drives the ticks."""

import asyncio
import time

from _common import pin_platform_in_process, load_1m

CFG = """
resources:
- identifier_glob: "*"
  capacity: %d
  algorithm: {kind: PROPORTIONAL_SHARE, lease_length: 600,
              refresh_interval: 16, learning_mode_duration: 0}
"""


async def main():
    import grpc
    from doorman_tpu.proto import doorman_pb2 as pb
    from doorman_tpu.proto.grpc_api import CapacityStub
    from doorman_tpu.server.config import parse_yaml_config
    from doorman_tpu.server.election import TrivialElection
    from doorman_tpu.server.server import CapacityServer

    server = CapacityServer(
        "chaos1m", TrivialElection(), mode="batch", tick_interval=1.0,
        minimum_refresh_interval=0.0, native_store=True,
    )
    port = await server.start(0, host="127.0.0.1")
    await server.load_config(parse_yaml_config(CFG % 50000))
    await asyncio.sleep(0)
    server.current_master = f"127.0.0.1:{port}"

    load_1m(server)
    print("loaded; waiting for ticks", flush=True)
    for _ in range(60):
        await asyncio.sleep(1)
        if server._ticks_done >= 3:
            break
    assert server._ticks_done >= 3, "ticks never ran"

    async def ask(cid, rid, wants):
        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as ch:
            stub = CapacityStub(ch)
            req = pb.GetCapacityRequest(client_id=cid)
            rr = req.resource.add()
            rr.resource_id = rid
            rr.wants = wants
            out = await stub.GetCapacity(req)
            return out.response[0].gets.capacity

    # Steady state: a high-demand client on res7 gets its wants once
    # the tick carrying them lands (batch mode serves the LAST tick's
    # grant; new demand is visible at the next refresh).
    g = 0.0
    for _ in range(8):
        g = await ask("c700", "res7", 2000.0)
        if g > 1500.0:
            break
        await asyncio.sleep(1)
    print(f"pre-cut grant: {g:.0f}", flush=True)
    assert g > 1500.0, g

    # CAPACITY CUT 50000 -> 500: config-changed rows must be delivered
    # same-tick; the next refresh must see a clamped grant.
    t_cut = time.time()
    await server.load_config(parse_yaml_config(CFG % 500))
    ok = False
    for _ in range(8):
        await asyncio.sleep(1)
        g = await ask("c700", "res7", 2000.0)
        if g <= 500.0:
            ok = True
            break
    dt = time.time() - t_cut
    print(f"post-cut grant: {g:.0f} after {dt:.1f}s", flush=True)
    assert ok, f"capacity cut not reflected: {g}"
    assert dt < 6.0, f"cut took {dt:.1f}s to land"

    # MASTERSHIP FLIP at full scale: fresh engine, server keeps serving.
    await server._on_is_master(False)
    await server._on_is_master(True)
    g = await ask("c700", "res7", 300.0)
    print(f"post-flip first grant: {g:.0f}", flush=True)
    for _ in range(10):
        await asyncio.sleep(1)
        g = await ask("c700", "res7", 300.0)
        if g >= 299.0:
            break
    assert g >= 299.0, f"no recovery after flip: {g}"
    print(f"post-flip recovered grant: {g:.0f}")
    print("CHAOS 1M OK")
    await server.stop()


pin_platform_in_process()
asyncio.run(main())
