"""End-to-end server coverage of the WIDE resident path: a lane
resource wider than the dense bucket cap partitions onto the chunked
solver (solver/resident_wide.py) from the very first eligibility check
— no ResidentOverflow round-trip — and serves correct, capacity-safe
grants over real gRPC, mixed alongside narrow resources on the narrow
resident solver.

DENSE_MAX_K is monkeypatched small so the boundary is exercised with
test-sized populations; boundary widths (cap, cap+1) pin the partition
edge itself."""

import asyncio
import time

import grpc
import numpy as np
import pytest

import tests.conftest  # noqa: F401

from doorman_tpu import native
from doorman_tpu.proto import doorman_pb2 as pb
from doorman_tpu.proto.grpc_api import CapacityStub
from doorman_tpu.server.config import parse_yaml_config
from doorman_tpu.server.election import TrivialElection
from doorman_tpu.server.server import CapacityServer

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native engine unavailable"
)

CONFIG = """
resources:
- identifier_glob: "wide"
  capacity: 1000
  algorithm: {kind: PROPORTIONAL_SHARE, lease_length: 60,
              refresh_interval: 1, learning_mode_duration: 0}
- identifier_glob: "*"
  capacity: 500
  algorithm: {kind: FAIR_SHARE, lease_length: 60, refresh_interval: 1,
              learning_mode_duration: 0}
"""


def patch_cap(monkeypatch, cap):
    """The dense bucket cap is read at call time in the three modules
    that partition or overflow on it."""
    import doorman_tpu.solver.batch as batch_mod
    import doorman_tpu.solver.resident as resident_mod
    import doorman_tpu.solver.resident_wide as wide_mod

    monkeypatch.setattr(batch_mod, "DENSE_MAX_K", cap)
    monkeypatch.setattr(resident_mod, "DENSE_MAX_K", cap)
    monkeypatch.setattr(wide_mod, "DENSE_MAX_K", cap)


def bulk_load(server, resource_id, n, wants=5.0):
    engine = server._store_factory.__self__
    res = server.resources[resource_id]
    rids = np.full(n, res.store._rid, np.int32)
    cids = np.array(
        [engine.client_handle(f"bulk_{resource_id}_{i}") for i in range(n)],
        np.int64,
    )
    engine.bulk_assign(
        rids, cids, np.full(n, time.time() + 60.0),
        np.full(n, 1.0), np.zeros(n),
        np.full(n, wants), np.ones(n, np.int32),
    )
    return res


def test_wide_resource_partitions_to_chunked_solver(monkeypatch):
    patch_cap(monkeypatch, 16)

    async def body():
        server = CapacityServer(
            "widesrv", TrivialElection(), mode="batch",
            tick_interval=0.05, minimum_refresh_interval=0.0,
            native_store=True,
        )
        port = await server.start(0, host="127.0.0.1")
        await server.load_config(parse_yaml_config(CONFIG))
        server.current_master = f"127.0.0.1:{port}"
        addr = f"127.0.0.1:{port}"

        async with grpc.aio.insecure_channel(addr) as ch:
            stub = CapacityStub(ch)

            def request(i, resource, wants):
                req = pb.GetCapacityRequest(client_id=f"c{i}")
                rr = req.resource.add()
                rr.resource_id = resource
                rr.wants = wants
                return req

            # Prime both resources over gRPC, then bulk-grow "wide"
            # past the (patched) cap BEFORE the first tick partitions.
            await stub.GetCapacity(request(0, "wide", 5.0))
            await stub.GetCapacity(request(0, "narrow", 5.0))
            res = bulk_load(server, "wide", 40, wants=40.0)
            assert len(res.store) > 16

            for _ in range(200):
                if (
                    server._resident_wide is not None
                    and server._resident_wide.ticks >= 3
                    and server._resident is not None
                    and server._resident.ticks >= 3
                ):
                    break
                await asyncio.sleep(0.05)
            # Partitioned directly — no overflow fallback needed.
            assert server._resident_wide is not None
            assert server._resident_wide.ticks >= 3
            assert "wide" in server._partition.wide_ids
            # The narrow resource kept the narrow resident solver.
            assert server._resident is not None
            assert server._resident.ticks >= 3
            assert "narrow" not in server._partition.wide_ids

            # Oversubscribed proportional share: grants scale to
            # capacity; the store conserves exactly.
            out = await stub.GetCapacity(request(0, "wide", 40.0))
            got = out.response[0].gets.capacity
            assert 0.0 <= got <= 40.0
            assert res.store.sum_has <= 1000.0 + 1e-6
            leases = dict(res.store.items())
            lease_sum = sum(l.has for l in leases.values())
            assert abs(lease_sum - res.store.sum_has) < 1e-6

        await server.stop()

    asyncio.run(body())


@pytest.mark.parametrize("width,expect_wide", [(16, False), (17, True)])
def test_partition_boundary(monkeypatch, width, expect_wide):
    """Exactly at the cap stays narrow; one past it goes wide."""
    patch_cap(monkeypatch, 16)

    async def body():
        server = CapacityServer(
            "boundary", TrivialElection(), mode="batch",
            tick_interval=0.05, minimum_refresh_interval=0.0,
            native_store=True,
        )
        port = await server.start(0, host="127.0.0.1")
        await server.load_config(parse_yaml_config(CONFIG))
        server.current_master = f"127.0.0.1:{port}"
        addr = f"127.0.0.1:{port}"

        async with grpc.aio.insecure_channel(addr) as ch:
            stub = CapacityStub(ch)
            req = pb.GetCapacityRequest(client_id="c0")
            rr = req.resource.add()
            rr.resource_id = "wide"
            rr.wants = 5.0
            await stub.GetCapacity(req)
            res = bulk_load(server, "wide", width - 1, wants=10.0)
            assert len(res.store) == width

            deadline = time.time() + 10.0
            while time.time() < deadline:
                solver = (
                    server._resident_wide
                    if expect_wide
                    else server._resident
                )
                if solver is not None and solver.ticks >= 2:
                    break
                await asyncio.sleep(0.05)
            assert ("wide" in server._partition.wide_ids) == expect_wide
            solver = (
                server._resident_wide if expect_wide else server._resident
            )
            assert solver is not None and solver.ticks >= 2
            # Demand fits capacity: everyone gets wants, conserved.
            assert res.store.sum_has <= 1000.0 + 1e-6

        await server.stop()

    asyncio.run(body())
