"""The Tick driver's lane partition (CapacityServer._lane_partition):
built once per config epoch and resource set, reused by every steady
tick, and rebuilt exactly once by each move of its key — a config
reload, a new resource, a ResidentOverflow — with the moved rows
solved on their new path."""

import asyncio

import numpy as np
import pytest

import tests.conftest  # noqa: F401

from doorman_tpu import native
from doorman_tpu.obs import trace as trace_mod
from doorman_tpu.proto import doorman_pb2 as pb
from doorman_tpu.server.config import parse_yaml_config
from doorman_tpu.server.election import TrivialElection
from doorman_tpu.server.server import CapacityServer
from doorman_tpu.solver.batch import BatchSolver

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native engine unavailable"
)

LANE = """
- identifier_glob: "*"
  capacity: 100
  algorithm: {kind: FAIR_SHARE, lease_length: 60, refresh_interval: 1,
              learning_mode_duration: 0}
"""
BANDS = """
- identifier_glob: "{}"
  capacity: 100
  algorithm: {{kind: PRIORITY_BANDS, lease_length: 60, refresh_interval: 1,
              learning_mode_duration: 0}}
"""
CAP = 16  # the dense bucket cap, patched down to test-sized widths


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def config(*bands):
    """Every resource FAIR_SHARE, but the ids in `bands` PRIORITY_BANDS."""
    return parse_yaml_config(
        "resources:" + "".join(BANDS.format(b) for b in bands) + LANE
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


@pytest.fixture
def small_cap(monkeypatch):
    """The dense bucket cap is read at call time in the three modules
    that partition or overflow on it."""
    import doorman_tpu.solver.batch as batch_mod
    import doorman_tpu.solver.resident as resident_mod
    import doorman_tpu.solver.resident_wide as wide_mod

    for mod in (batch_mod, resident_mod, wide_mod):
        monkeypatch.setattr(mod, "DENSE_MAX_K", CAP)


async def make_server(clock, repo, **kw):
    """A native batch server at depth 1, master, out of learning mode."""
    server = CapacityServer(
        "part", TrivialElection(), minimum_refresh_interval=0.0,
        clock=clock, mode="batch", native_store=True,
        tick_pipeline_depth=1, **kw,
    )
    await server.load_config(repo)
    await server._on_is_master(True)
    server.became_master_at = clock() - 10_000
    return server


async def ask(server, resource_id, n, wants=40.0, first=0):
    """Clients first..first+n-1 of one resource each want `wants`
    (with distinct priorities, for the bands)."""
    for c in range(first, first + n):
        req = pb.GetCapacityRequest(client_id=f"c{c}")
        r = req.resource.add()
        r.resource_id = resource_id
        r.wants = wants
        r.priority = c % 3
        await server.GetCapacity(req, None)


async def ticks(server, clock, n):
    for _ in range(n):
        await server.tick_once()
        clock.t += 1.0


def grants(res):
    return {c: lease.has for c, lease in res.store.items()}


def assert_batch_fixpoint(server, res):
    """The stored grants are what a fresh BatchSolver gives for the same
    store: a solve of the current state writes them back unchanged."""
    before = grants(res)
    solver = BatchSolver(clock=server._clock, dtype=np.float64)
    snap = solver.prepare([res])
    out = solver.apply([res], snap, solver.solve(snap))
    assert out[res.id] == pytest.approx(before, abs=1e-9)
    assert grants(res) == pytest.approx(before, abs=1e-9)


def test_steady_ticks_reuse_the_partition(tracer):
    async def body():
        clock = FakeClock()
        server = await make_server(clock, config())
        for r in ("r0", "r1"):
            await ask(server, r, 4)
        tracer.enable()
        await ticks(server, clock, 6)
        assert server.partition_rebuilds == 1
        part = server._partition
        assert [r.id for r in part.narrow] == ["r0", "r1"]
        assert part.wide == [] and part.prio == []
        await server.stop()

    asyncio.run(body())
    spans = [e for e in tracer.snapshot() if e.name == "tick.partition"]
    assert [e.args["rebuilt"] for e in spans] == [1, 0, 0, 0, 0, 0]


@pytest.mark.parametrize("move", ["reload_to_bands", "new_resource",
                                  "overflow"])
def test_a_key_move_rebuilds_once(small_cap, move):
    async def body():
        clock = FakeClock()
        server = await make_server(clock, config())
        for r in ("r0", "r1"):
            await ask(server, r, 4)
        await ticks(server, clock, 3)
        assert server.partition_rebuilds == 1
        batch_ticks = server._get_solver().ticks

        if move == "reload_to_bands":
            await server.load_config(config("r1"))
            await ticks(server, clock, 1)
            part = server._partition
            assert [r.id for r in part.prio] == ["r1"]
            assert [r.id for r in part.narrow] == ["r0"]
            # The moved rows went through the BatchSolver's priority
            # part on the tick that re-partitioned.
            assert server._get_solver().ticks == batch_ticks + 1
            moved = server.resources["r1"]
        elif move == "new_resource":
            await ask(server, "r2", 3)
            await ticks(server, clock, 2)
            part = server._partition
            assert [r.id for r in part.narrow] == ["r0", "r1", "r2"]
            moved = server.resources["r2"]
        else:
            # r0 outgrows the dense bucket between ticks: the next
            # dispatch overflows, falls back to the BatchSolver, and
            # the tick after re-partitions r0 into the wide lane.
            await ask(server, "r0", CAP, first=4)
            await ticks(server, clock, 1)
            assert server.partition_rebuilds == 1
            assert server._get_solver().ticks == batch_ticks + 1
            await ticks(server, clock, 2)
            part = server._partition
            assert part.wide_ids == {"r0"}
            assert [r.id for r in part.narrow] == ["r1"]
            assert server._resident_wide.ticks >= 1
            moved = server.resources["r0"]
        assert server.partition_rebuilds == 2
        await ticks(server, clock, 3)
        assert server.partition_rebuilds == 2
        assert 0.0 < sum(grants(moved).values()) <= 100.0 + 1e-9
        assert_batch_fixpoint(server, moved)
        await server.stop()

    asyncio.run(body())


def test_force_ids_hold_the_wide_and_priority_rows(small_cap):
    async def body():
        clock = FakeClock()
        server = await make_server(clock, config("bands"), stream_push=True)
        await ask(server, "narrow", 4)
        await ask(server, "wide", CAP + 4)
        await ask(server, "bands", 4)
        await ticks(server, clock, 2)
        part = server._partition
        assert part.force_ids == {"wide", "bands"}
        assert part.wide_ids == {"wide"}
        # Past the epoch move, the fanout's changed set carries their
        # rows every tick whether or not the narrow engine moved them.
        rids, check_all = server._stream_changed_rids()
        assert not check_all
        for resource_id in ("wide", "bands"):
            assert server.resources[resource_id].store._rid in rids
        await server.stop()

    asyncio.run(body())
