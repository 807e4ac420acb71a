"""PRIORITY_BANDS through the stack: config parsing/validation, the
scalar immediate-mode algorithm, and the batched tick with capacity
groups (Python and native stores)."""

import asyncio

import numpy as np
import jax
import pytest

from doorman_tpu.proto import doorman_pb2 as pb
from doorman_tpu.server import config as config_mod
from doorman_tpu.server.config import ConfigError
from doorman_tpu.server.election import TrivialElection
from doorman_tpu.server.server import CapacityServer

jax.config.update("jax_enable_x64", True)

BASE_YAML = """
groups:
  - name: upstream
    capacity: 120
resources:
  - identifier_glob: "prio-*"
    capacity: 100
    capacity_group: upstream
    algorithm:
      kind: PRIORITY_BANDS
      lease_length: 60
      refresh_interval: 5
  - identifier_glob: "*"
    capacity: 100
    algorithm:
      kind: PROPORTIONAL_SHARE
      lease_length: 60
      refresh_interval: 5
"""


# Group-free variant: immediate-mode servers REJECT grouped configs
# (group caps are enforced only by the batch tick), so the scalar
# band tests run without them.
NOGROUP_YAML = """
resources:
  - identifier_glob: "prio-*"
    capacity: 100
    algorithm:
      kind: PRIORITY_BANDS
      lease_length: 60
      refresh_interval: 5
  - identifier_glob: "*"
    capacity: 100
    algorithm:
      kind: PROPORTIONAL_SHARE
      lease_length: 60
      refresh_interval: 5
"""


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def test_config_groups_parse_and_validate():
    repo = config_mod.parse_yaml_config(BASE_YAML)
    assert repo.groups[0].name == "upstream"
    assert repo.resources[0].capacity_group == "upstream"
    assert repo.resources[0].algorithm.kind == pb.Algorithm.PRIORITY_BANDS

    with pytest.raises(ConfigError, match="undefined capacity group"):
        config_mod.parse_yaml_config(
            BASE_YAML.replace("name: upstream", "name: other")
        )
    with pytest.raises(ConfigError, match="requires the PRIORITY_BANDS"):
        config_mod.parse_yaml_config(
            BASE_YAML.replace("kind: PRIORITY_BANDS",
                              "kind: FAIR_SHARE")
        )
    with pytest.raises(ConfigError, match="duplicate capacity group"):
        config_mod.parse_yaml_config(BASE_YAML.replace(
            "groups:\n  - name: upstream\n    capacity: 120",
            "groups:\n  - name: upstream\n    capacity: 120\n"
            "  - name: upstream\n    capacity: 50",
        ))


def _request(client, resource, wants, priority):
    req = pb.GetCapacityRequest()
    req.client_id = client
    r = req.resource.add()
    r.resource_id = resource
    r.priority = priority
    r.wants = wants
    return req


def _make_server(clock, mode="immediate", native=False):
    server = CapacityServer(
        "s1", TrivialElection(), minimum_refresh_interval=0.0,
        clock=clock, mode=mode, native_store=native,
    )
    return server


async def _setup(server, clock):
    yaml = BASE_YAML if server.mode == "batch" else NOGROUP_YAML
    await server.load_config(config_mod.parse_yaml_config(yaml))
    await server._on_is_master(True)
    server.became_master_at = clock() - 10_000  # skip learning mode


def test_grouped_config_rejected_outside_batch_mode():
    """Group caps are enforced only by the batch tick; accepting a
    grouped config on an immediate server would validate-then-ignore it
    (silent overcommit), so load_config must reject instead."""

    async def scenario():
        clock = FakeClock()
        server = _make_server(clock, mode="immediate")
        with pytest.raises(ConfigError, match="capacity group"):
            await server.load_config(
                config_mod.parse_yaml_config(BASE_YAML)
            )
        # The server keeps running and accepts a group-free config.
        await server.load_config(
            config_mod.parse_yaml_config(NOGROUP_YAML)
        )
        # A batch server accepts the same grouped config.
        batch = _make_server(clock, mode="batch")
        await batch.load_config(config_mod.parse_yaml_config(BASE_YAML))

    asyncio.run(scenario())


def test_immediate_mode_priority_bands():
    async def scenario():
        clock = FakeClock()
        server = _make_server(clock)
        await _setup(server, clock)
        # Low-priority client asks first and gets everything...
        resp = await server.GetCapacity(
            _request("low", "prio-a", 80.0, priority=1), None
        )
        assert resp.response[0].gets.capacity == 80.0
        # ...then a high-priority client demands the full capacity. Its
        # banded share is 100, but only unpromised capacity is granted
        # immediately (the incremental discipline every scalar form
        # follows — no oversubscription while low still holds 80).
        resp = await server.GetCapacity(
            _request("high", "prio-a", 100.0, priority=5), None
        )
        assert resp.response[0].gets.capacity == 20.0
        # The low-priority client's next refresh is fully displaced...
        resp = await server.GetCapacity(
            _request("low", "prio-a", 80.0, priority=1), None
        )
        assert resp.response[0].gets.capacity == 0.0
        # ...after which the high-priority client converges to 100.
        resp = await server.GetCapacity(
            _request("high", "prio-a", 100.0, priority=5), None
        )
        assert resp.response[0].gets.capacity == 100.0

    asyncio.run(scenario())


@pytest.mark.parametrize("native", [False, True])
def test_batch_tick_priority_with_group_cap(native):
    async def scenario():
        clock = FakeClock()
        server = _make_server(clock, mode="batch", native=native)
        await _setup(server, clock)
        # Two priority resources in the shared 120-capacity group, plus a
        # plain proportional resource solved by the lane path.
        for client, res, wants, prio in [
            ("a", "prio-a", 100.0, 5),
            ("b", "prio-a", 50.0, 1),
            ("c", "prio-b", 100.0, 5),
            ("d", "plain", 40.0, 0),
        ]:
            await server.GetCapacity(_request(client, res, wants, prio), None)
        await server.tick_once()

        stores = {
            rid: dict(server.resources[rid].store.items())
            for rid in ("prio-a", "prio-b", "plain")
        }
        # Group usage capped at 120 < 200 total capacity.
        total_prio = sum(
            l.has for s in (stores["prio-a"], stores["prio-b"])
            for l in s.values()
        )
        assert total_prio == pytest.approx(120.0, rel=1e-6)
        # Within prio-a, the high-priority client is served first.
        assert stores["prio-a"]["a"].has > 0
        assert stores["prio-a"]["b"].has == pytest.approx(0.0, abs=1e-9)
        # Symmetric resources with symmetric demand split the group cap.
        assert stores["prio-a"]["a"].has == pytest.approx(
            stores["prio-b"]["c"].has, rel=1e-9
        )
        # The plain resource solves on the lane path, unaffected.
        assert stores["plain"]["d"].has == pytest.approx(40.0)
        # Priorities survive the write-back.
        assert stores["prio-a"]["a"].priority == 5
        assert stores["prio-a"]["b"].priority == 1

    asyncio.run(scenario())


def test_priority_survives_native_roundtrip():
    from doorman_tpu import native

    if not native.native_available():
        pytest.skip("native store build unavailable")
    clock = FakeClock()
    engine = native.StoreEngine(clock=clock)
    store = engine.store("res")
    store.assign("c", 60.0, 5.0, 1.0, 2.0, 1, priority=7)
    assert store.get("c").priority == 7
    assert dict(store.items())["c"].priority == 7
    *_, prio = engine.pack([store])
    assert list(prio) == [7]


def test_mixed_config_keeps_resident_path_for_lane_resources():
    """A config mixing PRIORITY_BANDS and lane resources must not lose
    the resident fast path: the lane subset ticks device-resident while
    the priority part goes through the BatchSolver — grants match the
    pure-batch world exactly on both."""
    async def scenario():
        clock = FakeClock()
        server = _make_server(clock, mode="batch", native=True)
        await _setup(server, clock)
        for client, res, wants, prio in [
            ("a", "prio-a", 100.0, 5),
            ("b", "prio-a", 50.0, 1),
            ("d", "plain", 140.0, 0),
            ("e", "plain", 80.0, 0),
        ]:
            await server.GetCapacity(_request(client, res, wants, prio), None)
        for _ in range(3):
            await server.tick_once()
            clock.t += 1.0

        # The resident path engaged for the lane subset...
        assert server._resident is not None and server._resident.ticks >= 1
        assert server._partition.eligible
        # ...serving the lane resource through it (proportional: 100
        # capacity, wants 140+80 => scaled by 100/220, free-clamped)...
        plain = dict(server.resources["plain"].store.items())
        assert plain["d"].has + plain["e"].has == pytest.approx(100.0)
        assert plain["d"].has > plain["e"].has > 0
        # ...while the priority resource ticked through the batch part
        # (band 5 first: a gets min(100, group cap 120) within cap 100).
        prio_a = dict(server.resources["prio-a"].store.items())
        assert prio_a["a"].has == pytest.approx(100.0)
        assert prio_a["b"].has == pytest.approx(0.0, abs=1e-9)

    asyncio.run(scenario())
