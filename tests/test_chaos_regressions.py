"""Chaos regression plans pinning recently patched behaviors.

Each test expresses its fault as a FaultPlan/FaultEvent (the chaos
subsystem's replay artifact) instead of a bespoke fixture:

  * transient election-renewal retry: the REAL EtcdKV election over the
    real v3 HTTP dialect (tests/fake_etcd) survives exactly one dropped
    keepalive round-trip — the patch that stopped small-TTL elections
    flapping under load;
  * stale-port detection: tools/drives ensure_ports_free fails LOUDLY
    when a leaked server still holds the port;
  * ResidentOverflow clears BOTH resident handles, so a fallback tick
    cannot be overwritten by one-tick-stale wide grants.
"""

import asyncio
import importlib.util
import pathlib

import pytest

import tests.conftest  # noqa: F401

from doorman_tpu.chaos import (
    ChaosEtcdGateway,
    FaultEvent,
    FaultPlan,
    FaultState,
    PortInjector,
    SolverInjector,
)
from doorman_tpu.server.election import EtcdKV, KVElection
from tests.fake_etcd import FakeEtcd

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_drive_common():
    spec = importlib.util.spec_from_file_location(
        "_drive_common", REPO / "tools" / "drives" / "_common.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_election_renewal_retry_survives_one_etcd_hiccup():
    """One dropped /v3/lease/keepalive round-trip must read as a
    transient failure (retried inside the renewal window), NOT as
    mastership loss. The fault is the plan's single event, scoped to
    the keepalive path so the election's watcher reads cannot absorb
    the budget."""
    plan = FaultPlan(
        name="renewal_hiccup",
        seed=0,
        setup={"election_ttl": 1.5},
        events=[
            FaultEvent(
                at_tick=0, kind="etcd_drop", target="etcd",
                duration_ticks=1,
                params={"calls": 1,
                        "path_prefix": "/v3/lease/keepalive"},
            )
        ],
        warmup_ticks=0,
        total_ticks=1,
    )
    fake = FakeEtcd()
    fake.start()
    state = FaultState(plan.seed)

    async def body():
        ttl = plan.setup["election_ttl"]
        gw = ChaosEtcdGateway([fake.address], state)
        election = KVElection(
            EtcdKV([fake.address], gateway=gw), "/chaos-lock", ttl=ttl
        )
        events = []
        won = asyncio.Event()

        async def on_is_master(is_master):
            events.append(is_master)
            if is_master:
                won.set()

        async def on_current(_):
            pass

        await election.run("candidate", on_is_master, on_current)
        await asyncio.wait_for(won.wait(), 10)
        # Arm the plan's fault: the next keepalive round-trip drops.
        for ev in plan.events_at(0):
            state.start(ev)
        # Ride through ~3 renewal cycles of real time.
        await asyncio.sleep(1.5 * ttl)
        assert events == [True], "one etcd hiccup read as mastership loss"
        assert fake.value("/chaos-lock") == "candidate"
        await election.stop()

    try:
        asyncio.run(body())
    finally:
        fake.stop()


def test_stale_port_detected_by_ensure_ports_free():
    """A 'leaked server' (the PortInjector holding the port, as a
    killed drive's zombie would) must make ensure_ports_free exit
    loudly; releasing the port clears the check."""
    plan = FaultPlan(
        name="stale_port",
        seed=0,
        setup={},
        events=[FaultEvent(at_tick=0, kind="port_bind",
                           duration_ticks=0)],
        warmup_ticks=0,
        total_ticks=1,
    )
    common = _load_drive_common()
    ports = PortInjector()
    try:
        bound = [ports.bind() for ev in plan.events_at(0)]
        assert bound
        with pytest.raises(SystemExit):
            common.ensure_ports_free(bound[0])
    finally:
        ports.release_all()
    common.ensure_ports_free(bound[0])  # freed: no complaint


def test_resident_overflow_clears_both_resident_handles():
    """An injected ResidentOverflow takes the BatchSolver fallback and
    must drop BOTH in-flight handles — with a wide resource in the mix,
    a surviving pre-overflow wide handle would be collected next tick
    and overwrite the fresher batch-applied grants with one-tick-stale
    ones (the chunk-version guard only detects membership changes, not
    value staleness)."""
    from doorman_tpu import native

    if not native.native_available():
        pytest.skip("native engine unavailable")

    from doorman_tpu.algorithms import Request
    from doorman_tpu.server.config import parse_yaml_config
    from doorman_tpu.server.election import TrivialElection
    from doorman_tpu.server.server import CapacityServer
    from doorman_tpu.solver.batch import DENSE_MAX_K

    plan_event = FaultEvent(
        at_tick=0, kind="resident_overflow", target="s0",
        duration_ticks=1, params={"calls": 1},
    )

    async def body():
        state = FaultState(0)
        server = CapacityServer(
            "s0", TrivialElection(), mode="batch",
            native_store=True, minimum_refresh_interval=0.0,
        )
        SolverInjector(state, "s0").install(server)
        await server.load_config(parse_yaml_config(
            "resources:\n"
            "- identifier_glob: \"*\"\n"
            "  capacity: 100\n"
            "  algorithm: {kind: PROPORTIONAL_SHARE, lease_length: 60,\n"
            "              refresh_interval: 1, learning_mode_duration: 0}\n"
        ))
        await asyncio.sleep(0)
        for c, w in [("a", 60.0), ("b", 50.0)]:
            server._decide("narrow0", Request(c, 0.0, w, 1, priority=1))
        # A resource wider than the dense bucket cap: takes the chunked
        # wide solver, so a wide handle is genuinely in flight.
        wide = server.get_or_create_resource("wide0")
        for i in range(DENSE_MAX_K + 8):
            wide.store.assign(f"w{i}", 60.0, 1.0, 0.0, 1.0, 1)
        await server.tick_once()
        await server.tick_once()
        assert len(server._resident_pipe) > 0
        assert len(server._resident_wide_pipe) > 0
        state.start(plan_event)
        await server.tick_once()  # overflow -> BatchSolver fallback
        assert len(server._resident_pipe) == 0
        assert len(server._resident_wide_pipe) == 0, (
            "fallback tick left a stale wide handle collectable"
        )
        await server.stop()

    asyncio.run(body())
