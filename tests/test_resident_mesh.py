"""Mesh-sharded resident ticks vs the single-device resident ticks.

The resident solvers with `mesh=` shard the device tables' row axis
over the 8-device virtual CPU mesh (tests/conftest.py forces it); the
contract is BYTE-IDENTICAL store contents versus the single-device
solver over multi-tick churn — assignments, releases, new clients,
learning-mode flips, rotation — including wide resources whose chunks
STRADDLE a shard boundary.  The narrow solver is row-local, so that is
automatic; for the wide solver it is the bit-stable psum reduction
(parallel.sharded.resident_chunk_reduces) doing the work: psum
assembles the global per-row totals from disjoint shard supports
(exact) and every shard runs the same sorted segment op, so the
straddling chunks' totals never re-associate.

World-building and churn come from the existing single-device resident
suites, so the mesh path is exercised against exactly the scenarios
they pin.
"""

import asyncio
import time

import numpy as np
import pytest

import tests.conftest  # noqa: F401

import jax

from doorman_tpu import native
from doorman_tpu.parallel import make_mesh
from doorman_tpu.parallel.mesh import make_mesh_from_spec
from doorman_tpu.solver.resident import ResidentDenseSolver
from doorman_tpu.solver.resident_wide import WideResidentSolver
from tests.test_resident_solver import (
    all_leases,
    churn,
    make_world,
)
from tests.test_resident_wide import make_world as make_wide_world

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native engine unavailable"
)

CHUNK_W = 8  # 21 clients/resource -> 3 chunks: every resource straddles


def assert_identical(a, b, msg=""):
    assert a.keys() == b.keys(), f"membership diverged {msg}"
    for key in a:
        assert a[key] == b[key], f"{msg} lease {key}: {a[key]} != {b[key]}"


def run_churn(solver_mesh, res_m, solver_one, res_one, ticks=8,
              check_each=True, quiesce=0, clock_box=None):
    """Drive both worlds through the shared churn scenario (plus a
    learning-mode flip at tick 4), then `quiesce` further quiet ticks;
    compare stores per tick (rotate=1) or only at the end."""
    rng_m, rng_o = (np.random.default_rng(99) for _ in range(2))
    for step in range(ticks):
        churn(res_m, step, rng_m)
        churn(res_one, step, rng_o)
        if step == 4:
            res_m[2].learning_mode_end = clock_box[0] + 100
            res_one[2].learning_mode_end = clock_box[0] + 100
        epoch = 1 if step >= 4 else 0
        solver_mesh.step(res_m, config_epoch=epoch)
        solver_one.step(res_one, config_epoch=epoch)
        if check_each:
            assert_identical(
                all_leases(res_m), all_leases(res_one), f"tick {step}"
            )
        clock_box[0] += 1.0
    for step in range(quiesce):
        solver_mesh.step(res_m, config_epoch=1)
        solver_one.step(res_one, config_epoch=1)
        clock_box[0] += 1.0
    assert_identical(all_leases(res_m), all_leases(res_one), "final")


def test_narrow_mesh_bit_identical_over_churn():
    t = [1000.0]
    clock = lambda: t[0]
    eng_m, res_m = make_world(clock)
    eng_o, res_o = make_world(clock)
    mesh = make_mesh()
    run_churn(
        ResidentDenseSolver(
            eng_m, dtype=np.float64, clock=clock, rotate_ticks=1,
            mesh=mesh,
        ),
        res_m,
        ResidentDenseSolver(
            eng_o, dtype=np.float64, clock=clock, rotate_ticks=1
        ),
        res_o,
        clock_box=t,
    )


def test_wide_mesh_bit_identical_with_straddling_chunks():
    t = [1000.0]
    clock = lambda: t[0]
    eng_m, res_m = make_wide_world(clock)
    eng_o, res_o = make_wide_world(clock)
    mesh = make_mesh()
    sm = WideResidentSolver(
        eng_m, dtype=np.float64, clock=clock, rotate_ticks=1,
        chunk_width=CHUNK_W, mesh=mesh,
    )
    so = WideResidentSolver(
        eng_o, dtype=np.float64, clock=clock, rotate_ticks=1,
        chunk_width=CHUNK_W,
    )
    run_churn(sm, res_m, so, res_o, clock_box=t)
    # The layout actually straddles: 4 resources x 3 chunks over 8
    # shards of 2 rows — resource 0's chunks span shards 0 and 1, etc.
    assert sm._Rp == 16 and sm._R == 12
    assert sm._Rp // sm._meshrows.n_dev == 2


def test_wide_mesh_two_axis_mesh_matches():
    """A ('dc', 'clients') 2x4 mesh flattens to the same row partition;
    the psum/pmax just run over two axes."""
    t = [1000.0]
    clock = lambda: t[0]
    eng_m, res_m = make_wide_world(clock)
    eng_o, res_o = make_wide_world(clock)
    mesh = make_mesh([2, 4], ("dc", "clients"))
    sm = WideResidentSolver(
        eng_m, dtype=np.float64, clock=clock, rotate_ticks=1,
        chunk_width=CHUNK_W, mesh=mesh,
    )
    so = WideResidentSolver(
        eng_o, dtype=np.float64, clock=clock, rotate_ticks=1,
        chunk_width=CHUNK_W,
    )
    run_churn(sm, res_m, so, res_o, ticks=5, clock_box=t)


def test_rotation_converges_to_single_device_fixpoint():
    """rotate_ticks>1: the mesh rotates PER SHARD (balanced delivery),
    so mid-churn store contents may transiently differ from the
    single-device solver's global rotation — a row lands a tick earlier
    on one or the other. The churn here is wants-only (bulk_refresh,
    like client refreshes whose demand moved): the device tables then
    evolve identically on both solvers, and once churn stops and both
    complete two full rotations, every store row holds the same device
    fixpoint, byte for byte (the invariant the idle fast path relies
    on). Full-assign churn that echoes the store's `has` back would
    genuinely couple the worlds to their delivery schedules — that
    feedback is pinned bit-identical at rotate_ticks=1 above, which is
    how the server runs when same-tick freshness matters."""
    t = [1000.0]
    clock = lambda: t[0]
    eng_m, res_m = make_wide_world(clock)
    eng_o, res_o = make_wide_world(clock)
    mesh = make_mesh()
    sm = WideResidentSolver(
        eng_m, dtype=np.float64, clock=clock, rotate_ticks=3,
        chunk_width=CHUNK_W, mesh=mesh,
    )
    so = WideResidentSolver(
        eng_o, dtype=np.float64, clock=clock, rotate_ticks=3,
        chunk_width=CHUNK_W,
    )

    def wants_churn(engine, resources, step, rng):
        res = resources[step % len(resources)]
        i = resources.index(res)
        engine.bulk_refresh(
            np.array([res.store._rid], np.int32),
            np.array([engine.client_handle(f"c{i}_0")], np.int64),
            np.array([t[0] + 60.0]),
            np.array([5.0]),
            np.array([float(rng.integers(1, 200))]),
        )

    rng_m, rng_o = (np.random.default_rng(7) for _ in range(2))
    for step in range(6):
        wants_churn(eng_m, res_m, step, rng_m)
        wants_churn(eng_o, res_o, step, rng_o)
        sm.step(res_m)
        so.step(res_o)
        t[0] += 1.0
    for _ in range(9):  # three full rotations, no churn
        sm.step(res_m)
        so.step(res_o)
        # The actual mesh invariant at any rotation: the device tables
        # of record are BYTE-identical every tick (the solve is over
        # the full table regardless of what delivers).
        np.testing.assert_array_equal(
            np.asarray(sm._has), np.asarray(so._has)
        )
        t[0] += 1.0
    # Store rows carry each schedule's last-delivery VINTAGE: the has
    # chain contracts to its fixpoint over the quiet rotations (here
    # exactly, after ~6 quiet ticks) but the idle fast path freezes
    # deliveries after two quiet rotations, so a row delivered a tick
    # apart on the two schedules may keep a 1-ulp-older iterate.
    # Equality bound = one contraction step of the chain (~eps * has).
    a, b = all_leases(res_m), all_leases(res_o)
    assert a.keys() == b.keys()
    for key in a:
        np.testing.assert_allclose(
            a[key], b[key], rtol=1e-13, atol=0,
            err_msg=f"fixpoint lease {key}",
        )


def test_mesh_rotation_is_balanced_across_shards():
    """Each quiet tick's delivery set spreads over the shards instead
    of marching one contiguous window through them: no shard delivers
    more than ceil(its rows / rotate) rotation rows."""
    t = [1000.0]
    clock = lambda: t[0]
    eng, res = make_wide_world(clock)
    mesh = make_mesh()
    solver = WideResidentSolver(
        eng, dtype=np.float64, clock=clock, rotate_ticks=2,
        chunk_width=CHUNK_W, mesh=mesh,
    )
    solver.step(res)  # rebuild tick delivers everything
    handle = solver.dispatch(res)
    assert handle.shard_counts is not None
    # 12 real rows over shards of 2 -> 6 populated shards; rotate=2
    # delivers 1 row per populated shard per tick.
    assert int(handle.shard_counts.max()) <= 1 + 1  # rotation + dirty
    assert (handle.shard_counts[:6] >= 1).all()
    solver.collect(handle)


def test_shard_traffic_gauges_published():
    """Mesh ticks publish per-shard byte gauges and a skew ratio in the
    default registry (scraped at /metrics, mirrored to /debug/traces
    when the tracer is on)."""
    from doorman_tpu.obs import metrics as metrics_mod

    t = [1000.0]
    clock = lambda: t[0]
    eng, res = make_wide_world(clock)
    solver = WideResidentSolver(
        eng, dtype=np.float64, clock=clock, rotate_ticks=1,
        chunk_width=CHUNK_W, mesh=make_mesh(),
    )
    solver.step(res)
    reg = metrics_mod.default_registry()
    per = reg.gauge(
        "doorman_tick_shard_bytes",
        "Per-shard host-link payload bytes of the last mesh-sharded "
        "tick (direction: upload/download).",
        labels=("component", "direction", "shard"),
    )
    skew = reg.gauge(
        "doorman_tick_shard_skew",
        "max/mean ratio of per-shard payload bytes for the last "
        "mesh-sharded tick (1.0 = perfectly balanced).",
        labels=("component", "direction"),
    )
    # The rebuild tick delivered every row: shard 0 downloaded bytes.
    assert per.value("resident_wide", "download", "0") > 0
    assert skew.value("resident_wide", "download") >= 1.0


def test_mesh_spec_parsing():
    devices = jax.devices()
    m = make_mesh_from_spec("auto")
    assert int(np.prod(list(m.shape.values()))) == len(devices)
    m = make_mesh_from_spec("2x4")
    assert dict(m.shape) == {"dc": 2, "clients": 4}
    m = make_mesh_from_spec("8")
    assert dict(m.shape) == {"clients": 8}
    with pytest.raises(ValueError):
        make_mesh_from_spec("2xbanana")
    with pytest.raises(ValueError):
        make_mesh_from_spec("3x5")  # does not cover 8 devices


def test_server_mesh_matches_single_device_server():
    """End-to-end server wiring: a batch+native CapacityServer with
    mesh= produces byte-identical store contents to an unmeshed one
    over the same ticks — narrow resources on the narrow resident
    solver, a wide (past the patched cap) resource on the chunked one."""
    import doorman_tpu.solver.batch as batch_mod
    import doorman_tpu.solver.resident as resident_mod
    import doorman_tpu.solver.resident_wide as wide_mod
    from doorman_tpu.proto import doorman_pb2 as pb
    from doorman_tpu.server.config import parse_yaml_config
    from doorman_tpu.server.election import TrivialElection
    from doorman_tpu.server.server import CapacityServer

    config = """
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

    async def body():
        servers = []
        for mesh in (make_mesh(), None):
            server = CapacityServer(
                f"srv_{'mesh' if mesh is not None else 'one'}",
                TrivialElection(), mode="batch", tick_interval=3600.0,
                minimum_refresh_interval=0.0, native_store=True,
                mesh=mesh,
            )
            await server.start(0, host="127.0.0.1")
            await server.load_config(parse_yaml_config(config))
            servers.append(server)
            # Same demand on both: gRPC-shaped decides + a bulk block
            # that pushes "wide" past the patched dense cap.
            from doorman_tpu.algorithms import Request

            for i in range(8):
                server._decide("narrow", Request(f"n{i}", 0.0, 7.0, 1))
            engine = server._store_factory.__self__
            res = server.resources
            wide = server.get_or_create_resource("wide")
            n = 40
            rids = np.full(n, wide.store._rid, np.int32)
            cids = np.array(
                [engine.client_handle(f"w{i}") for i in range(n)],
                np.int64,
            )
            engine.bulk_assign(
                rids, cids, np.full(n, time.time() + 60.0),
                np.full(n, 1.0), np.zeros(n),
                np.arange(1.0, n + 1.0), np.ones(n, np.int32),
            )
        mesh_srv, one_srv = servers
        assert mesh_srv.status()["mesh"] == {"clients": 8}
        assert one_srv.status()["mesh"] is None
        for _ in range(4):
            await mesh_srv.tick_once()
            await one_srv.tick_once()
        for rid in ("narrow", "wide"):
            a = dict(mesh_srv.resources[rid].store.items())
            b = dict(one_srv.resources[rid].store.items())
            assert a.keys() == b.keys()
            for key in a:
                assert (
                    a[key].has, a[key].wants
                ) == (b[key].has, b[key].wants), (rid, key)
        assert mesh_srv._resident is not None
        assert mesh_srv._resident_wide is not None
        assert "wide" in mesh_srv._partition.wide_ids
        for s in servers:
            await s.stop()

    def patch(mod, cap=16):
        orig = mod.DENSE_MAX_K
        mod.DENSE_MAX_K = cap
        return orig

    mods = (batch_mod, resident_mod, wide_mod)
    origs = [patch(m) for m in mods]
    try:
        asyncio.run(body())
    finally:
        for m, o in zip(mods, origs):
            m.DENSE_MAX_K = o
