"""The C++ store engine vs the Python LeaseStore: same interface, same
numbers, on identical operation sequences (differential testing); plus the
bulk pack path and the server wired with --native-store."""

import numpy as np
import pytest

from doorman_tpu import native
from doorman_tpu.core.store import LeaseStore

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native store build unavailable"
)


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture
def pair():
    clock = FakeClock()
    engine = native.StoreEngine(clock=clock)
    return LeaseStore("res", clock=clock), engine.store("res"), clock


def test_assign_release_sums_parity(pair):
    py, cc, clock = pair
    rng = np.random.default_rng(0)
    clients = [f"client-{i}" for i in range(40)]
    for step in range(500):
        c = clients[rng.integers(len(clients))]
        op = rng.random()
        if op < 0.6:
            wants = float(rng.integers(0, 100))
            has = float(rng.integers(0, 50))
            sub = int(rng.integers(1, 4))
            a = py.assign(c, 60.0, 5.0, has, wants, sub)
            b = cc.assign(c, 60.0, 5.0, has, wants, sub)
            assert a == b
        elif op < 0.8:
            py.release(c)
            cc.release(c)
        else:
            clock.t += float(rng.integers(0, 30))
            assert py.clean() == cc.clean()
        assert len(py) == len(cc)
        assert py.count == cc.count
        assert py.sum_has == pytest.approx(cc.sum_has)
        assert py.sum_wants == pytest.approx(cc.sum_wants)
        assert py.get(c) == cc.get(c)
        assert py.has_client(c) == cc.has_client(c)


def test_items_and_status_content_parity(pair):
    py, cc, clock = pair
    for i in range(10):
        py.assign(f"c{i}", 60.0, 5.0, float(i), float(2 * i), 1)
        cc.assign(f"c{i}", 60.0, 5.0, float(i), float(2 * i), 1)
    # Same content; order may differ after swap-removes, so compare as
    # dicts (both sides are deterministic, which test_pack_* checks).
    assert dict(py.items()) == dict(cc.items())
    a, b = py.lease_status(), cc.lease_status()
    assert (a.id, a.sum_has, a.sum_wants) == (b.id, b.sum_has, b.sum_wants)
    assert {s.client_id: s.lease for s in a.leases} == {
        s.client_id: s.lease for s in b.leases
    }


def test_subclients_and_zero_lease(pair):
    py, cc, _ = pair
    assert cc.get("ghost").is_zero
    assert cc.subclients("ghost") == 0
    cc.assign("c", 60.0, 5.0, 1.0, 2.0, 3)
    assert cc.subclients("c") == 3


def test_engine_pack_resource_major():
    clock = FakeClock()
    engine = native.StoreEngine(clock=clock)
    stores = [engine.store(f"res{i}") for i in range(3)]
    expect = []
    for r, s in enumerate(stores):
        for j in range(r + 1):  # 1, 2, 3 leases
            s.assign(f"c{r}-{j}", 60.0, 5.0, float(j), float(10 * r + j),
                     1 + j)
            expect.append((r, f"c{r}-{j}", float(10 * r + j), float(j),
                           float(1 + j)))
    assert engine.total_leases == 6
    ridx, cid, wants, has, sub, _prio = engine.pack(stores)
    got = [
        (int(ridx[i]), engine.client_name(int(cid[i])), wants[i], has[i],
         sub[i])
        for i in range(len(ridx))
    ]
    assert got == expect
    # Pack order follows the caller's order argument, not creation order:
    # reversed, res2's three leases come first as segment 0.
    ridx2, cid2, *_ = engine.pack(stores[::-1])
    assert [int(r) for r in ridx2] == [0, 0, 0, 1, 1, 2]
    assert engine.client_name(int(cid2[0])) == "c2-0"


def test_pack_after_release_swaps_deterministically():
    clock = FakeClock()
    engine = native.StoreEngine(clock=clock)
    s = engine.store("res")
    for i in range(4):
        s.assign(f"c{i}", 60.0, 5.0, 0.0, float(i), 1)
    s.release("c0")  # swap-remove: c3 moves into slot 0
    names = [c for c, _ in s.items()]
    assert names == ["c3", "c1", "c2"]


def test_clean_exact_boundary(pair):
    py, cc, clock = pair
    py.assign("c", 10.0, 5.0, 1.0, 1.0, 1)  # expiry 110
    cc.assign("c", 10.0, 5.0, 1.0, 1.0, 1)
    clock.t = 110.0  # now == expiry: NOT expired (strict >)
    assert py.clean() == cc.clean() == 0
    clock.t = 110.0001
    assert py.clean() == cc.clean() == 1


def _make_resources(store_factory, clock, n_resources=6, n_clients=15):
    from doorman_tpu.core.resource import Resource
    from doorman_tpu.proto import doorman_pb2 as pb

    rng = np.random.default_rng(11)
    kinds = [
        pb.Algorithm.PROPORTIONAL_SHARE,
        pb.Algorithm.FAIR_SHARE,
        pb.Algorithm.STATIC,
        pb.Algorithm.NO_ALGORITHM,
    ]
    resources = []
    for r in range(n_resources):
        t = pb.ResourceTemplate()
        t.identifier_glob = f"res{r}"
        t.capacity = float(rng.integers(50, 500))
        t.algorithm.kind = kinds[r % len(kinds)]
        t.algorithm.lease_length = 60
        t.algorithm.refresh_interval = 5
        res = Resource(
            f"res{r}", t, clock=clock, store_factory=store_factory
        )
        for c in range(int(rng.integers(1, n_clients))):
            res.store.assign(
                f"client-{c}", 60.0, 5.0,
                float(rng.integers(0, 50)), float(rng.integers(0, 100)), 1,
            )
        resources.append(res)
    return resources


def test_batch_tick_native_matches_python():
    """A full BatchSolver tick over native stores produces exactly the
    grants and store state of the Python-store tick (the native pack and
    dm_apply fast paths against the list-based reference path)."""
    import jax

    jax.config.update("jax_enable_x64", True)
    from doorman_tpu.solver.batch import BatchSolver

    clock = FakeClock(500.0)
    py_res = _make_resources(None, clock)
    engine = native.StoreEngine(clock=clock)
    cc_res = _make_resources(engine.store, clock)

    solver_py = BatchSolver(clock=clock)
    solver_cc = BatchSolver(clock=clock)
    grants_py = solver_py.tick(py_res)
    grants_cc = solver_cc.tick(cc_res)
    assert grants_py == grants_cc
    for a, b in zip(py_res, cc_res):
        assert a.store.sum_has == pytest.approx(b.store.sum_has)
        assert a.store.sum_wants == pytest.approx(b.store.sum_wants)
        assert dict(a.store.items()) == dict(b.store.items())


def test_batch_apply_native_skips_released_and_vanished():
    import jax

    jax.config.update("jax_enable_x64", True)
    from doorman_tpu.solver.batch import BatchSolver

    clock = FakeClock(500.0)
    engine = native.StoreEngine(clock=clock)
    resources = _make_resources(engine.store, clock, n_resources=3)
    solver = BatchSolver(clock=clock)
    snap = solver.prepare(resources)
    gets = solver.solve(snap)
    # Mid-solve: one client releases, one resource vanishes.
    victim = next(iter(dict(resources[0].store.items())))
    resources[0].store.release(victim)
    dropped = resources.pop(1)
    grants = solver.apply(resources, snap, gets)
    assert victim not in grants.get("res0", {})
    assert dropped.id not in grants
    assert not dropped.store.has_client("client-0") or all(
        l.expiry <= 560.0 for _, l in dropped.store.items()
    )  # vanished resource got no fresh expiry stamps


def test_server_with_native_store():
    """The end-to-end server path on the native engine: grant, then a
    mastership reset wipes engine state."""
    import asyncio

    from doorman_tpu.proto import doorman_pb2 as pb
    from doorman_tpu.server import config as config_mod
    from doorman_tpu.server.election import TrivialElection
    from doorman_tpu.server.server import CapacityServer

    async def scenario():
        clock = FakeClock(1000.0)
        server = CapacityServer(
            "s1", TrivialElection(), minimum_refresh_interval=0.0,
            clock=clock, native_store=True,
        )
        assert server._store_factory is not None
        yaml_text = """
resources:
  - identifier_glob: "*"
    capacity: 100
    algorithm:
      kind: PROPORTIONAL_SHARE
      lease_length: 60
      refresh_interval: 5
"""
        await server.load_config(config_mod.parse_yaml_config(yaml_text))
        await server._on_is_master(True)
        server.became_master_at = clock() - 1000  # past learning mode

        req = pb.GetCapacityRequest()
        r = req.resource.add()
        r.resource_id = "res0"
        r.priority = 1
        r.wants = 50.0
        r.has.expiry_time = 0
        req.client_id = "client-a"
        resp = await server.GetCapacity(req, None)
        assert resp.response[0].gets.capacity == 50.0
        res = server.resources["res0"]
        assert type(res.store).__name__ == "NativeLeaseStore"
        assert res.store.sum_wants == 50.0

        # Mastership loss wipes the native engine state.
        await server._on_is_master(False)
        await server._on_is_master(True)
        assert server.resources == {}

    asyncio.run(scenario())


def test_band_aggregates_parity_and_bulk_refresh():
    """band_aggregates: same triples from the Python and native stores;
    bulk_refresh: wants update + stamp with has/priority preserved."""
    import numpy as np

    from doorman_tpu.core.store import LeaseStore

    engine = native.StoreEngine()
    ns = engine.store("r")
    ps = LeaseStore("r")
    for store in (ns, ps):
        store.assign("a", 60, 5, 3.0, 10.0, 1, priority=2)
        store.assign("b", 60, 5, 1.0, 5.0, 2, priority=1)
        store.assign("c", 60, 5, 0.0, 7.0, 1, priority=2)
    assert ns.band_aggregates() == ps.band_aggregates() == [
        (1, 5.0, 2), (2, 17.0, 2),
    ]

    rids = np.full(2, ns._rid, np.int32)
    cids = np.array(
        [engine.client_handle("a"), engine.client_handle("zz")], np.int64
    )
    n = engine.bulk_refresh(
        rids, cids, np.full(2, 1e12), np.full(2, 9.0), np.full(2, 42.0)
    )
    assert n == 1  # unknown client skipped
    lease = ns.get("a")
    assert lease.wants == 42.0 and lease.has == 3.0
    assert lease.priority == 2 and lease.refresh_interval == 9.0
    assert ns.sum_wants == 42.0 + 5.0 + 7.0


def test_drain_dirty2_classifies_wants_only_vs_full():
    """drain_dirty2 flags rows that changed beyond wants: membership,
    has, or subclients set dirty_full; pure wants churn (bulk_refresh,
    or assign with only wants moved) does not."""
    import numpy as np

    engine = native.StoreEngine()
    sa = engine.store("a")
    sb = engine.store("b")
    sc = engine.store("c")
    sa.assign("x", 60, 5, 0.0, 10.0, 1)
    sb.assign("y", 60, 5, 0.0, 10.0, 1)
    sc.assign("z", 60, 5, 0.0, 10.0, 1)
    rids, full = engine.drain_dirty2()
    assert set(rids) == {sa._rid, sb._rid, sc._rid}
    assert all(full)  # inserts are membership changes

    # wants-only churn: assign same has/sub, new wants -> not full.
    sa.assign("x", 60, 5, 0.0, 20.0, 1)
    # has change -> full (learning-mode echo must reach the device).
    sb.assign("y", 60, 5, 4.0, 10.0, 1)
    # bulk wants refresh -> not full.
    engine.bulk_refresh(
        np.asarray([sc._rid], np.int32),
        np.asarray([engine.client_handle("z")], np.int64),
        np.full(1, 1e12), np.full(1, 5.0), np.full(1, 30.0),
    )
    rids, full = engine.drain_dirty2()
    flags = dict(zip(rids.tolist(), full.tolist()))
    assert flags[sa._rid] == 0
    assert flags[sb._rid] == 1
    assert flags[sc._rid] == 0

    # release -> membership change -> full; subclient change -> full.
    sa.release("x")
    sb.assign("y", 60, 5, 4.0, 10.0, 3)
    rids, full = engine.drain_dirty2()
    flags = dict(zip(rids.tolist(), full.tolist()))
    assert flags[sa._rid] == 1 and flags[sb._rid] == 1

    # The flag is consumed by the drain: re-dirtying with wants only
    # afterwards reports not-full again.
    sb.assign("y", 60, 5, 4.0, 11.0, 3)
    rids, full = engine.drain_dirty2()
    assert dict(zip(rids.tolist(), full.tolist()))[sb._rid] == 0


def test_min_expiry_bound_sweeps_correctly():
    """The engine's per-resource min-expiry bound makes the per-tick
    sweep O(resources) in steady state; it must stay a valid LOWER
    bound through re-stamps that loosen it (later expiry on the same
    client) and recompute exactly whenever a scan happens."""
    t = [0.0]
    engine = native.StoreEngine(clock=lambda: t[0])
    store = engine.store("r")
    store.assign("a", 10.0, 5, 0.0, 1.0, 1)   # expires at 10
    store.assign("b", 30.0, 5, 0.0, 1.0, 1)   # expires at 30

    t[0] = 5.0
    assert engine.clean_all() == 0            # bound (10) skips the scan
    assert len(store) == 2

    # Re-stamp "a" far into the future: the bound stays loosely at 10.
    store.assign("a", 200.0, 5, 0.0, 1.0, 1)  # expires at 205

    t[0] = 50.0
    assert engine.clean_all() == 1            # scans: only "b" lapsed
    assert store.has_client("a") and not store.has_client("b")

    t[0] = 150.0
    assert engine.clean_all() == 0            # recomputed bound skips
    t[0] = 250.0
    assert engine.clean_all() == 1            # "a" finally lapses
    assert len(store) == 0


def test_regrant_updates_has_without_dirtying_or_restamping():
    """regrant is the single-lease delivery write-back: has and the
    running sum move; expiry/refresh/wants stay put; the row is NOT
    marked dirty (delivery is the solver's own output, and a dirty mark
    would force a device re-upload and defeat the idle fast path)."""
    engine = native.StoreEngine(clock=lambda: 100.0)
    store = engine.store("r")
    store.assign("a", 60.0, 5.0, 2.0, 10.0, 1)
    engine.drain_dirty2()  # consume the insert's dirty mark

    store.regrant("a", 7.5)
    lease = store.get("a")
    assert lease.has == 7.5 and store.sum_has == 7.5
    assert lease.expiry == 160.0 and lease.wants == 10.0
    rids, _ = engine.drain_dirty2()
    assert len(rids) == 0, "regrant dirtied the row"
    store.regrant("missing", 3.0)  # released mid-solve: no-op
    assert store.sum_has == 7.5


def test_engine_sums_match_per_store_sums():
    """StoreEngine.sums reads every resource's aggregates in one call:
    row by row the per-store `_sums()`, through assigns, refreshes, a
    release, an expiry sweep and a dense grant write-back."""
    clock = FakeClock()
    engine = native.StoreEngine(clock=clock)
    stores = [engine.store(f"r{i}") for i in range(5)]
    rids = np.array([s._rid for s in stores], np.int32)

    def check():
        rows = engine.sums(rids)
        assert rows.shape == (5, 4) and rows.dtype == np.float64
        for s, row in zip(stores, rows):
            np.testing.assert_array_equal(row, s._sums())
        return rows

    for i, s in enumerate(stores):
        for j in range(i + 2):  # expiries 110 + 10 j
            s.assign(f"c{j}", 10.0 + 10.0 * j, 5.0, 0.5 * j,
                     3.0 * j + i, 1 + j % 3)
    check()

    handles = np.array([engine.client_handle(f"c{j}") for j in range(3)],
                       np.int64)
    assert engine.bulk_refresh(
        np.full(3, stores[2]._rid, np.int32), handles,
        np.full(3, 1e12), np.full(3, 5.0), np.array([7.0, 8.0, 9.0]),
    ) == 3
    check()

    stores[3].release("c1")
    check()

    clock.t = 125.0  # c0 and c1 lapse except where refreshed
    assert engine.clean_all() > 0
    rows = check()
    assert rows[0, 3] == 0 and rows[2, 3] == 4

    wants, _, _, act, counts, versions = engine.pack_rows(rids, 8)
    grants = np.where(act.astype(bool), 0.5 * wants + 1.0, 0.0)
    assert engine.apply_dense(
        rids, grants, np.zeros(5, np.uint8), versions
    ) == 5
    rows = check()
    np.testing.assert_allclose(rows[:, 0], grants.sum(axis=1))


def test_engine_sums_out_of_range_rid_reads_zeros():
    engine = native.StoreEngine(clock=FakeClock())
    store = engine.store("real")
    store.assign("c0", 60.0, 5.0, 1.0, 2.0, 3)
    rows = engine.sums(np.array([999, -1, store._rid], np.int32))
    np.testing.assert_array_equal(rows[:2], np.zeros((2, 4)))
    np.testing.assert_array_equal(rows[2], [1.0, 2.0, 3.0, 1.0])
    assert engine.sums(np.zeros(0, np.int32)).shape == (0, 4)


def test_out_of_range_resource_handles_are_noops():
    """Every extern entry point must treat an out-of-range resource
    handle as a no-op (skip / return 0 / zero-fill), never as an
    out-of-bounds read: the ctypes boundary should degrade a
    Python-level bookkeeping bug into a miss, not memory corruption.
    Exercised through raw lib calls with a handle the engine never
    issued."""
    import ctypes

    import numpy as np

    clock = FakeClock()
    engine = native.StoreEngine(clock=clock)
    store = engine.store("real")
    store.assign("c0", 60.0, 5.0, 1.0, 2.0, 1)
    lib, ptr = engine._lib, engine._ptr
    bad = 999  # never issued by dm_resource

    assert lib.dm_regrant(ptr, bad, 0, 5.0) == 0
    assert lib.dm_assign(ptr, bad, 0, 60.0, 5.0, 1.0, 2.0, 1, 0) == 0
    assert lib.dm_release(ptr, bad, 0) == 0
    assert lib.dm_clean(ptr, bad, ctypes.c_double(1e18)) == 0
    assert lib.dm_get(ptr, bad, 0, (ctypes.c_double * 6)()) == 0

    sums = (ctypes.c_double * 4)(7.0, 7.0, 7.0, 7.0)
    lib.dm_sums(ptr, bad, sums)
    assert list(sums) == [0.0, 0.0, 0.0, 0.0]

    out = (ctypes.c_int64 * 4)()
    assert lib.dm_dump(
        ptr, bad, out, (ctypes.c_double * 4)(), (ctypes.c_double * 4)(),
        (ctypes.c_double * 4)(), (ctypes.c_double * 4)(),
        (ctypes.c_int32 * 4)(), (ctypes.c_int64 * 4)(), 4
    ) == 0

    # dm_pack skips out-of-range order entries but keeps packing the
    # valid ones (segment ids still index the order array).
    order = np.array([bad, store._rid], np.int32)
    ridx = np.empty(4, np.int32)
    cid = np.empty(4, np.int64)
    w = np.empty(4, np.float64)
    h = np.empty(4, np.float64)
    s = np.empty(4, np.float64)
    p = np.empty(4, np.int64)
    n = lib.dm_pack(
        ptr, order.ctypes.data_as(native._I32P), 2,
        ridx.ctypes.data_as(native._I32P),
        cid.ctypes.data_as(native._I64P),
        w.ctypes.data_as(native._F64P), h.ctypes.data_as(native._F64P),
        s.ctypes.data_as(native._F64P), p.ctypes.data_as(native._I64P),
        4,
    )
    assert n == 1 and int(ridx[0]) == 1 and w[0] == 2.0

    # dm_apply: an edge whose segment maps to an out-of-range handle
    # is skipped (order[] upper bound), valid edges still apply.
    u8p = ctypes.POINTER(ctypes.c_uint8)
    ridx_a = np.array([0, 1], np.int32)
    cid_a = np.array([0, engine.client_handle("c0")], np.int64)
    gets = np.array([9.0, 3.5], np.float64)
    keep = np.zeros(2, np.uint8)
    applied_flags = np.zeros(2, np.uint8)
    applied = lib.dm_apply(
        ptr, order.ctypes.data_as(native._I32P), 2,
        ridx_a.ctypes.data_as(native._I32P),
        cid_a.ctypes.data_as(native._I64P),
        gets.ctypes.data_as(native._F64P), 2,
        keep.ctypes.data_as(u8p),
        applied_flags.ctypes.data_as(u8p),
    )
    assert applied == 1
    assert list(applied_flags) == [0, 1]
    assert store.get("c0").has == 3.5

    # And the real store's demand is untouched by all of the above.
    assert store.sum_wants == 2.0 and len(store) == 1
