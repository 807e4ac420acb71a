"""Churn-proportional scoped solve: byte identity, escalation, and the
scope-cache dispatch pins.

The scoped tick (solver/engine.py ScopeTracker + the fused-scoped
executables in resident.py / resident_wide.py) solves only the
resource-group closure of the dirty set plus the not-yet-converged
frontier, gathered into a pow2-bucketed compact table, and carries
every other unit's resident grants forward untouched. This suite pins
the claims that make it shippable:

  * byte identity: scoped vs full stores are IDENTICAL over seeded
    churn that mixes bf16-exact/non-exact wants, releases, new
    clients, learning flips and config-epoch bumps, across all four
    resident paths (narrow/wide x single-device/mesh), with the
    delta-tracking changed-rid stream — the streaming push's input —
    equal too;
  * escalation: every forced-full reason fires when its trigger does
    (rebuild, config-epoch, config-drift, expiry-sweep, round-trip,
    disabled, scope-reset) and `last_solve_mode`/`last_full_reason`
    record it;
  * accounting: a steady scoped tick costs 3 dispatches (fused buffer
    + scope buffer + launch) while the scope changes and falls back to
    the PR-13 2-dispatch floor when the scope repeats (the quiet-tick
    fixpoint: the scope index buffer is cached, never re-placed);
  * closure: a wide resource's scope spans ALL its straddling chunks
    from one dirty slot; mesh ticks carry per-shard scoped extents
    whose counts sum to the global scope.
"""

import numpy as np
import pytest

import tests.conftest  # noqa: F401

from doorman_tpu import native
from doorman_tpu.parallel import make_mesh
from doorman_tpu.solver.resident import ResidentDenseSolver
from doorman_tpu.solver.resident_wide import WideResidentSolver
from doorman_tpu.utils import dispatch as dispatch_mod
from tests.test_engine import assert_store_parity, conformance_churn
from tests.test_resident_solver import all_leases, make_world

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native engine unavailable"
)

SCOPED_PATHS = ("resident", "resident_mesh", "wide", "wide_mesh")


def _make(path, engine, clock, scoped, fused=True):
    mesh = make_mesh() if path.endswith("_mesh") else None
    if path.startswith("resident"):
        return ResidentDenseSolver(
            engine, dtype=np.float64, clock=clock, rotate_ticks=1,
            mesh=mesh, fused=fused, scoped=scoped,
        )
    return WideResidentSolver(
        engine, dtype=np.float64, clock=clock, rotate_ticks=1,
        chunk_width=8, mesh=mesh, fused=fused, scoped=scoped,
    )


@pytest.mark.parametrize("path", SCOPED_PATHS)
def test_scoped_vs_full_byte_identity(path):
    """The load-bearing pin: one seeded churn stream (mixed bands of
    algo kinds via make_world, bf16-exact and non-exact wants,
    releases, new clients, a learning-mode flip with a config-epoch
    bump), scoped and full solvers compared store-for-store every
    tick. Narrow paths additionally run delta tracking and must emit
    the SAME changed-rid stream — the streaming push fans out from
    exactly this set, so equal rids pin the push sequence unchanged."""
    t = [1000.0]
    clock = lambda: t[0]  # noqa: E731
    eng_a, res_a = make_world(clock)
    eng_b, res_b = make_world(clock)
    full = _make(path, eng_a, clock, scoped=False)
    scoped = _make(path, eng_b, clock, scoped=True)
    assert scoped.scoped_solve and not full.scoped_solve
    track = path.startswith("resident")
    if track:
        assert full.enable_delta_tracking()
        assert scoped.enable_delta_tracking()
    rng_a, rng_b = (np.random.default_rng(17) for _ in range(2))
    scoped_ran = 0
    for step in range(10):
        conformance_churn(res_a, step, rng_a)
        conformance_churn(res_b, step, rng_b)
        if step == 4:
            # Learning-mode flip mid-run: the epoch bump must escalate
            # the scoped path to a full solve, loudly.
            res_a[2].learning_mode_end = t[0] + 2.5
            res_b[2].learning_mode_end = t[0] + 2.5
        epoch = 1 if step >= 4 else 0
        full.step(res_a, epoch)
        scoped.step(res_b, epoch)
        ref, got = all_leases(res_a), all_leases(res_b)
        # Scoped vs full is exact on EVERY path (the compact solve
        # runs the same per-unit ops over the same values; unscoped
        # units are carried, not recomputed) — the wide paths'
        # reassociation tolerance applies vs the BatchSolver, not
        # here.
        assert ref.keys() == got.keys(), f"{path} step {step}"
        for key in ref:
            assert got[key] == ref[key], (
                f"{path} step {step} lease {key}: "
                f"{got[key]} != {ref[key]}"
            )
        if track:
            assert (
                sorted(full.take_changed_rids())
                == sorted(scoped.take_changed_rids())
            ), f"{path} step {step}: changed-rid streams diverged"
        if scoped.last_solve_mode == "scoped":
            scoped_ran += 1
        t[0] += 1.0
    # The scoped executable actually ran (not everything escalated),
    # and the full reference never ran scoped.
    assert scoped_ran >= 5, scoped.solve_modes
    assert full.solve_modes["scoped"] == 0
    scoped_keys = [
        k for k in scoped._tick_fns if "scoped" in str(k[0])
    ]
    assert scoped_keys, "no scoped executable compiled"


def test_scoped_matches_batch_ground_truth():
    """Scoped narrow stores also match the BatchSolver oracle world, so
    the scoped path cannot drift from the reference math even if both
    resident modes drifted together."""
    from doorman_tpu.solver.batch import BatchSolver
    from doorman_tpu.solver.engine import BatchTickAdapter

    t = [1000.0]
    clock = lambda: t[0]  # noqa: E731
    eng_a, res_a = make_world(clock)
    eng_b, res_b = make_world(clock)
    batch = BatchTickAdapter(BatchSolver(dtype=np.float64, clock=clock))
    scoped = _make("resident", eng_b, clock, scoped=True)
    rng_a, rng_b = (np.random.default_rng(23) for _ in range(2))
    for step in range(6):
        conformance_churn(res_a, step, rng_a)
        conformance_churn(res_b, step, rng_b)
        batch.step(res_a, 0)
        scoped.step(res_b, 0)
        assert_store_parity(
            all_leases(res_a), all_leases(res_b), "resident",
            f"step {step}",
        )
        t[0] += 1.0
    assert scoped.solve_modes["scoped"] >= 4


def test_forced_full_escalation_reasons():
    """Each escalation trigger fires its documented reason (the
    forced-full reasons table, doc/operations.md) and the tick solves
    full; steady ticks in between run scoped."""
    t = [1000.0]
    clock = lambda: t[0]  # noqa: E731
    engine, resources = make_world(clock)
    solver = _make("resident", engine, clock, scoped=True)

    def tick(epoch=0):
        solver.step(resources, epoch)
        t[0] += 1.0
        return solver.last_solve_mode, solver.last_full_reason

    # First tick: rebuild.
    assert tick() == ("full", "rebuild")
    # Steady dirty tick: scoped.
    resources[0].store.assign("c0_0", 60.0, 5.0, 0.0, 17.0, 1)
    assert tick() == ("scoped", None)
    # Config-epoch bump: templates re-read.
    assert tick(epoch=1) == ("full", "config-epoch")
    # Learning window installed WITH an epoch bump (the mirror re-read
    # sees it); the flip when TIME passes the window end — no epoch
    # movement — is the time-driven config-drift escalation.
    resources[3].learning_mode_end = t[0] + 1.5
    assert tick(epoch=2) == ("full", "config-epoch")
    assert tick(epoch=2)[0] == "scoped"  # inside the window: steady
    assert tick(epoch=2) == ("full", "config-drift")  # window ended
    # Expiry sweep: a lease the sweep removes without naming its row.
    resources[5].store.assign("dying", 0.5, 0.5, 0.0, 3.0, 1)
    t[0] += 2.0
    assert tick(epoch=2) == ("full", "expiry-sweep")
    # Membership change (new resource list) forces a rebuild.
    engine2, resources2 = make_world(clock, n_res=13)
    solver2 = _make("resident", engine2, clock, scoped=True)
    solver2.step(resources2[:12], 0)
    assert solver2.last_full_reason == "rebuild"
    solver2.step(resources2, 0)
    assert (
        solver2.last_solve_mode,
        solver2.last_full_reason,
    ) == ("full", "rebuild")
    # Runtime toggle off -> "disabled"; back on -> one "scope-reset".
    solver.scoped_solve = False
    assert tick(epoch=2) == ("full", "disabled")
    solver.scoped_solve = True
    assert tick(epoch=2) == ("full", "scope-reset")
    resources[0].store.assign("c0_0", 60.0, 5.0, 0.0, 19.0, 1)
    assert tick(epoch=2) == ("scoped", None)


def test_round_trip_mode_never_scopes():
    """fused=False (the triage baseline) records the round-trip
    reason and produces identical stores anyway."""
    t = [1000.0]
    clock = lambda: t[0]  # noqa: E731
    engine, resources = make_world(clock)
    solver = _make("resident", engine, clock, scoped=True, fused=False)
    rng = np.random.default_rng(3)
    for step in range(3):
        conformance_churn(resources, step, rng)
        solver.step(resources, 0)
        t[0] += 1.0
    assert solver.solve_modes["scoped"] == 0
    assert solver.last_full_reason == "round-trip"


def test_scope_cache_dispatch_counts():
    """The scope-buffer cache pin (the PR-13-style dispatch-count
    test): a steady tracked scoped tick costs 3 dispatches (fused
    buffer + scope buffer + launch) and 1 host sync while the scope
    CHANGES; when the same dirty set repeats — the quiet-tick fixpoint
    producing a byte-identical scope vector — the cached scope buffer
    is NOT re-placed and the tick is back at the 2-dispatch fused
    floor."""
    t = [1000.0]
    clock = lambda: t[0]  # noqa: E731
    engine, resources = make_world(clock)
    solver = _make("resident", engine, clock, scoped=True)
    solver.enable_delta_tracking()
    rng = np.random.default_rng(5)
    for step in range(4):  # build + settle the executables
        conformance_churn(resources, step, rng)
        solver.step(resources, 0)
        t[0] += 1.0

    def dirty(i, wants):
        resources[i].store.assign(
            f"c{i}_0", 60.0, 5.0,
            resources[i].store.get(f"c{i}_0").has, wants, 1,
        )

    # Drain the frontier to empty so the scope is exactly the dirty
    # row (quiet ticks retire converged rows through the moved mask).
    for _ in range(6):
        solver.step(resources, 0)
        t[0] += 1.0

    # Same dirty row, same wants value twice: after the first tick
    # establishes the scope (and its frontier entry keeps the row in
    # scope), the second tick's scope vector is byte-identical and the
    # cache must serve it.
    dirty(0, 21.0)
    solver.step(resources, 0)
    t[0] += 1.0
    dirty(0, 22.0)
    before = dispatch_mod.snapshot()
    solver.step(resources, 0)
    cached = dispatch_mod.delta(before)
    t[0] += 1.0
    assert solver.last_solve_mode == "scoped"
    assert cached["dispatches"] == 2, cached
    assert cached["host_syncs"] == 1, cached

    # A DIFFERENT row dirties: the scope vector changes, costing the
    # one extra scope-buffer placement.
    dirty(7, 33.0)
    before = dispatch_mod.snapshot()
    solver.step(resources, 0)
    moved = dispatch_mod.delta(before)
    t[0] += 1.0
    assert solver.last_solve_mode == "scoped"
    assert moved["dispatches"] == 3, moved
    assert moved["host_syncs"] == 1, moved


def test_quiet_ticks_shrink_scope_to_fixpoint():
    """After churn stops, the frontier drains through the moved-mask
    feedback down to its floor — the rows the full solve itself never
    stops moving (PROPORTIONAL_SHARE's `min(scaled, free)` can cycle
    at the ULP, and the scoped tick replays the full solve's
    iteration bit-for-bit) — and the scope then REPEATS byte-identically
    tick over tick, which is what the scope-buffer cache and the
    2-dispatch quiet-tick pin ride on. A FAIR_SHARE-only world (its
    level depends on wants, not has) converges bitwise and drains the
    frontier to exactly zero."""
    from doorman_tpu.core.resource import Resource
    from doorman_tpu.proto import doorman_pb2 as pb

    t = [1000.0]
    clock = lambda: t[0]  # noqa: E731
    # Mixed world: the frontier must shrink and stabilize.
    engine, resources = make_world(clock)
    solver = _make("resident", engine, clock, scoped=True)
    rng = np.random.default_rng(7)
    for step in range(3):
        conformance_churn(resources, step, rng)
        solver.step(resources, 0)
        t[0] += 1.0
    sizes = []
    for _ in range(8):
        solver.step(resources, 0)
        if solver.last_solve_mode == "scoped":
            sizes.append(solver.last_scope["rows"])
        t[0] += 1.0
    assert sizes[-1] <= max(2, sizes[0]), sizes
    assert sizes[-1] == sizes[-2] == sizes[-3], sizes  # stable floor

    # Fair-share world: exact bitwise convergence, frontier -> empty.
    eng2 = native.StoreEngine(clock=clock)
    res2 = []
    for r in range(6):
        tpl = pb.ResourceTemplate(
            identifier_glob=f"fair{r}", capacity=100.0,
            algorithm=pb.Algorithm(
                kind=pb.Algorithm.FAIR_SHARE,
                lease_length=60, refresh_interval=5,
            ),
        )
        res = Resource(
            f"fair{r}", tpl, clock=clock, store_factory=eng2.store
        )
        for c in range(5):
            res.store.assign(f"f{r}_{c}", 60.0, 5.0, 0.0, 30.0 + c, 1)
        res2.append(res)
    solver2 = _make("resident", eng2, clock, scoped=True)
    for _ in range(6):
        solver2.step(res2, 0)
        t[0] += 1.0
    assert len(solver2._scope) == 0
    assert solver2.last_scope == {"rows": 0, "resources": 0}


def test_pow2_bucket_boundaries_compile_bounded():
    """Scope sizes crossing a pow2 boundary compile a new executable;
    sizes within a bucket reuse it (the recompile count stays
    O(log R))."""
    from doorman_tpu.solver.engine import pow2_bucket

    assert pow2_bucket(0) == 8
    assert pow2_bucket(8) == 8
    assert pow2_bucket(9) == 16
    assert pow2_bucket(17, 8) == 32
    t = [1000.0]
    clock = lambda: t[0]  # noqa: E731
    engine, resources = make_world(clock)
    solver = _make("resident", engine, clock, scoped=True)
    solver.step(resources, 0)
    t[0] += 1.0

    def scoped_cbs():
        return {
            k[4]
            for k in solver._tick_fns
            if str(k[0]) == "fused_scoped"
        }

    # The post-rebuild frontier covers all 12 rows -> the 16 bucket;
    # once it drains, small dirty sets ride the 8 bucket; a mid-bucket
    # size reuses the executable (no new key).
    solver.step(resources, 0)
    t[0] += 1.0
    assert 16 in scoped_cbs()
    for _ in range(8):  # drain to the small-scope bucket
        solver.step(resources, 0)
        t[0] += 1.0
    resources[0].store.assign("x", 60.0, 5.0, 0.0, 5.0, 1)
    solver.step(resources, 0)
    t[0] += 1.0
    assert scoped_cbs() == {8, 16}
    n_keys = len(solver._tick_fns)
    # Another small scope (different rows, same bucket): no recompile.
    resources[3].store.assign("x", 60.0, 5.0, 0.0, 6.0, 1)
    solver.step(resources, 0)
    t[0] += 1.0
    assert len(solver._tick_fns) == n_keys
    assert scoped_cbs() == {8, 16}


def test_wide_straddling_chunk_closure():
    """The group-closure invariant on the wide path: ONE dirty slot of
    a resource that straddles several chunk rows scopes the segment's
    ENTIRE row span (per-segment lanes couple every chunk), and only
    that segment."""
    from doorman_tpu.core.resource import Resource
    from doorman_tpu.proto import doorman_pb2 as pb

    t = [1000.0]
    clock = lambda: t[0]  # noqa: E731
    engine = native.StoreEngine(clock=clock)
    resources = []
    for r, n_clients in ((0, 30), (1, 30), (2, 6)):
        # FAIR_SHARE: the level depends on wants/subclients only, so
        # the solve converges bitwise in one tick and the settled
        # frontier is exactly empty (see
        # test_quiet_ticks_shrink_scope_to_fixpoint for why a
        # has-coupled lane may keep a ULP-cycling floor).
        tpl = pb.ResourceTemplate(
            identifier_glob=f"wide{r}",
            capacity=500.0,
            algorithm=pb.Algorithm(
                kind=pb.Algorithm.FAIR_SHARE,
                lease_length=60, refresh_interval=5,
            ),
        )
        res = Resource(
            f"wide{r}", tpl, clock=clock, store_factory=engine.store
        )
        for c in range(n_clients):
            res.store.assign(f"w{r}_{c}", 60.0, 5.0, 0.0, 7.0 + c, 1)
        resources.append(res)
    solver = WideResidentSolver(
        engine, dtype=np.float64, clock=clock, rotate_ticks=1,
        chunk_width=8, scoped=True,
    )
    solver.step(resources, 0)  # rebuild (full)
    t[0] += 1.0
    # Settle the post-rebuild frontier to empty.
    for _ in range(8):
        solver.step(resources, 0)
        t[0] += 1.0
    # An empty scope, nothing staged: the floor-sized scatter blocks.
    assert solver.last_scope == {
        "rows": 0, "resources": 0, "slots": 0, "padded": 1024 + 256,
        "whole": 0,
    }
    # One slot of resource 0 (30 clients / width 8 -> 4 chunk rows).
    resources[0].store.assign("w0_3", 60.0, 5.0, 0.0, 99.0, 1)
    solver.step(resources, 0)
    assert solver.last_solve_mode == "scoped"
    assert solver.last_scope["resources"] == 1
    assert solver.last_scope["rows"] == 4  # the whole straddling span


def test_mesh_per_shard_scope_extents():
    """Mesh narrow ticks group the scope by owning shard: the handle's
    per-shard scoped counts sum to the global scope and the moved
    feedback still retires converged rows."""
    t = [1000.0]
    clock = lambda: t[0]  # noqa: E731
    engine, resources = make_world(clock)
    solver = _make("resident_mesh", engine, clock, scoped=True)
    solver.step(resources, 0)
    t[0] += 1.0
    # Dirty rows on two different shards (rows split across devices).
    for i in (0, 11):
        resources[i].store.assign(
            f"c{i}_0", 60.0, 5.0,
            resources[i].store.get(f"c{i}_0").has, 51.0 + i, 1,
        )
    handle = solver.dispatch(resources, 0)
    assert handle.scope_ids is not None
    assert handle.scope_counts is not None
    assert int(handle.scope_counts.sum()) == len(handle.scope_ids)
    assert (np.diff(handle.scope_ids) > 0).all()  # sorted, unique
    solver.collect(handle)
    t[0] += 1.0
    # Quiet ticks drain the frontier through the per-shard moved mask:
    # what stays is exactly the rows whose next solve still moves them
    # (a PROPORTIONAL_SHARE row whose Σ wants·p rounds past capacity
    # 2-cycles by an ULP forever, as the reference formula does).
    for _ in range(8):
        solver.step(resources, 0)
        t[0] += 1.0
    assert set(solver._scope.ids().tolist()) == _moving_rows(solver)


def _moving_rows(solver) -> set:
    """Rows whose fresh full solve differs from their resident grants,
    solved on the host from the engine's own device tables."""
    import jax

    from doorman_tpu.solver.dense import DenseBatch, solve_dense

    get = lambda a: np.asarray(jax.device_get(a))  # noqa: E731
    cfg = solver._config
    has = get(solver._has)
    gets = np.asarray(solve_dense(DenseBatch(
        wants=get(solver._wants), has=has, subclients=get(solver._sub),
        active=get(solver._act), capacity=get(cfg.cap_d),
        algo_kind=get(cfg.kind_d), learning=get(cfg.learn_d),
        static_capacity=get(cfg.statc_d),
    )))
    moved = (gets != has).any(axis=1)[: solver._R]
    return set(np.nonzero(moved)[0].tolist())


def test_scoped_toggle_mid_run_keeps_parity():
    """Flipping scoped_solve at runtime (triage flow) keeps stores
    byte-identical to an always-full reference; re-enabling re-seeds
    the frontier before the next scoped tick."""
    t = [1000.0]
    clock = lambda: t[0]  # noqa: E731
    eng_a, res_a = make_world(clock)
    eng_b, res_b = make_world(clock)
    ref = _make("resident", eng_a, clock, scoped=False)
    toggled = _make("resident", eng_b, clock, scoped=True)
    rng_a, rng_b = (np.random.default_rng(31) for _ in range(2))
    for step in range(8):
        conformance_churn(res_a, step, rng_a)
        conformance_churn(res_b, step, rng_b)
        if step == 3:
            toggled.scoped_solve = False
        if step == 5:
            toggled.scoped_solve = True
        ref.step(res_a, 0)
        toggled.step(res_b, 0)
        assert all_leases(res_a) == all_leases(res_b), f"step {step}"
        t[0] += 1.0
    assert toggled.solve_modes["scoped"] >= 2


def test_scope_status_block():
    """The /debug/status scope block reports plain host values (mode,
    reason, scope, frontier, tick split) — what the server's status()
    embeds per resident path."""
    t = [1000.0]
    clock = lambda: t[0]  # noqa: E731
    engine, resources = make_world(clock)
    solver = _make("resident", engine, clock, scoped=True)
    solver.step(resources, 0)
    st = solver.scope_status()
    assert st["enabled"] is True
    assert st["last_mode"] == "full"
    assert st["last_full_reason"] == "rebuild"
    assert st["full_ticks"] == 1 and st["scoped_ticks"] == 0
    t[0] += 1.0
    resources[0].store.assign("c0_0", 60.0, 5.0, 0.0, 9.0, 1)
    solver.step(resources, 0)
    st = solver.scope_status()
    assert st["last_mode"] == "scoped"
    assert st["last_full_reason"] is None
    assert st["scoped_ticks"] == 1
    assert st["last_scope_rows"] >= 1
    assert st["frontier"] >= 1
