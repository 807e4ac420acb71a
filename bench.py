"""Headline benchmark: batched lease recompute at 1M clients x 10k resources.

North star (BASELINE.md): recompute the leases of 1M clients over 10k
resources in < 100 ms on one TPU chip. Layout is the TPU-native dense
bucket [R, K] (doorman_tpu.solver.dense): 10k resources x 100 clients each
padded to K=128 — per-resource aggregation is a row reduction on the VPU,
no scatter/gather in the solve.

The measured loop is the steady-state tick pipeline exactly as the batch
server runs it, with the device as the store of record:

  upload demand deltas (5% of resources change wants per tick)
    -> on-device: scatter deltas into the donated wants table, solve the
       FULL table (every lease of every resource recomputed; `has` chains
       from the previous tick)
    -> download the grant rows for the clients refreshing this tick
       (20% per tick at the reference's 5s min refresh / ~1s tick), bf16,
       sliced to the bucket fill width (the snapshot packer stores clients
       contiguously from lane 0, so only the first `fill` lanes carry
       leases — no padding bytes cross the host link).

Several ticks stay in flight (uploads run ahead of the solve, downloads
trail it, as in the server's asyncio tick loop); reported value is
steady-state wall-clock per tick, best of RUNS measured runs (the
host<->device link is shared and noisy; best-of-N isolates the
framework's own steady state).  Before the measured runs, a spot check
validates one full tick's grants against the numpy oracle
(doorman_tpu.algorithms.tick) and the downloaded slice against the
on-device table.

Prints one JSON line:
    {"metric": ..., "value": <ms per tick>, "unit": "ms",
     "vs_baseline": <100ms target / measured>}
"""

from __future__ import annotations

import json
import time

import numpy as np

# Every JSON line printed also lands in doc/bench_last.json (with a
# timestamp and platform) via emit(): a committed, auditable record of
# the last successful measurement that survives a run that dies later
# (the measured numbers must never live only in prose).
_EMITTED: list = []
_DIAGNOSTICS: list = []
# Rows carried forward from the previous artifact on --only runs (a
# stage subset must not discard the other stages' standing rows); a
# re-measured metric replaces its carried-forward row.
_PRESEEDED: list = []
_PLATFORM_INFO: dict = {}
_TRAJECTORY = None  # lazy TrajectoryComparator over prior BENCH rounds


def _annotate_row(obj: dict) -> None:
    """Every metric row carries its SLO verdict (the north-star tick
    budget for *_wall_ms rows; storm rows attach their own verdict
    list before emit) and its delta vs the previous BENCH round that
    measured the same metric. Annotation trouble must never kill a
    measurement — it reports to stderr and the row ships bare."""
    if "metric" not in obj:
        return
    try:
        import os

        from doorman_tpu.obs import slo as slo_mod

        global _TRAJECTORY
        if _TRAJECTORY is None:
            _TRAJECTORY = slo_mod.TrajectoryComparator(
                os.path.dirname(os.path.abspath(__file__))
            )
        obj.setdefault("delta_vs_prev", _TRAJECTORY.delta(obj))
        if "slo" not in obj:
            verdict = slo_mod.bench_verdict(obj)
            if verdict is not None:
                verdict["delta_vs_prev"] = _TRAJECTORY.slo_delta(verdict)
                obj["slo"] = verdict
        elif isinstance(obj["slo"], list):
            for verdict in obj["slo"]:
                verdict.setdefault(
                    "delta_vs_prev", _TRAJECTORY.slo_delta(verdict)
                )
    except Exception as e:  # the measurement outranks its annotations
        import sys

        print(f"bench: row annotation failed: {e!r}", file=sys.stderr)


def emit(obj: dict, artifact_extra: dict = None) -> None:
    """Print one result line and append it to the artifact.
    `artifact_extra` rides along in doc/bench_last.json only (bulky
    payloads like per-tick phase breakdowns stay off stdout, whose last
    line the driver parses as the headline metric)."""
    _annotate_row(obj)
    print(json.dumps(obj), flush=True)
    rec = dict(obj)
    if artifact_extra:
        rec.update(artifact_extra)
    _EMITTED.append(rec)
    # Incremental artifact: every emitted result lands on disk
    # IMMEDIATELY, so a run that dies mid-sequence discards nothing
    # already measured.
    try:
        write_artifact(complete=False)
    except Exception:
        pass  # artifact trouble must never kill a measurement run


def diagnostic(obj: dict) -> None:
    """Report a run-infrastructure condition (backend unreachable, probe
    failures). Distinct from emit(): a diagnostic is NOT a measurement —
    it prints and lands in the artifact under "diagnostics", never in
    "results", so trajectory tooling cannot ingest it as a metric row
    (the BENCH_r05 {"metric": "backend_unreachable", "value": 0} trap)."""
    print(json.dumps(obj), flush=True)
    _DIAGNOSTICS.append(obj)
    try:
        write_artifact(complete=False)
    except Exception:
        pass


def _platform_info() -> dict:
    """Device identity for the artifact (require_tpu records it)."""
    import platform

    info = {"platform": "unknown", "device": "unknown"}
    info.update(_PLATFORM_INFO)
    info["host"] = platform.node()
    return info


def write_artifact(complete: bool = True) -> None:
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "doc", "bench_last.json"
    )
    info = _platform_info()
    record = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": info["platform"],
        "device": info["device"],
        "host": info["host"],
        # False marks a partial artifact (run still going, or died
        # mid-run): the results list holds everything emitted so far.
        "complete": complete,
        "results": [
            row
            for row in _PRESEEDED
            if row.get("metric")
            not in {r.get("metric") for r in _EMITTED}
        ]
        + _EMITTED,
        # Infrastructure conditions (probe failures etc.) — never
        # measurements; kept apart so tooling can't mistake them.
        "diagnostics": _DIAGNOSTICS,
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)

NUM_CLIENTS = 1_000_000
NUM_RESOURCES = 10_000
CLIENTS_PER_RESOURCE = NUM_CLIENTS // NUM_RESOURCES  # 100
BUCKET_K = 128
CHURN_RESOURCES = NUM_RESOURCES // 20  # 5% demand churn per tick
REFRESH_RESOURCES = NUM_RESOURCES // 5  # 20% of leases delivered per tick
TARGET_MS = 100.0
TICKS = 40
PIPELINE_DEPTH = 8  # downloads in flight; the link needs >=4 to stream
UPLOAD_LOOKAHEAD = 2  # ticks of demand churn staged ahead of the solve
RUNS = 5  # best-of


def phase_attribution(solver, phase_mark, collects_mark, n_ticks):
    """Per-phase ms/tick over the measured window, shared by the narrow
    and wide server-tick benches: dispatch phases divide by the ticks
    DISPATCHED in the window, collect phases (download/apply) by the
    collects that actually landed in it (pipelining shifts a few
    warmup collects past the snapshot)."""
    n_collects = max(solver.ticks - collects_mark, 1)
    collect_phases = ("download", "apply")
    return {
        k: round(
            (v - phase_mark.get(k, 0.0)) * 1000.0
            / (n_collects if k in collect_phases else n_ticks),
            3,
        )
        for k, v in solver.phase_s.items()
    }


def phase_deltas_ms(samples):
    """Per-tick phase breakdown (ms) from consecutive cumulative
    phase_s snapshots — one dict per tick, for the artifact (satisfies
    'where does THIS tick's time go', not just the window average)."""
    return [
        {
            k: round((cur.get(k, 0.0) - prev.get(k, 0.0)) * 1000.0, 3)
            for k in cur
        }
        for prev, cur in zip(samples, samples[1:])
    ]


def spot_check(wants, has, active, capacity, kind, static_cap, gets):
    """Validate a handful of resources against the numpy oracles."""
    from doorman_tpu.algorithms.tick import oracle_row

    rng = np.random.default_rng(7)
    for r in rng.integers(0, wants.shape[0], 25):
        m = active[r]
        w = wants[r, m].astype(np.float64)
        expected = oracle_row(
            int(kind[r]), float(capacity[r]), float(static_cap[r]),
            w, has[r, m].astype(np.float64), np.ones_like(w),
        )
        np.testing.assert_allclose(
            gets[r, m].astype(np.float64), expected, rtol=2e-6, atol=1e-4,
            err_msg=f"resource {r} kind {int(kind[r])}",
        )


def main() -> None:
    import jax
    import jax.numpy as jnp

    from doorman_tpu.solver.dense import DenseBatch
    from doorman_tpu.solver.pallas_dense import solve_dense_pallas

    device = jax.devices()[0]
    dtype = np.float32
    solve = solve_dense_pallas  # fused VMEM kernel for the solve

    rng = np.random.default_rng(42)
    R, K, C = NUM_RESOURCES, BUCKET_K, CLIENTS_PER_RESOURCE
    active = np.zeros((R, K), dtype=bool)
    active[:, :C] = True
    wants0 = (rng.integers(0, 100, (R, K)) * active).astype(dtype)
    capacity = rng.integers(100, 100_000, R).astype(dtype)
    kind = rng.choice(
        np.array([0, 1, 2, 3, 4], dtype=np.int32),
        size=R,
        p=[0.05, 0.05, 0.6, 0.25, 0.05],
    )
    static_cap = rng.integers(1, 100, R).astype(dtype)

    put = lambda a: jax.device_put(a, device)
    sub_d = put(active.astype(dtype))
    active_d = put(active)
    cap_d, kind_d = put(capacity), put(kind)
    learning_d = put(np.zeros(R, dtype=bool))
    static_d = put(static_cap)

    from functools import partial

    @partial(jax.jit, donate_argnums=(0, 1))
    def tick(wants, has, idx, rows, refresh_idx):
        wants = wants.at[idx].set(rows)
        gets = solve(
            DenseBatch(
                wants=wants, has=has, subclients=sub_d, active=active_d,
                capacity=cap_d, algo_kind=kind_d, learning=learning_d,
                static_capacity=static_d,
            )
        )
        # Only the first C lanes of each bucket row carry leases (the
        # snapshot packer fills clients contiguously from lane 0); padding
        # bytes never cross the host link.
        return wants, gets, gets[refresh_idx, :C].astype(jnp.bfloat16)

    # Pre-generate per-tick demand churn and refresh batches on the host.
    churn_idx = [
        rng.choice(R, CHURN_RESOURCES, replace=False).astype(np.int32)
        for _ in range(TICKS)
    ]
    churn_rows = [
        (rng.integers(0, 100, (CHURN_RESOURCES, K)) * active[churn_idx[t]])
        .astype(dtype)
        for t in range(TICKS)
    ]
    refresh_idx = [
        rng.choice(R, REFRESH_RESOURCES, replace=False).astype(np.int32)
        for _ in range(TICKS)
    ]

    # Warm-up/compile, then a correctness spot check of one full tick.
    wants_d = put(wants0)
    has_d = put(np.zeros((R, K), dtype))
    wants_d, gets_d, out = tick(
        wants_d, has_d, put(churn_idx[0]), put(churn_rows[0]),
        put(refresh_idx[0]),
    )
    jax.block_until_ready(out)
    wants1 = np.array(wants0)
    wants1[churn_idx[0]] = churn_rows[0]
    gets_host = jax.device_get(gets_d)
    spot_check(
        wants1, np.zeros((R, K)), active, capacity, kind, static_cap,
        gets_host,
    )
    # The downloaded slice must be exactly the bf16 view of the grant
    # rows that refreshed this tick — validates the :C packing.
    np.testing.assert_array_equal(
        jax.device_get(out),
        gets_host[refresh_idx[0], :C].astype(jnp.bfloat16),
    )

    # Steady-state pipelined ticks: churn uploads for the next
    # UPLOAD_LOOKAHEAD ticks are staged while earlier ticks solve, and up
    # to PIPELINE_DEPTH grant downloads trail the solves.
    from doorman_tpu.utils.transfer import land_parts, start_download

    def run_once():
        wants_d = put(wants0)
        gets_d = put(np.zeros((R, K), dtype))
        staged, in_flight = {}, []
        start = time.perf_counter()
        for t in range(TICKS):
            for ta in range(t, min(t + UPLOAD_LOOKAHEAD + 1, TICKS)):
                if ta not in staged:
                    staged[ta] = (
                        put(churn_idx[ta]), put(churn_rows[ta]),
                        put(refresh_idx[ta]),
                    )
            idx, rows, ridx = staged.pop(t)
            wants_d, gets_d, out = tick(wants_d, gets_d, idx, rows, ridx)
            # Several async copy streams per slab (the link needs
            # overlapping copies in flight to reach full bandwidth).
            in_flight.append(start_download(out))
            if len(in_flight) >= PIPELINE_DEPTH:
                land_parts(in_flight.pop(0))
        for parts in in_flight:
            land_parts(parts)
        return time.perf_counter() - start

    per_tick_ms = sorted(
        run_once() / TICKS * 1000.0 for _ in range(RUNS)
    )

    # Best-of-N is the headline, with the selection rule explicit and
    # median/mean alongside for run-over-run comparability.
    ms = per_tick_ms[0]
    emit(
        {
            "metric": (
                "lease_recompute_1m_clients_x_10k_resources_wall_ms"
            ),
            "value": round(ms, 3),
            "unit": "ms",
            "vs_baseline": round(TARGET_MS / ms, 3),
            "selection": f"best_of_{RUNS}",
            "median_ms": round(float(np.median(per_tick_ms)), 3),
            "mean_ms": round(float(np.mean(per_tick_ms)), 3),
        }
    )


def bench_server_tick() -> None:
    """Second metric: the REAL server tick end-to-end at 1M leases.

    Unlike the headline loop (a synthetic table), this measures the
    batch server's actual hot path with the native C++ engine as the
    store of record, exactly as server.py's tick loop runs it
    (replacing reference go/server/doorman/server.go:732-817), through
    the device-resident solver (solver/resident.py):

      dispatch — expiry sweep (one dm_clean_all C call), drain the
                 engine's dirty-row list, pack + upload ONLY the rows
                 whose demand changed (5% churn per tick, applied
                 between ticks as the RPC handlers would), launch the
                 full-table solve, start the grant download for the
                 delivery set (dirty rows + the rotation slice that
                 rides the 16s refresh cadence);
      collect  — download lands, one dm_apply_dense C call writes the
                 grants back (lease expiry stays client-driven: the 5%
                 churn per tick re-stamps its leases the way RPC
                 refreshes would).

    PIPELINE_DEPTH_SERVER ticks stay in flight, as in the server's
    tick loop. Steady state: warm-up ticks compile both bucket shapes,
    then per-tick wall times are measured; median reported (best
    alongside). The first tick (rotate=1: every grant delivered) is
    spot-checked against the numpy oracles before any timing.

    Measured twice from identical initial state: the round-trip
    store->drain->pack path first (its metric name and semantics
    unchanged since r03, so its delta_vs_prev stays honest), then the
    FUSED pipeline as the headline row (the driver parses the LAST
    line): fused-tick mode — one packed staged upload, ONE
    staging->solve->delta launch, one download stream
    (solver/resident.py fused tails) — plus admission-fused staging
    (each churn batch plays an admission window that pre-packs the
    rows it wrote, engine.FusedStaging). The fused row carries the
    per-tick dispatch accounting (`dispatches_per_tick` /
    `host_syncs_per_tick` through the utils.dispatch chokepoints, and
    `dispatch_reduction` vs the round-trip run), its own tick-budget
    SLO verdict, and the STANDING <10 ms one-chip TPU verdict
    (obs.slo.tpu_tick_budget_spec — no_data on CPU fallback, pass/fail
    automatically on the next hardware round). Both variants ride the
    engine seam's compact transfers (bf16-exact wants, int32 indices);
    tests/test_engine.py + tests/test_fused_tick.py pin the paths
    byte-identical.
    """
    import jax

    from doorman_tpu import native
    from doorman_tpu.core.resource import Resource
    from doorman_tpu.proto import doorman_pb2 as pb
    from doorman_tpu.solver.resident import ResidentDenseSolver

    device = jax.devices()[0]
    dtype = np.float32

    R, C = NUM_RESOURCES, CLIENTS_PER_RESOURCE

    def run(fused: bool, scoped: bool = False,
            churn_res: int = CHURN_RESOURCES,
            lane: "tuple | None" = None,
            audit: int = 0) -> dict:
        """One full build + warmup + measured window; a fresh engine
        and rng per variant, so every path starts from byte-identical
        stores and replays the same-seeded churn stream. `fused` turns
        on the WHOLE fused pipeline: fused-tick mode (one launch per
        tick) plus admission-fused staging. `scoped` additionally
        scopes each tick's solve to the dirty rows + convergence
        frontier (the churn-proportional tick); `churn_res` is the
        resources whose demand changes per tick (the churn tiers).
        `lane` = (wire kind, variant|None) pins EVERY resource to one
        algorithm lane (the fairness-portfolio rows; the rng still
        draws the kind vector so the demand stream stays identical to
        the mixed runs). `audit` > 0 attaches a production-shaped
        ShadowAuditor (obs/audit.py) sampling every `audit` ticks:
        the hot-path snapshot cost lands inside the measured tick
        wall exactly as server.py's _audit_step pays it, the oracle
        replay rides the audit executor off-thread."""
        rng = np.random.default_rng(11)
        engine = native.StoreEngine()
        kind_choices = np.array(
            [
                pb.Algorithm.NO_ALGORITHM,
                pb.Algorithm.STATIC,
                pb.Algorithm.PROPORTIONAL_SHARE,
                pb.Algorithm.FAIR_SHARE,
            ],
            dtype=np.int64,
        )
        kinds = rng.choice(
            kind_choices, size=R, p=[0.05, 0.05, 0.65, 0.25]
        )
        capacity = rng.integers(100, 100_000, R).astype(np.float64)

        def algorithm(r: int) -> pb.Algorithm:
            if lane is None:
                return pb.Algorithm(
                    kind=int(kinds[r]), lease_length=600,
                    refresh_interval=16,
                )
            wkind, variant = lane
            algo = pb.Algorithm(
                kind=int(wkind), lease_length=600, refresh_interval=16
            )
            if variant is not None:
                algo.parameters.add(name="variant", value=variant)
            return algo

        resources = []
        rids = np.empty(R * C, np.int32)
        for r in range(R):
            tpl = pb.ResourceTemplate(
                identifier_glob=f"res{r}",
                capacity=float(capacity[r]),
                algorithm=algorithm(r),
            )
            res = Resource(f"res{r}", tpl, store_factory=engine.store)
            resources.append(res)
            rids[r * C : (r + 1) * C] = res.store._rid
        res_rids = rids[::C].copy()  # one engine rid per resource

        # 1M distinct clients, C per resource, loaded in one bulk call.
        cids = np.array(
            [engine.client_handle(f"c{i}") for i in range(R * C)],
            np.int64,
        )
        wants = rng.integers(0, 100, R * C).astype(np.float64)
        now = time.time()
        engine.bulk_assign(
            rids,
            cids,
            np.full(R * C, now + 600.0),
            np.full(R * C, 16.0),
            np.zeros(R * C),
            wants,
            np.ones(R * C, np.int32),
        )

        solver = ResidentDenseSolver(
            engine, dtype=dtype, device=device,
            rotate_ticks=1,  # first tick delivers all (oracle check)
            fused=fused,
            scoped=scoped,
        )
        if fused:
            solver.attach_staging()
        solver.step(resources)  # build + compile + full delivery

        # Spot-check the first tick against the numpy oracles: after
        # it, has == grants computed from (capacity, wants, has=0).
        from doorman_tpu.algorithms.tick import oracle_row
        from doorman_tpu.core.resource import algo_kind_for, static_param

        for r in rng.integers(0, R, 10):
            res = resources[r]
            st = [
                res.store.get(f"c{i}")
                for i in range(r * C, (r + 1) * C)
            ]
            w = np.array([lease.wants for lease in st])
            g = np.array([lease.has for lease in st])
            # The internal lane (variant-aware), not the wire kind:
            # the portfolio rows pin their lane's own oracle.
            k = algo_kind_for(res.template)
            expected = oracle_row(
                k, float(capacity[r]), static_param(res.template),
                w, np.zeros_like(w), np.ones_like(w),
            )
            np.testing.assert_allclose(
                g, expected, rtol=2e-6, atol=1e-4,
                err_msg=f"res{r} kind {k}",
            )

        # Steady state: grants rotate out on the refresh cadence
        # (refresh_interval=16s at ~1s ticks), dirty rows same-tick.
        solver.rotate_ticks = SERVER_ROTATE_TICKS

        # Pre-generate per-tick demand churn (5% of resources change
        # wants), applied through the engine's bulk path as the RPC
        # handlers' store writes land between ticks.
        n_ticks = SERVER_WARMUP + TICKS_SERVER
        churn_rows = [
            rng.choice(R, churn_res, replace=False)
            for _ in range(n_ticks)
        ]
        churn_wants = [
            rng.integers(0, 100, churn_res * C).astype(np.float64)
            for _ in range(n_ticks)
        ]

        def churn(t):
            # A client refresh's store effect: wants update + expiry
            # stamp, has preserved (only grants change has).
            sel = churn_rows[t]
            edge = (sel[:, None] * C + np.arange(C)).ravel()
            engine.bulk_refresh(
                rids[edge], cids[edge],
                np.full(len(edge), time.time() + 600.0),
                np.full(len(edge), 16.0),
                churn_wants[t],
            )
            if fused:
                # The admission window that just wrote these rows
                # pre-packs them (server._fused_stage's hot path);
                # the next dispatch's drain still decides WHICH rows
                # ship — the cache only short-circuits the pack.
                solver.stage_rids(res_rids[sel])

        from doorman_tpu.utils import dispatch as dispatch_mod

        auditor = None
        res_map = {}
        if audit:
            from doorman_tpu.obs.audit import ShadowAuditor

            auditor = ShadowAuditor(sample=audit, inline=False)
            res_map = {res.id: res for res in resources}

        tick_ms = []
        tick_only_ms = []
        churn_ms = []
        handles = []
        phase_mark = {}
        collects_mark = 0
        fused_windows = fused_rows = 0
        scoped_ticks = full_ticks = 0
        scoped_rows_sum = 0
        dispatch_mark = dispatch_mod.snapshot()
        phase_samples = [dict(solver.phase_s)]
        for t in range(n_ticks):
            if t == SERVER_WARMUP:
                phase_mark = dict(solver.phase_s)
                collects_mark = solver.ticks
                fused_windows = fused_rows = 0
                scoped_ticks = full_ticks = 0
                scoped_rows_sum = 0
                dispatch_mark = dispatch_mod.snapshot()
            t0 = time.perf_counter()
            churn(t)
            t1 = time.perf_counter()
            handles.append(solver.dispatch(resources))
            fused_windows += solver.last_fused["windows"]
            fused_rows += solver.last_fused["rows"]
            if solver.last_solve_mode == "scoped":
                scoped_ticks += 1
                scoped_rows_sum += solver.last_scope["rows"]
            else:
                full_ticks += 1
            if len(handles) >= PIPELINE_DEPTH_SERVER:
                solver.collect(handles.pop(0))
            if auditor is not None:
                # The hot-path half of the audit (predicate + host
                # snapshot) on the measured clock, as the server's
                # tick loop pays it; the compare is off-thread.
                auditor.maybe_sample(t, None, res_map)
            t2 = time.perf_counter()
            churn_ms.append((t1 - t0) * 1000.0)
            tick_ms.append((t2 - t0) * 1000.0)
            # Dispatch+collect only (the churn writer excluded): the
            # series the churn-proportionality SLO fits — the writer's
            # cost is the CLIENT workload and scales with churn by
            # definition; the tick's solve cost is the claim.
            tick_only_ms.append((t2 - t1) * 1000.0)
            phase_samples.append(dict(solver.phase_s))
        t0 = time.perf_counter()
        for h in handles:
            solver.collect(h)
        drain_ms = (time.perf_counter() - t0) * 1000.0
        audit_stats = None
        if auditor is not None:
            auditor.drain()
            auditor.close()
            audit_stats = auditor.status()
        # Per-tick device-dispatch accounting over the measured window
        # (the same counters the flight recorder stamps per server
        # tick): the fused-vs-round-trip launch-tax reduction as a
        # number on the rows below.
        dispatch_delta = dispatch_mod.delta(dispatch_mark)
        timed = sorted(
            t + drain_ms / n_ticks for t in tick_ms[SERVER_WARMUP:]
        )
        # Per-phase attribution (phase_attribution): dispatch = sweep
        # + drain + staging + pack + config + upload + solve; collect
        # = download + apply; churn is the client-write workload
        # applied between ticks (included in the headline number
        # because the reference's per-request decide pays it inline
        # too — and in the fused variant it carries the window-time
        # row packs, which is exactly the point).
        phases = phase_attribution(
            solver, phase_mark, collects_mark, TICKS_SERVER
        )
        phases["churn"] = round(
            float(np.mean(churn_ms[SERVER_WARMUP:])), 3
        )
        return {
            "timed": timed,
            "tick_only": sorted(
                t + drain_ms / n_ticks
                for t in tick_only_ms[SERVER_WARMUP:]
            ),
            "phases": phases,
            "per_tick": phase_deltas_ms(phase_samples)[SERVER_WARMUP:],
            "fused_windows": fused_windows,
            "fused_rows": fused_rows,
            "scoped_ticks": scoped_ticks,
            "full_ticks": full_ticks,
            "scoped_rows_per_tick": round(
                scoped_rows_sum / max(scoped_ticks, 1), 1
            ),
            "dispatches_per_tick": round(
                dispatch_delta["dispatches"] / TICKS_SERVER, 3
            ),
            "host_syncs_per_tick": round(
                dispatch_delta["host_syncs"] / TICKS_SERVER, 3
            ),
            "audit": audit_stats,
        }

    # Round-trip variant first (metric name + semantics unchanged
    # since r03, so its trajectory deltas stay honest); the FUSED
    # pipeline is the headline — the LAST emitted line the driver
    # parses.
    main_run = run(fused=False)
    timed = main_run["timed"]
    med = float(np.median(timed))
    emit(
        {
            "metric": "server_tick_1m_leases_native_store_wall_ms",
            "value": round(med, 3),
            "unit": "ms",
            "vs_baseline": round(SERVER_TICK_TARGET_MS / med, 3),
            "selection": f"median_of_{TICKS_SERVER}",
            "best_ms": round(timed[0], 3),
            "p50_ms": round(float(np.percentile(timed, 50)), 3),
            "p90_ms": round(float(np.percentile(timed, 90)), 3),
            "p99_ms": round(float(np.percentile(timed, 99)), 3),
            "pipeline_depth": PIPELINE_DEPTH_SERVER,
            "rotate_ticks": SERVER_ROTATE_TICKS,
            "dispatches_per_tick": main_run["dispatches_per_tick"],
            "host_syncs_per_tick": main_run["host_syncs_per_tick"],
            "phase_ms": main_run["phases"],
        },
        artifact_extra={
            # Measured window only: one per-phase dict per tick.
            "phase_ms_per_tick": main_run["per_tick"],
        },
    )

    fused_run = run(fused=True)
    ftimed = fused_run["timed"]
    fmed = float(np.median(ftimed))
    fp50 = float(np.percentile(ftimed, 50))
    reduction = (
        main_run["dispatches_per_tick"]
        / max(fused_run["dispatches_per_tick"], 1e-9)
    )
    fused_row = {
        "metric": "server_tick_1m_leases_native_store_fused_wall_ms",
        "value": round(fmed, 3),
        "unit": "ms",
        "vs_baseline": round(SERVER_TICK_TARGET_MS / fmed, 3),
        "selection": f"median_of_{TICKS_SERVER}",
        "best_ms": round(ftimed[0], 3),
        "p50_ms": round(fp50, 3),
        "p90_ms": round(float(np.percentile(ftimed, 90)), 3),
        "p99_ms": round(float(np.percentile(ftimed, 99)), 3),
        "pipeline_depth": PIPELINE_DEPTH_SERVER,
        "rotate_ticks": SERVER_ROTATE_TICKS,
        # Fused-window depth over the measured window: windows
        # folded per tick and rows served from the window-time
        # pack cache (the same tallies the flight recorder stamps
        # on each server tick as fused_windows/fused_rows).
        "fused_windows_per_tick": round(
            fused_run["fused_windows"] / TICKS_SERVER, 3
        ),
        "fused_rows_per_tick": round(
            fused_run["fused_rows"] / TICKS_SERVER, 3
        ),
        # The launch-tax numbers: device dispatches + host syncs per
        # tick through the counted chokepoints, and the reduction the
        # one-launch fused tick buys vs the round-trip run above
        # (acceptance floor: >= 3x).
        "dispatches_per_tick": fused_run["dispatches_per_tick"],
        "host_syncs_per_tick": fused_run["host_syncs_per_tick"],
        "dispatch_reduction": round(reduction, 2),
        "phase_ms": fused_run["phases"],
    }
    from doorman_tpu.obs import slo as slo_mod

    verdicts = []
    budget = slo_mod.bench_verdict(fused_row)
    if budget is not None:
        verdicts.append(budget)
    # The standing one-chip TPU target (<10 ms p50).
    verdicts.append(slo_mod.tpu_tick_verdict(fp50))
    fused_row["slo"] = verdicts
    emit(
        fused_row,
        artifact_extra={
            "phase_ms_per_tick": fused_run["per_tick"],
        },
    )

    # ---- scoped churn tiers: tick cost follows churn, not table size.
    # One scoped run per churn tier (--churn), same build + seeded
    # stream discipline as above. Tier rows report the full headline
    # semantics (churn writer included) PLUS tick_only_* (dispatch +
    # collect, writer excluded) — the series the churn-proportionality
    # SLO fits, since the writer's cost scales with churn by
    # definition. The HEADLINE tier (1% churn, the production steady
    # state) is emitted LAST as
    # server_tick_1m_leases_native_store_scoped_wall_ms; the worst-case
    # pin measures an UNscoped full solve at the 100% tier so "the
    # worst case never regresses" compares like against like
    # (doc/bench.md "Churn tiers").
    headline_frac = SCOPED_HEADLINE_CHURN
    tiers = {}
    for frac in SCOPED_CHURN_TIERS:
        churn_res = max(1, min(R, int(round(R * frac))))
        tiers[frac] = run(fused=True, scoped=True, churn_res=churn_res)
        tiers[frac]["churn_res"] = churn_res
    full100 = run(
        fused=True, scoped=False,
        churn_res=max(1, min(R, int(round(R * max(SCOPED_CHURN_TIERS))))),
    )

    def tier_row(frac, data, metric):
        ttimed = data["timed"]
        tonly = data["tick_only"]
        row = {
            "metric": metric,
            "value": round(float(np.median(ttimed)), 3),
            "unit": "ms",
            "vs_baseline": round(
                SERVER_TICK_TARGET_MS / float(np.median(ttimed)), 3
            ),
            "selection": f"median_of_{TICKS_SERVER}",
            "churn_fraction": frac,
            "churn_resources_per_tick": data["churn_res"],
            "p50_ms": round(float(np.percentile(ttimed, 50)), 3),
            "p90_ms": round(float(np.percentile(ttimed, 90)), 3),
            "p99_ms": round(float(np.percentile(ttimed, 99)), 3),
            "tick_only_p50_ms": round(
                float(np.percentile(tonly, 50)), 3
            ),
            "tick_only_p99_ms": round(
                float(np.percentile(tonly, 99)), 3
            ),
            # Scope shape over the measured window: rows the compact
            # solve covered per scoped tick, and the scoped/full tick
            # split (forced-full escalations show here).
            "scoped_rows_per_tick": data["scoped_rows_per_tick"],
            "scoped_ticks": data["scoped_ticks"],
            "full_ticks": data["full_ticks"],
            "dispatches_per_tick": data["dispatches_per_tick"],
            "host_syncs_per_tick": data["host_syncs_per_tick"],
            "pipeline_depth": PIPELINE_DEPTH_SERVER,
            "rotate_ticks": SERVER_ROTATE_TICKS,
            "phase_ms": data["phases"],
        }
        return row

    def tier_label(frac):
        pct = frac * 100.0
        text = (f"{pct:g}").replace(".", "p")
        return f"churn{text}pct"

    for frac in sorted(tiers):
        if frac == headline_frac:
            continue  # the headline tier is the LAST emitted line
        emit(
            tier_row(
                frac, tiers[frac],
                "server_tick_1m_leases_native_store_scoped_"
                f"{tier_label(frac)}_wall_ms",
            ),
            artifact_extra={
                "phase_ms_per_tick": tiers[frac]["per_tick"],
            },
        )

    # Churn-proportionality verdicts: the log-log slope of tick-only
    # median vs churn fraction must stay sublinear (< 1.0 — cost
    # follows churn), and the 100%-churn scoped tier must stay within
    # noise of the unscoped full solve at the same churn (<= 1.15x —
    # the worst case never regresses).
    fracs = sorted(tiers)
    med_only = {
        f: float(np.median(tiers[f]["tick_only"])) for f in fracs
    }
    exponent = round(
        float(
            np.polyfit(
                np.log([f for f in fracs]),
                np.log([max(med_only[f], 1e-9) for f in fracs]),
                1,
            )[0]
        ),
        3,
    )
    worst_frac = max(fracs)
    full100_med = float(np.median(full100["tick_only"]))
    worst_ratio = round(med_only[worst_frac] / max(full100_med, 1e-9), 3)
    prop_specs = [
        slo_mod.SloSpec(
            name="server_tick_scoped:churn_proportional",
            kind="max", target=1.0, unit="exponent",
            source={"type": "scalar", "key": "exponent"},
            description=(
                "log-log slope of scoped tick-only median vs churn "
                "fraction — < 1.0 means tick cost follows churn, "
                "not table size"
            ),
        ),
        slo_mod.SloSpec(
            name="server_tick_scoped:worst_case_vs_full",
            kind="max", target=1.15, unit="ratio",
            source={"type": "scalar", "key": "worst_ratio"},
            description=(
                "100%-churn scoped tick-only median vs the unscoped "
                "full solve at the same churn — the worst case never "
                "regresses"
            ),
        ),
    ]
    prop_verdicts = slo_mod.SloEngine(prop_specs).evaluate(
        slo_mod.SloInputs(
            scalars={"exponent": exponent, "worst_ratio": worst_ratio}
        )
    )
    emit({
        "metric": "server_tick_scoped_churn_proportionality",
        "value": exponent,
        "unit": "exponent",
        "tiers": {
            str(f): round(med_only[f], 3) for f in fracs
        },
        "tiers_wall_ms": {
            str(f): round(float(np.median(tiers[f]["timed"])), 3)
            for f in fracs
        },
        "worst_ratio_vs_full": worst_ratio,
        "full_solve_at_worst_tier_ms": round(full100_med, 3),
        "slo": prop_verdicts,
    })

    # ---- fairness portfolio: per-lane rows at the same 1M-lease
    # shape, each through the FULL fused + scoped pipeline at the
    # headline churn (every resource pinned to one lane via the
    # config's `variant` parameter, the same demand stream as the
    # mixed runs). Each row carries the tick-budget SLO — the "any
    # algorithm, same sub-100 ms tick" claim as numbers — plus the
    # standing <10 ms TPU verdict on tick-only p50.
    headline_churn_res = max(
        1, min(R, int(round(R * headline_frac)))
    )
    PORTFOLIO_LANES = [
        ("fair_share", int(pb.Algorithm.FAIR_SHARE), None),
        ("maxmin", int(pb.Algorithm.FAIR_SHARE), "maxmin"),
        ("balanced", int(pb.Algorithm.FAIR_SHARE), "balanced"),
        ("logutil", int(pb.Algorithm.PROPORTIONAL_SHARE), "logutil"),
    ]
    lane_dispatches = {}
    for lname, wkind, variant in PORTFOLIO_LANES:
        lrun = run(
            fused=True, scoped=True, churn_res=headline_churn_res,
            lane=(wkind, variant),
        )
        lrun["churn_res"] = headline_churn_res
        lrow = tier_row(
            headline_frac, lrun,
            f"server_tick_1m_leases_native_store_{lname}_scoped_wall_ms",
        )
        lane_dispatches[lname] = lrun["dispatches_per_tick"]
        lverdicts = []
        budget = slo_mod.bench_verdict(lrow)
        if budget is not None:
            lverdicts.append(budget)
        lverdicts.append(
            slo_mod.tpu_tick_verdict(
                float(np.percentile(lrun["tick_only"], 50))
            )
        )
        lrow["slo"] = lverdicts
        emit(lrow)

    # Compile-away pin: a proportional-ONLY config (no iterative lane
    # in the static kind set) must tick with the same per-tick
    # dispatch/launch count as every portfolio run — absent lanes are
    # compiled away, never launched around (the jaxpr-level pin lives
    # in tests/test_fairness_lanes.py). Its scoped wall time is also
    # the headline's "unchanged-within-noise" guard: the portfolio
    # landing must not tax a deployment that never configures it.
    prop_run = run(
        fused=True, scoped=True, churn_res=headline_churn_res,
        lane=(int(pb.Algorithm.PROPORTIONAL_SHARE), None),
    )
    prop_run["churn_res"] = headline_churn_res
    prop_med_only = float(np.median(prop_run["tick_only"]))
    mixed_med_only = float(
        np.median(tiers[headline_frac]["tick_only"])
    )
    compile_away = all(
        d == prop_run["dispatches_per_tick"]
        for d in lane_dispatches.values()
    )
    ca_specs = [
        slo_mod.SloSpec(
            name="server_tick_portfolio:compile_away_dispatches",
            kind="max", target=0.0, unit="count",
            source={"type": "scalar", "key": "dispatch_spread"},
            description=(
                "max |dispatches_per_tick difference| between the "
                "proportional-only config and every portfolio lane "
                "run — absent lanes change executable content, never "
                "launch structure"
            ),
        ),
        slo_mod.SloSpec(
            name="server_tick_portfolio:proportional_only_unchanged",
            kind="max", target=1.15, unit="ratio",
            source={"type": "scalar", "key": "prop_ratio"},
            description=(
                "proportional-only scoped tick-only median vs the "
                "mixed headline tier — the portfolio must cost a "
                "lane-free config nothing beyond noise"
            ),
        ),
    ]
    dispatch_spread = max(
        abs(d - prop_run["dispatches_per_tick"])
        for d in lane_dispatches.values()
    )
    prop_ratio = round(
        prop_med_only / max(mixed_med_only, 1e-9), 3
    )
    ca_verdicts = slo_mod.SloEngine(ca_specs).evaluate(
        slo_mod.SloInputs(
            scalars={
                "dispatch_spread": dispatch_spread,
                "prop_ratio": prop_ratio,
            }
        )
    )
    emit({
        "metric": "server_tick_fairness_portfolio_compile_away",
        "value": prop_run["dispatches_per_tick"],
        "unit": "dispatches_per_tick",
        "dispatches_per_tick_by_lane": lane_dispatches,
        "identical_launch_count": compile_away,
        "proportional_only_tick_only_ms": round(prop_med_only, 3),
        "proportional_only_wall_ms": round(
            float(np.median(prop_run["timed"])), 3
        ),
        "ratio_vs_mixed_headline": prop_ratio,
        "slo": ca_verdicts,
    })

    # ---- shadow-audit overhead: the headline scoped config re-run
    # with a production-shaped ShadowAuditor sampling every 17 ticks
    # (coprime with the 16-tick rotation cadence, so samples never
    # alias the delivery slice). The hot-path cost — the fixpoint
    # predicate plus the host-side snapshot of every resource's solve
    # inputs — lands inside the measured tick wall exactly as
    # server.py's _audit_step pays it; the numpy-oracle replay rides
    # the audit executor. The gate: the audited median tick must stay
    # within 5% of the unaudited headline tier.
    audited = run(
        fused=True, scoped=True, churn_res=headline_churn_res,
        audit=SERVER_ROTATE_TICKS + 1,
    )
    audited_med = float(np.median(audited["timed"]))
    base_med = float(np.median(tiers[headline_frac]["timed"]))
    audit_ratio = round(audited_med / max(base_med, 1e-9), 3)
    audit_mean_ratio = round(
        float(np.mean(audited["timed"]))
        / max(float(np.mean(tiers[headline_frac]["timed"])), 1e-9),
        3,
    )
    audit_specs = [
        slo_mod.SloSpec(
            name="server_tick_audit:overhead",
            kind="max", target=1.05, unit="ratio",
            source={"type": "scalar", "key": "audit_ratio"},
            description=(
                "audited scoped headline median tick vs the unaudited "
                "tier — continuous shadow-oracle auditing must cost "
                "the steady-state tick <= 5%"
            ),
        ),
    ]
    audit_verdicts = slo_mod.SloEngine(audit_specs).evaluate(
        slo_mod.SloInputs(scalars={"audit_ratio": audit_ratio})
    )
    emit({
        "metric": "server_tick_1m_leases_audit_overhead",
        "value": audit_ratio,
        "unit": "ratio",
        "audited_wall_ms": round(audited_med, 3),
        "baseline_wall_ms": round(base_med, 3),
        "mean_ratio": audit_mean_ratio,
        "audit_sample_ticks": SERVER_ROTATE_TICKS + 1,
        "audit_samples": audited["audit"]["samples"],
        "audit_compared_resources": audited["audit"][
            "compared_resources"
        ],
        "audit_divergences": audited["audit"]["divergences"],
        "slo": audit_verdicts,
    })

    # The scoped steady-state tick is the round's HEADLINE (the LAST
    # emitted line, which the driver parses): 1% churn — a production
    # steady state — through the full fused + scoped pipeline.
    head = tier_row(
        headline_frac, tiers[headline_frac],
        "server_tick_1m_leases_native_store_scoped_wall_ms",
    )
    hp50_only = float(
        np.percentile(tiers[headline_frac]["tick_only"], 50)
    )
    head_verdicts = []
    budget = slo_mod.bench_verdict(head)
    if budget is not None:
        head_verdicts.append(budget)
    head_verdicts.append(
        slo_mod.tpu_tick_verdict(hp50_only)
    )
    head["slo"] = head_verdicts
    emit(
        head,
        artifact_extra={
            "phase_ms_per_tick": tiers[headline_frac]["per_tick"],
        },
    )


def bench_server_tick_wide() -> None:
    """Third metric: the WIDE-resource server tick — doorman's headline
    shape, ONE shared resource with a huge client population
    (/root/reference/doc/design.md:218; the reference's O(n)-per-request
    loop is /root/reference/go/server/doorman/algorithm.go:213-292) —
    measured end-to-end through the chunked wide resident solver
    (solver/resident_wide.py) with the native engine as the store of
    record, at 1 resource x 1M clients and 10 x 100k.

    Per tick: 5% of clients change wants (slot-granular dirty tracking
    ships only those slots), the full table solves on device with the
    two-level chunk reduction, and the rotation slice + full-dirty rows
    download and apply. Same pipelining/warmup discipline as
    bench_server_tick; median reported with p50/p90/p99."""
    import jax

    from doorman_tpu import native
    from doorman_tpu.core.resource import Resource
    from doorman_tpu.proto import doorman_pb2 as pb
    from doorman_tpu.solver.resident_wide import WideResidentSolver

    device = jax.devices()[0]
    dtype = np.float32

    from doorman_tpu.algorithms.tick import oracle_row

    for label, R, C in (("1res_1m", 1, 1_000_000),
                        ("10res_100k", 10, 100_000)):
        rng = np.random.default_rng(23)
        engine = native.StoreEngine()
        capacity = float(C) * 40.0  # oversubscribed (mean wants ~50)
        resources = []
        rids = np.empty(R * C, np.int32)
        for r in range(R):
            tpl = pb.ResourceTemplate(
                identifier_glob=f"wide{r}",
                capacity=capacity,
                algorithm=pb.Algorithm(
                    kind=pb.Algorithm.PROPORTIONAL_SHARE,
                    lease_length=600, refresh_interval=16,
                ),
            )
            res = Resource(f"wide{r}", tpl, store_factory=engine.store)
            resources.append(res)
            rids[r * C : (r + 1) * C] = res.store._rid
        cids = np.array(
            [engine.client_handle(f"w{i}") for i in range(R * C)],
            np.int64,
        )
        wants = rng.integers(1, 100, R * C).astype(np.float64)
        now = time.time()
        engine.bulk_assign(
            rids, cids, np.full(R * C, now + 600.0),
            np.full(R * C, 16.0), np.zeros(R * C), wants,
            np.ones(R * C, np.int32),
        )

        solver = WideResidentSolver(
            engine, dtype=dtype, device=device,
            rotate_ticks=1,  # first tick delivers everything
        )
        solver.step(resources)  # build + compile + full delivery

        # Oracle spot-check of the first tick (PROPORTIONAL_SHARE over
        # the full population, has=0 snapshot).
        for r in range(R):
            w = wants[r * C : (r + 1) * C]
            expected = oracle_row(
                int(pb.Algorithm.PROPORTIONAL_SHARE), capacity, 0.0,
                w.astype(np.float64), np.zeros(C), np.ones(C),
            )
            sample = rng.integers(0, C, 20)
            got = np.array(
                [resources[r].store.get(f"w{r * C + i}").has
                 for i in sample]
            )
            np.testing.assert_allclose(
                got, expected[sample], rtol=2e-6, atol=1e-4,
                err_msg=f"{label} resource {r}",
            )

        solver.rotate_ticks = SERVER_ROTATE_TICKS
        n_churn = (R * C) // 20  # 5% of clients per tick
        n_ticks = SERVER_WARMUP + TICKS_WIDE
        churn_edges = [
            rng.choice(R * C, n_churn, replace=False)
            for _ in range(n_ticks)
        ]
        churn_wants = [
            rng.integers(1, 100, n_churn).astype(np.float64)
            for _ in range(n_ticks)
        ]

        tick_ms = []
        handles = []
        phase_mark = {}
        collects_mark = 0
        phase_samples = [dict(solver.phase_s)]
        for t in range(n_ticks):
            if t == SERVER_WARMUP:
                phase_mark = dict(solver.phase_s)
                collects_mark = solver.ticks
            t0 = time.perf_counter()
            edge = churn_edges[t]
            engine.bulk_refresh(
                rids[edge], cids[edge],
                np.full(n_churn, time.time() + 600.0),
                np.full(n_churn, 16.0), churn_wants[t],
            )
            handles.append(solver.dispatch(resources))
            if len(handles) >= PIPELINE_DEPTH_SERVER:
                solver.collect(handles.pop(0))
            tick_ms.append((time.perf_counter() - t0) * 1000.0)
            phase_samples.append(dict(solver.phase_s))
        t0 = time.perf_counter()
        for h in handles:
            solver.collect(h)
        drain_ms = (time.perf_counter() - t0) * 1000.0
        timed = sorted(
            t + drain_ms / n_ticks for t in tick_ms[SERVER_WARMUP:]
        )
        med = float(np.median(timed))
        phases = phase_attribution(
            solver, phase_mark, collects_mark, TICKS_WIDE
        )
        emit(
            {
                "metric": f"server_tick_wide_{label}_wall_ms",
                "value": round(med, 3),
                "unit": "ms",
                "vs_baseline": round(SERVER_TICK_TARGET_MS / med, 3),
                "selection": f"median_of_{TICKS_WIDE}",
                "best_ms": round(timed[0], 3),
                "p50_ms": round(float(np.percentile(timed, 50)), 3),
                "p90_ms": round(float(np.percentile(timed, 90)), 3),
                "p99_ms": round(float(np.percentile(timed, 99)), 3),
                "chunk_rows": solver._R,
                "rotate_ticks": SERVER_ROTATE_TICKS,
                "phase_ms": phases,
            },
            artifact_extra={
                "phase_ms_per_tick": phase_deltas_ms(phase_samples)[
                    SERVER_WARMUP:
                ],
            },
        )


def bench_server_tick_wide_mesh() -> None:
    """Fourth metric: the WIDE server tick with the device table
    MESH-SHARDED across every visible chip (solver/resident_wide.py
    with mesh=) at the headline shape, 1 resource x 1M clients. Same
    workload, pipelining and warmup discipline as
    bench_server_tick_wide's 1res_1m case, so the reported scaling is
    the mesh's doing alone; `scaling_vs_1device` divides the 1-device
    median (measured earlier in this run) by this one.

    Requires >= 2 devices (and >= the --mesh-devices request): with
    fewer (one chip) this emits one `diagnostic` line — NOT a metric
    row — and does not run, so trajectory tooling never ingests a
    single-device number as a mesh measurement.
    """
    import jax

    from doorman_tpu import native
    from doorman_tpu.core.resource import Resource
    from doorman_tpu.parallel import make_mesh
    from doorman_tpu.proto import doorman_pb2 as pb
    from doorman_tpu.solver.resident_wide import WideResidentSolver

    devices = jax.devices()
    requested = max(MESH_BENCH_DEVICES or len(devices), 2)
    if len(devices) < requested:
        diagnostic(
            {
                "diagnostic": "mesh_devices_unavailable",
                "available": len(devices),
                "requested": requested,
                "note": (
                    "server_tick_wide_mesh needs >=2 devices; set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                    "for a CPU dry-run"
                ),
            }
        )
        return
    devices = devices[:requested]
    n_dev = len(devices)
    mesh = make_mesh([n_dev], ("clients",), devices)
    dtype = np.float32

    R, C = 1, 1_000_000
    rng = np.random.default_rng(23)
    engine = native.StoreEngine()
    capacity = float(C) * 40.0
    tpl = pb.ResourceTemplate(
        identifier_glob="wide0",
        capacity=capacity,
        algorithm=pb.Algorithm(
            kind=pb.Algorithm.PROPORTIONAL_SHARE,
            lease_length=600, refresh_interval=16,
        ),
    )
    res = Resource("wide0", tpl, store_factory=engine.store)
    rids = np.full(R * C, res.store._rid, np.int32)
    cids = np.array(
        [engine.client_handle(f"w{i}") for i in range(R * C)], np.int64
    )
    wants = rng.integers(1, 100, R * C).astype(np.float64)
    now = time.time()
    engine.bulk_assign(
        rids, cids, np.full(R * C, now + 600.0),
        np.full(R * C, 16.0), np.zeros(R * C), wants,
        np.ones(R * C, np.int32),
    )
    resources = [res]

    solver = WideResidentSolver(
        engine, dtype=dtype, mesh=mesh, rotate_ticks=1
    )
    solver.step(resources)  # build + compile + full delivery

    # Oracle spot-check of the first (deliver-everything) tick.
    from doorman_tpu.algorithms.tick import oracle_row

    expected = oracle_row(
        int(pb.Algorithm.PROPORTIONAL_SHARE), capacity, 0.0,
        wants.astype(np.float64), np.zeros(R * C), np.ones(R * C),
    )
    sample = rng.integers(0, C, 20)
    got = np.array([res.store.get(f"w{i}").has for i in sample])
    np.testing.assert_allclose(
        got, expected[sample], rtol=2e-6, atol=1e-4,
        err_msg="mesh wide first tick",
    )

    solver.rotate_ticks = SERVER_ROTATE_TICKS
    n_churn = (R * C) // 20
    n_ticks = SERVER_WARMUP + TICKS_WIDE
    churn_edges = [
        rng.choice(R * C, n_churn, replace=False) for _ in range(n_ticks)
    ]
    churn_wants = [
        rng.integers(1, 100, n_churn).astype(np.float64)
        for _ in range(n_ticks)
    ]

    tick_ms = []
    handles = []
    phase_mark = {}
    collects_mark = 0
    phase_samples = [dict(solver.phase_s)]
    for t in range(n_ticks):
        if t == SERVER_WARMUP:
            phase_mark = dict(solver.phase_s)
            collects_mark = solver.ticks
        t0 = time.perf_counter()
        edge = churn_edges[t]
        engine.bulk_refresh(
            rids[edge], cids[edge],
            np.full(n_churn, time.time() + 600.0),
            np.full(n_churn, 16.0), churn_wants[t],
        )
        handles.append(solver.dispatch(resources))
        if len(handles) >= PIPELINE_DEPTH_SERVER:
            solver.collect(handles.pop(0))
        tick_ms.append((time.perf_counter() - t0) * 1000.0)
        phase_samples.append(dict(solver.phase_s))
    t0 = time.perf_counter()
    for h in handles:
        solver.collect(h)
    drain_ms = (time.perf_counter() - t0) * 1000.0
    timed = sorted(t + drain_ms / n_ticks for t in tick_ms[SERVER_WARMUP:])
    med = float(np.median(timed))
    phases = phase_attribution(solver, phase_mark, collects_mark, TICKS_WIDE)
    # The 1-device comparator measured earlier in this same run (same
    # shape, same workload); absent when that bench did not run.
    base = next(
        (
            r["value"]
            for r in _EMITTED
            if r.get("metric") == "server_tick_wide_1res_1m_wall_ms"
        ),
        None,
    )
    emit(
        {
            "metric": "server_tick_wide_mesh_1res_1m_wall_ms",
            "value": round(med, 3),
            "unit": "ms",
            "vs_baseline": round(SERVER_TICK_TARGET_MS / med, 3),
            "selection": f"median_of_{TICKS_WIDE}",
            "best_ms": round(timed[0], 3),
            "p50_ms": round(float(np.percentile(timed, 50)), 3),
            "p90_ms": round(float(np.percentile(timed, 90)), 3),
            "p99_ms": round(float(np.percentile(timed, 99)), 3),
            "devices": n_dev,
            "chunk_rows": solver._R,
            "rotate_ticks": SERVER_ROTATE_TICKS,
            "scaling_vs_1device": (
                round(base / med, 3) if base else None
            ),
            "phase_ms": phases,
        },
        artifact_extra={
            "phase_ms_per_tick": phase_deltas_ms(phase_samples)[
                SERVER_WARMUP:
            ],
        },
    )


# server_rpc_storm: concurrent closed-loop GetCapacity clients against
# the real immediate-mode server over loopback gRPC, admission off vs
# on (doorman_tpu.admission).
STORM_WORKERS = 48
STORM_SECONDS = 2.0
STORM_CALIB_SECONDS = 0.8
# Saturation gate: the admission-off p99 must be at least this multiple
# of the single-worker p50, or the storm never actually queued on the
# event loop and the off/on comparison is meaningless — that run emits
# a diagnostic, never a metric row (BENCH_r05 convention).
STORM_SATURATION_FACTOR = 3.0


def bench_server_rpc_storm() -> None:
    """RPC goodput and tail latency under a client storm, admission off
    vs on.

    The real immediate-mode CapacityServer serves loopback gRPC while
    STORM_WORKERS closed-loop clients (pinned round-robin to three
    priority bands) hammer GetCapacity as fast as responses return —
    the front-door failure mode the admission subsystem exists for. A
    single-worker calibration pins the unloaded p50; the admission-off
    storm must push p99 past STORM_SATURATION_FACTOR x that, proving
    real queueing, before any metric row is emitted. The admission-on
    phase runs a fresh server with coalescing plus an offered-load
    budget set to 70% of the measured admission-off goodput, so the
    controller has real headroom to defend; storm workers honor
    retry-after with jitter exactly like the production client."""
    import asyncio

    from doorman_tpu.loadtest.storm import run_storm
    from doorman_tpu.server.config import parse_yaml_config
    from doorman_tpu.server.election import TrivialElection
    from doorman_tpu.server.server import CapacityServer

    config = parse_yaml_config(
        "resources:\n"
        '- identifier_glob: "*"\n'
        "  capacity: 1000000\n"
        "  algorithm: {kind: FAIR_SHARE, lease_length: 60,\n"
        "              refresh_interval: 1, learning_mode_duration: 0}\n"
    )

    async def storm_phase(admission, workers, seconds):
        server = CapacityServer(
            "storm-bench", TrivialElection(), mode="immediate",
            minimum_refresh_interval=0.0, admission=admission,
        )
        port = await server.start(0, host="127.0.0.1")
        await server.load_config(config)
        await asyncio.sleep(0)  # election callbacks land
        try:
            return await run_storm(
                f"127.0.0.1:{port}", "storm", workers=workers,
                duration=seconds, bands=(0, 1, 2), seed=7,
            )
        finally:
            await server.stop()

    async def run():
        calib = await storm_phase(None, 1, STORM_CALIB_SECONDS)
        off = await storm_phase(None, STORM_WORKERS, STORM_SECONDS)
        floor = STORM_SATURATION_FACTOR * max(calib["p50_s"], 1e-6)
        if off["p99_s"] < floor or off["ok"] == 0:
            # The storm never queued (a fast box, a tiny worker count,
            # a broken loopback): report why, emit NO metric rows.
            diagnostic({
                "diagnostic": "storm_unsaturated",
                "note": (
                    f"admission-off p99 {off['p99_s'] * 1000:.2f} ms "
                    f"below the saturation floor {floor * 1000:.2f} ms "
                    f"({STORM_SATURATION_FACTOR}x unloaded p50 "
                    f"{calib['p50_s'] * 1000:.2f} ms); storm stats: "
                    f"{off}"
                ),
            })
            return
        from doorman_tpu.admission import Admission

        admission = Admission(
            coalesce_window=0.005,
            window=0.25,
            max_rps=off["goodput_qps"] * 0.7,
        )
        on = await storm_phase(admission, STORM_WORKERS, STORM_SECONDS)
        emit({
            "metric": "server_rpc_storm_goodput_qps_admission_off",
            "value": off["goodput_qps"],
            "unit": "qps",
            "p50_ms": round(off["p50_s"] * 1000, 3),
            "p99_ms": round(off["p99_s"] * 1000, 3),
            "workers": STORM_WORKERS,
        })
        # Machine-readable SLO verdicts for the storm pair: top-band
        # goodput floor (per-band tallies embedded), per-band p99
        # ceilings vs the admission-off tails, and the goodput floor
        # the controller was budgeted to defend. emit() attaches each
        # verdict's delta vs the prior round.
        from doorman_tpu.obs import slo as slo_mod

        storm_slo = slo_mod.storm_slo_verdicts(
            off, on, goodput_floor_ratio=0.7
        )
        emit(
            {
                "metric": "server_rpc_storm_goodput_qps_admission_on",
                "value": on["goodput_qps"],
                "unit": "qps",
                "p50_ms": round(on["p50_s"] * 1000, 3),
                "p99_ms": round(on["p99_s"] * 1000, 3),
                "shed": on["shed"],
                "p99_vs_admission_off": round(
                    off["p99_s"] / max(on["p99_s"], 1e-9), 3
                ),
                "slo": storm_slo,
            },
            artifact_extra={"off": off, "on": on, "calibration": calib},
        )

    asyncio.run(run())


# server_push_vs_poll: the streaming lease push (WatchCapacity,
# doc/streaming.md) against the equivalent polling population on the
# same server build: steady-state GetCapacity rate, pushed bytes per
# tick, and grant-propagation latency from a wants churn to every
# subscriber observing its moved grant.
PUSH_SUBSCRIBERS = 1000
PUSH_STEADY_SECONDS = 3.0
PUSH_CHURN_EVENTS = 4
PUSH_CHURN_SETTLE_SECONDS = 2.6  # > refresh_interval + tick
PUSH_TICK_SECONDS = 0.1
PUSH_CHANNELS = 20
# refresh 2s is CONSERVATIVE for the poll side: reference configs
# refresh at 5s, which would flatter both ratios further.
PUSH_REFRESH_SECONDS = 2
PUSH_LEASE_SECONDS = 60


def bench_server_push_vs_poll() -> None:
    """Steady-state RPC load and grant-propagation latency: poll vs
    stream at PUSH_SUBSCRIBERS subscribers.

    Two phases against identically-configured batch-mode servers
    (python store; no device work — this bench measures the serving
    path). The POLL phase runs
    1k clients refreshing at the served refresh interval — the
    pre-streaming contract. The STREAM phase holds 1k WatchCapacity
    subscriptions on the same population. Each phase measures its
    steady-state GetCapacity rate over a quiet window (unchanged
    wants), then drives PUSH_CHURN_EVENTS oversubscription flips from
    one churner client and records, per subscriber, the time from the
    churn RPC to the first observed grant change (poll: next refresh
    that returns a moved lease; stream: the tick-edge push landing).

    The RPC-reduction verdict is conservative: the observed value is
    the MEASURED window ratio clamped to the analytic steady-state
    bound (lease margin / refresh interval) — a quiet window with zero
    stream-side RPCs must not claim more than the safety-poll cadence
    amortizes to over a full lease."""
    import asyncio

    import grpc as _grpc

    from doorman_tpu.proto import doorman_pb2 as _pb
    from doorman_tpu.proto import doorman_stream_pb2 as _spb
    from doorman_tpu.proto.grpc_api import CapacityStub
    from doorman_tpu.server.config import parse_yaml_config
    from doorman_tpu.server.election import TrivialElection
    from doorman_tpu.server.server import CapacityServer

    capacity = PUSH_SUBSCRIBERS * 10  # wants 10 each: exactly at cap
    config = parse_yaml_config(
        "resources:\n"
        '- identifier_glob: "*"\n'
        f"  capacity: {capacity}\n"
        "  safe_capacity: 1\n"
        "  algorithm: {kind: PROPORTIONAL_SHARE,\n"
        f"              lease_length: {PUSH_LEASE_SECONDS},\n"
        f"              refresh_interval: {PUSH_REFRESH_SECONDS},\n"
        "              learning_mode_duration: 0}\n"
    )

    async def make_server():
        server = CapacityServer(
            "push-bench", TrivialElection(), mode="batch",
            tick_interval=PUSH_TICK_SECONDS,
            minimum_refresh_interval=0.0, stream_push=True,
        )
        port = await server.start(0, host="127.0.0.1")
        await server.load_config(config)
        await asyncio.sleep(0)  # election callbacks land
        server.current_master = f"127.0.0.1:{port}"
        return server, f"127.0.0.1:{port}"

    def make_channels(addr):
        # Distinct connections (local subchannel pool) so 1k held
        # streams spread instead of queueing on one HTTP/2 session.
        return [
            _grpc.aio.insecure_channel(
                addr, options=(("grpc.use_local_subchannel_pool", 1),)
            )
            for _ in range(PUSH_CHANNELS)
        ]

    # Shared churn-event marker: subscriber tasks record the time from
    # the marked churn RPC to their FIRST observed grant change.
    event = {"id": 0, "t": 0.0}

    async def drive_churn(stub):
        """Flip one churner between under- and oversubscription; each
        flip rescales EVERY subscriber's proportional grant."""
        has = None
        for k in range(PUSH_CHURN_EVENTS):
            wants = float(capacity) if k % 2 == 0 else 1.0
            req = _pb.GetCapacityRequest(client_id="churner")
            rr = req.resource.add()
            rr.resource_id = "bench"
            rr.wants = wants
            if has is not None:
                rr.has.CopyFrom(has)
            event["id"] += 1
            event["t"] = time.monotonic()
            out = await stub.GetCapacity(req)
            lease = _pb.Lease()
            lease.CopyFrom(out.response[0].gets)
            has = lease
            await asyncio.sleep(PUSH_CHURN_SETTLE_SECONDS)

    async def poll_phase():
        server, addr = await make_server()
        channels = make_channels(addr)
        rpcs = [0]
        orig = server.on_request
        server.on_request = lambda m, d, e: (
            rpcs.__setitem__(0, rpcs[0] + (m == "GetCapacity")),
            orig(m, d, e),
        )
        samples: list = []
        stop = asyncio.Event()

        async def poller(i):
            stub = CapacityStub(channels[i % PUSH_CHANNELS])
            req = _pb.GetCapacityRequest(client_id=f"p{i}")
            rr = req.resource.add()
            rr.resource_id = "bench"
            rr.wants = 10.0
            last_cap, seen = None, 0
            # Stagger the fleet across the refresh interval.
            await asyncio.sleep((i / PUSH_SUBSCRIBERS)
                                * PUSH_REFRESH_SECONDS)
            while not stop.is_set():
                out = await stub.GetCapacity(req)
                rr.has.CopyFrom(out.response[0].gets)
                cap = out.response[0].gets.capacity
                if cap != last_cap:
                    if last_cap is not None and event["id"] > seen:
                        samples.append(time.monotonic() - event["t"])
                        seen = event["id"]
                    last_cap = cap
                try:
                    await asyncio.wait_for(
                        stop.wait(), PUSH_REFRESH_SECONDS
                    )
                except asyncio.TimeoutError:
                    pass

        tasks = [asyncio.ensure_future(poller(i))
                 for i in range(PUSH_SUBSCRIBERS)]
        try:
            # Join + settle, then the quiet steady-state window.
            await asyncio.sleep(2.0 * PUSH_REFRESH_SECONDS)
            mark = rpcs[0]
            await asyncio.sleep(PUSH_STEADY_SECONDS)
            steady = rpcs[0] - mark
            stub = CapacityStub(channels[0])
            await drive_churn(stub)
        finally:
            stop.set()
            for task in tasks:
                task.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            for ch in channels:
                await ch.close()
            await server.stop()
        return {"steady_rpcs": steady, "samples": sorted(samples)}

    async def stream_phase():
        server, addr = await make_server()
        channels = make_channels(addr)
        rpcs = [0]
        orig = server.on_request
        server.on_request = lambda m, d, e: (
            rpcs.__setitem__(0, rpcs[0] + (m == "GetCapacity")),
            orig(m, d, e),
        )
        samples: list = []
        stop = asyncio.Event()
        established = [0]

        async def subscriber(i):
            stub = CapacityStub(channels[i % PUSH_CHANNELS])
            req = _spb.WatchCapacityRequest(client_id=f"s{i}")
            rr = req.resource.add()
            rr.resource_id = "bench"
            rr.wants = 10.0
            call = stub.WatchCapacity(req)
            pending = None
            last_cap, seen = None, 0
            try:
                while not stop.is_set():
                    if pending is None:
                        pending = asyncio.ensure_future(call.read())
                    done, _ = await asyncio.wait(
                        {pending}, timeout=0.5
                    )
                    if not done:
                        continue
                    task, pending = pending, None
                    msg = task.result()
                    if msg is _grpc.aio.EOF or msg.HasField("mastership"):
                        return
                    if msg.snapshot:
                        established[0] += 1
                    for row in msg.response:
                        cap = row.gets.capacity
                        if cap != last_cap:
                            if (last_cap is not None
                                    and event["id"] > seen):
                                samples.append(
                                    time.monotonic() - event["t"]
                                )
                                seen = event["id"]
                            last_cap = cap
            finally:
                if pending is not None:
                    pending.cancel()
                call.cancel()

        tasks = [asyncio.ensure_future(subscriber(i))
                 for i in range(PUSH_SUBSCRIBERS)]
        try:
            # Establishment (1k subscribe decides) + settle.
            deadline = time.monotonic() + 15.0
            while (established[0] < PUSH_SUBSCRIBERS
                   and time.monotonic() < deadline):
                await asyncio.sleep(0.1)
            n_established = established[0]
            await asyncio.sleep(2.0 * PUSH_REFRESH_SECONDS)
            registry = server._streams
            mark = rpcs[0]
            bytes_mark = registry.total_bytes
            ticks_mark = server._ticks_done
            await asyncio.sleep(PUSH_STEADY_SECONDS)
            steady = rpcs[0] - mark
            steady_bytes = registry.total_bytes - bytes_mark
            steady_ticks = max(server._ticks_done - ticks_mark, 1)
            stub = CapacityStub(channels[0])
            churn_bytes_mark = registry.total_bytes
            churn_ticks_mark = server._ticks_done
            await drive_churn(stub)
            churn_bytes = registry.total_bytes - churn_bytes_mark
            churn_ticks = max(server._ticks_done - churn_ticks_mark, 1)
        finally:
            stop.set()
            for task in tasks:
                task.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            for ch in channels:
                await ch.close()
            await server.stop()
        return {
            "steady_rpcs": steady,
            "established": n_established,
            "steady_push_bytes_per_tick": round(
                steady_bytes / steady_ticks, 1
            ),
            "churn_push_bytes_per_tick": round(
                churn_bytes / churn_ticks, 1
            ),
            "samples": sorted(samples),
        }

    async def run():
        poll = await poll_phase()
        stream = await stream_phase()
        if (not poll["samples"] or not stream["samples"]
                or stream["established"] < PUSH_SUBSCRIBERS // 2):
            # The comparison never happened (establishment failed or no
            # subscriber observed the churn): report why, no metric row.
            diagnostic({
                "diagnostic": "push_vs_poll_invalid",
                "note": (
                    f"established {stream.get('established')} of "
                    f"{PUSH_SUBSCRIBERS}; propagation samples "
                    f"poll={len(poll['samples'])} "
                    f"stream={len(stream['samples'])}"
                ),
            })
            return
        from doorman_tpu.obs import slo as slo_mod

        poll_rate = poll["steady_rpcs"] / PUSH_STEADY_SECONDS
        stream_rate = stream["steady_rpcs"] / PUSH_STEADY_SECONDS
        measured = poll["steady_rpcs"] / max(stream["steady_rpcs"], 1)
        # What the expiry-margin safety poll amortizes to over a full
        # lease: one stream RPC per (lease - refresh) vs one poll per
        # refresh (client._watch_poll_deadline).
        amortized = (
            (PUSH_LEASE_SECONDS - PUSH_REFRESH_SECONDS)
            / PUSH_REFRESH_SECONDS
        )
        reduction = round(min(measured, amortized), 3)
        poll_p50 = slo_mod.sample_quantile(poll["samples"], 0.50)
        poll_p99 = slo_mod.sample_quantile(poll["samples"], 0.99)
        stream_p50 = slo_mod.sample_quantile(stream["samples"], 0.50)
        stream_p99 = slo_mod.sample_quantile(stream["samples"], 0.99)
        speedup = round(poll_p50 / max(stream_p50, 1e-9), 3)
        specs = [
            slo_mod.SloSpec(
                name="server_push_vs_poll:rpc_reduction",
                kind="min", target=10.0, unit="x",
                source={"type": "scalar", "key": "rpc_reduction"},
                description=(
                    "steady-state GetCapacity rate, poll/stream, "
                    "clamped to the lease-margin amortized bound"
                ),
            ),
            slo_mod.SloSpec(
                name="server_push_vs_poll:grant_propagation_speedup",
                kind="min", target=2.0, unit="x",
                source={"type": "scalar", "key": "prop_speedup_p50"},
                description=(
                    "grant-propagation p50, poll lag / push lag"
                ),
            ),
        ]
        verdicts = slo_mod.SloEngine(specs).evaluate(slo_mod.SloInputs(
            scalars={
                "rpc_reduction": reduction,
                "prop_speedup_p50": speedup,
            }
        ))
        emit({
            "metric": "server_push_vs_poll_rpc_rate_poll",
            "value": round(poll_rate, 1),
            "unit": "qps",
            "subscribers": PUSH_SUBSCRIBERS,
            "prop_p50_ms": round(poll_p50 * 1000, 1),
            "prop_p99_ms": round(poll_p99 * 1000, 1),
            "prop_samples": len(poll["samples"]),
        })
        emit(
            {
                "metric": "server_push_vs_poll_rpc_rate_stream",
                "value": round(stream_rate, 1),
                "unit": "qps",
                "subscribers": stream["established"],
                "rpc_reduction": reduction,
                "rpc_reduction_measured": round(measured, 1),
                "rpc_reduction_amortized_bound": round(amortized, 1),
                "steady_push_bytes_per_tick": (
                    stream["steady_push_bytes_per_tick"]
                ),
                "churn_push_bytes_per_tick": (
                    stream["churn_push_bytes_per_tick"]
                ),
                "prop_p50_ms": round(stream_p50 * 1000, 1),
                "prop_p99_ms": round(stream_p99 * 1000, 1),
                "prop_speedup_p50": speedup,
                "prop_samples": len(stream["samples"]),
                "slo": verdicts,
            },
            artifact_extra={
                "poll": {k: v for k, v in poll.items()
                         if k != "samples"},
                "stream": {k: v for k, v in stream.items()
                           if k != "samples"},
            },
        )

    asyncio.run(run())


# server_stream_fanout_scaling: the sharded fan-out engine (ISSUE 12).
# Subscriber tiers with CONSTANT subscribers-per-resource (the churn
# always touches the same number of affected subscribers), so fan-out
# wall time per tick measures cost in TOTAL subscribers — the sublinear
# claim. 100k+ live streams ride the direct registry surface (the
# fanout is the thing measured; establishment transport is the storm
# leg's job).
FANOUT_TIERS = (1_000, 10_000, 100_000)
FANOUT_SUBS_PER_RESOURCE = 50
FANOUT_CHURN_RESOURCES = 4
FANOUT_CHURN_TICKS = 6
FANOUT_QUIET_TICKS = 3
FANOUT_TIER_BUDGET_SECONDS = 120.0
FANOUT_STORM_SECONDS = 30.0
FANOUT_SHARDS = 4


def bench_server_stream_fanout_scaling() -> None:
    """Fan-out wall time per tick across subscriber tiers, quiet-tick
    cost, grant-propagation, and the storm driver's held-stream count.

    Per tier: a native-store batch server with a 4-shard stream
    registry holds N direct WatchCapacity subscriptions (one resource
    each, N/50 resources so churn always affects ~200 subscribers),
    then FANOUT_CHURN_TICKS ticks each churn 4 resources — the
    device matcher extracts the (subscriber, row) pairs and only those
    decide+serialize. The emitted value is the measured log-log
    exponent of mean churn-tick fan-out wall time vs subscriber count:
    < 1.0 is the sublinearity SLO floor (flat is the design point —
    affected subscribers are constant by construction). Quiet ticks
    (nothing changed, nothing due) are measured separately and must
    stay subscriber-count-independent; grant propagation is the full
    tick wall (the push is enqueued inside the tick edge), p99 held
    under one tick interval. A tier that cannot establish within its
    budget degrades the row to the achieved tiers (diagnostic-not-row
    below two tiers — no scaling claim from one point). The storm leg
    re-establishes the largest achieved tier's stream count over real
    loopback gRPC with the multiplexed driver (--streams-per-worker)
    and reports the streams actually held."""
    import asyncio

    from doorman_tpu import native as _native
    from doorman_tpu.algorithms import Request as _Request
    from doorman_tpu.proto import doorman_stream_pb2 as _spb
    from doorman_tpu.server.config import parse_yaml_config
    from doorman_tpu.server.election import TrivialElection
    from doorman_tpu.server.server import CapacityServer

    if not _native.native_available():
        diagnostic({
            "diagnostic": "stream_fanout_requires_native",
            "note": (
                "the fan-out scaling row measures the delta-tracked "
                "native path; the python store re-decides every "
                "subscription per tick (check_all) by design"
            ),
        })
        return

    # Capacity 600 vs 50 subscribers wanting 10: the churner's 500-want
    # flip moves the row between under- and oversubscription, so every
    # churned row's subscribers observe a real grant change (10 <-> 6).
    config = parse_yaml_config(
        "resources:\n"
        '- identifier_glob: "*"\n'
        "  capacity: 600\n"
        "  safe_capacity: 1\n"
        "  algorithm: {kind: PROPORTIONAL_SHARE, lease_length: 7200,\n"
        "              refresh_interval: 3600,\n"
        "              learning_mode_duration: 0}\n"
    )

    async def make_server():
        server = CapacityServer(
            "fanout-bench", TrivialElection(), mode="batch",
            tick_interval=1.0, minimum_refresh_interval=0.0,
            native_store=True, stream_push=True,
            stream_shards=FANOUT_SHARDS, flightrec_capacity=0,
        )
        port = await server.start(0, host="127.0.0.1")
        await server.load_config(config)
        await asyncio.sleep(0)  # election callbacks land
        server.current_master = f"127.0.0.1:{port}"
        for task in server._tasks:
            task.cancel()
        server._tasks.clear()
        return server, f"127.0.0.1:{port}"

    def drain(subs):
        n = 0
        for sub in subs:
            while not sub.queue.empty():
                sub.queue.get_nowait()
                n += 1
        return n

    async def run_tier(n_subs: int) -> dict:
        server, _addr = await make_server()
        try:
            registry = server._streams
            n_resources = max(n_subs // FANOUT_SUBS_PER_RESOURCE, 1)
            by_resource: dict = {}
            t_start = time.monotonic()
            for i in range(n_subs):
                rid = f"r{i % n_resources}"
                req = _spb.WatchCapacityRequest(client_id=f"s{i}")
                rr = req.resource.add()
                rr.resource_id = rid
                rr.wants = 10.0
                sub = registry.subscribe(req)
                server._stream_match_add(sub)
                by_resource.setdefault(rid, []).append(sub)
                if (
                    i % 4096 == 0
                    and time.monotonic() - t_start
                    > FANOUT_TIER_BUDGET_SECONDS
                ):
                    raise TimeoutError(
                        f"established {i} of {n_subs} within budget"
                    )
            establish_s = time.monotonic() - t_start
            all_subs = [s for subs in by_resource.values() for s in subs]
            drain(all_subs)
            for _ in range(3):  # warm: deliveries converge
                await server.tick_once()
                drain(all_subs)
            registry.take_tick_stats()
            churn_fanout_s, tick_walls, pushed = [], [], 0
            for t in range(FANOUT_CHURN_TICKS):
                churned = [
                    f"r{(t * FANOUT_CHURN_RESOURCES + j) % n_resources}"
                    for j in range(FANOUT_CHURN_RESOURCES)
                ]
                wants = 500.0 if t % 2 == 0 else 1.0
                for rid in churned:
                    server._decide(
                        rid, _Request("churner", 0.0, wants, 1,
                                      priority=0)
                    )
                t0 = time.monotonic()
                await server.tick_once()
                tick_walls.append(time.monotonic() - t0)
                churn_fanout_s.append(registry.last_fanout_seconds)
                # Drain everything: grants land one pipelined tick
                # after their solve, so a row's push can trail its
                # churn tick (harness cost, outside the fanout lap).
                pushed += drain(all_subs)
            # Settle: the last churn's delivery (and its pushes) land
            # before the quiet window, so quiet ticks are QUIET.
            for _ in range(2):
                await server.tick_once()
                pushed += drain(all_subs)
            churn_stats = registry.take_tick_stats()
            quiet_fanout_s = []
            for _ in range(FANOUT_QUIET_TICKS):
                await server.tick_once()
                quiet_fanout_s.append(registry.last_fanout_seconds)
            stats = registry.take_tick_stats()
            return {
                "matched_pairs": churn_stats["matched_pairs"],
                "churn_subs_walked": churn_stats["subs_walked"],
                "subscribers": n_subs,
                "resources": n_resources,
                "establish_s": round(establish_s, 3),
                "churn_fanout_ms_mean": round(
                    1000.0 * sum(churn_fanout_s) / len(churn_fanout_s),
                    4,
                ),
                "quiet_fanout_ms_mean": round(
                    1000.0 * sum(quiet_fanout_s) / len(quiet_fanout_s),
                    4,
                ),
                "tick_wall_ms_p99": round(
                    1000.0 * sorted(tick_walls)[-1], 3
                ),
                "pushed_messages": pushed,
                "quiet_subs_walked": stats["subs_walked"],
            }
        finally:
            await server.stop()

    async def run_storm_leg(target: int) -> dict:
        from doorman_tpu.loadtest.storm import run_storm

        server, addr = await make_server()
        # The storm leg needs the real tick cadence for pushes.
        server._tasks.append(
            asyncio.get_running_loop().create_task(server._tick_loop())
        )
        try:
            workers = 32
            out = await run_storm(
                addr, "storm", workers=workers,
                duration=FANOUT_STORM_SECONDS, bands=(0,), wants=5.0,
                stream=True, seed=3,
                streams_per_worker=max(target // workers, 1),
                resource_spread=max(
                    target // FANOUT_SUBS_PER_RESOURCE, 1
                ),
            )
            return {
                "target": target,
                "held": out["ok"],
                "pushes": out["pushes"],
                "errors": out["errors"],
                "resets": out["resets"],
            }
        finally:
            await server.stop()

    async def run():
        import math

        from doorman_tpu.obs import slo as slo_mod

        tiers, failures = [], []
        for n in FANOUT_TIERS:
            try:
                tiers.append(await run_tier(n))
            except (TimeoutError, MemoryError) as exc:
                failures.append({"subscribers": n, "error": str(exc)})
                break
        if len(tiers) < 2:
            diagnostic({
                "diagnostic": "stream_fanout_unmeasured",
                "note": (
                    "fewer than two subscriber tiers completed; no "
                    "scaling claim from one point"
                ),
                "tiers": tiers,
                "failures": failures,
            })
            return
        xs = [math.log(t["subscribers"]) for t in tiers]
        ys = [
            math.log(max(t["churn_fanout_ms_mean"], 1e-4))
            for t in tiers
        ]
        n = len(xs)
        mx, my = sum(xs) / n, sum(ys) / n
        exponent = round(
            sum((x - mx) * (y - my) for x, y in zip(xs, ys))
            / sum((x - mx) ** 2 for x in xs),
            4,
        )
        prop_p99 = max(t["tick_wall_ms_p99"] for t in tiers)
        quiet_ratio = round(
            max(t["quiet_fanout_ms_mean"] for t in tiers)
            / max(min(t["quiet_fanout_ms_mean"] for t in tiers), 1e-4),
            2,
        )
        storm = await run_storm_leg(tiers[-1]["subscribers"])
        if storm["held"] < storm["target"] * 0.9:
            diagnostic({
                "diagnostic": "stream_storm_under_target",
                "note": (
                    f"storm driver held {storm['held']} of "
                    f"{storm['target']} streams within "
                    f"{FANOUT_STORM_SECONDS:.0f}s on this box"
                ),
                "storm": storm,
            })
        specs = [
            slo_mod.SloSpec(
                name="server_stream_fanout_scaling:sublinear",
                kind="max", target=1.0, unit="exponent",
                source={"type": "scalar", "key": "exponent"},
                description=(
                    "log-log slope of churn-tick fan-out wall time vs "
                    "subscriber count"
                ),
            ),
            slo_mod.SloSpec(
                name="server_stream_fanout_scaling:grant_propagation",
                kind="max", target=1000.0, unit="ms",
                source={"type": "scalar", "key": "prop_p99_ms"},
                description=(
                    "p99 tick wall (push enqueued inside the tick "
                    "edge) vs one tick interval"
                ),
            ),
        ]
        verdicts = slo_mod.SloEngine(specs).evaluate(slo_mod.SloInputs(
            scalars={"exponent": exponent, "prop_p99_ms": prop_p99}
        ))
        emit(
            {
                "metric": "server_stream_fanout_scaling",
                "value": exponent,
                "unit": "exponent",
                "stream_shards": FANOUT_SHARDS,
                "subscribers_max": tiers[-1]["subscribers"],
                "prop_p99_ms": prop_p99,
                "quiet_fanout_ms_max": max(
                    t["quiet_fanout_ms_mean"] for t in tiers
                ),
                "quiet_fanout_spread": quiet_ratio,
                "quiet_subs_walked": max(
                    t["quiet_subs_walked"] for t in tiers
                ),
                "storm_streams_held": storm["held"],
                "tiers": tiers,
                "slo": verdicts,
            },
            artifact_extra={"failures": failures, "storm": storm},
        )

    asyncio.run(run())


FRONTEND_WORKERS = 4
FRONTEND_RING_BYTES = 1 << 22
FRONTEND_EST_SECONDS = 5.0
FRONTEND_HOLD_SECONDS = 90.0
FRONTEND_HELD_TARGET = 1_000_000
FRONTEND_EST_FLOOR = 50_000.0  # establishments/s through the ramp
FRONTEND_PUSH_TICKS = 30
FRONTEND_TICK_INTERVAL = 1.0
FRONTEND_READY_SECONDS = 60.0


def bench_server_frontend() -> None:
    """Serving-plane pool rows: establishment storm through the
    SO_REUSEPORT worker pool, and the held-stream ceiling with
    client-observed push latency.

    Both rows drive the REAL pool (spawned listener workers over
    shared-memory rings, the Establish/Drop/Heartbeat control surface
    — nothing inline): `server_frontend_establishment_storm` pushes
    the multi-process storm driver's establishment burst through the
    pool's forwarded gate and reports merged establishments/sec
    (floor: 50k/s); `server_frontend_held_streams` parks a held-stream
    population across the workers, then churns a sentinel stream's
    resource across manual ticks and measures the client-observed push
    latency (tick edge -> WatchCapacity message through the ring and
    the holding worker), p99 held under one tick interval (floors: 1M
    streams held, push p99 <= 1 tick).

    Cores gate (the BENCH_r05 convention: a diagnostic, never a
    metric row): the pool's workers, the tick process, and the storm
    client processes only measure anything when they run CONCURRENTLY
    — a single-core box timeslices them and would record meaningless
    rates into the trajectory, so fewer than FRONTEND_WORKERS + 2
    cores degrades BOTH rows to `frontend_requires_cores`."""
    import asyncio
    import os
    import socket

    cores = os.cpu_count() or 1
    needed = FRONTEND_WORKERS + 2
    if cores < needed:
        diagnostic({
            "diagnostic": "frontend_requires_cores",
            "cpu_cores": cores,
            "cores_needed": needed,
            "rows": [
                "server_frontend_establishment_storm",
                "server_frontend_held_streams",
            ],
            "note": (
                f"the serving-plane rows need {FRONTEND_WORKERS} "
                "listener workers, the tick process, and the storm "
                f"clients running concurrently ({needed} cores); only "
                f"{cores} available — no metric row (remeasure on a "
                "multi-core box)"
            ),
        })
        return

    from doorman_tpu.algorithms import Request as _Request
    from doorman_tpu.loadtest.storm import percentile, run_storm_procs
    from doorman_tpu.obs import slo as slo_mod
    from doorman_tpu.proto import doorman_stream_pb2 as _spb
    from doorman_tpu.server.config import parse_yaml_config
    from doorman_tpu.server.election import TrivialElection
    from doorman_tpu.server.server import CapacityServer

    config = parse_yaml_config(
        "resources:\n"
        '- identifier_glob: "*"\n'
        "  capacity: 600\n"
        "  safe_capacity: 1\n"
        "  algorithm: {kind: PROPORTIONAL_SHARE, lease_length: 7200,\n"
        "              refresh_interval: 3600,\n"
        "              learning_mode_duration: 0}\n"
    )

    def _free_port() -> int:
        # The workers SO_REUSEPORT-bind the public port themselves;
        # the tick process only needs to pick a free one for them.
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def _held_total(pool) -> int:
        return sum(pool.control.status()["worker_held"].values())

    async def run():
        import grpc

        from doorman_tpu.proto.grpc_api import CapacityStub

        server = CapacityServer(
            "frontend-bench", TrivialElection(), mode="batch",
            tick_interval=FRONTEND_TICK_INTERVAL,
            minimum_refresh_interval=0.0, stream_push=True,
            stream_shards=FANOUT_SHARDS, flightrec_capacity=0,
        )
        pool = server.attach_frontend(
            FRONTEND_WORKERS, ring_bytes=FRONTEND_RING_BYTES,
            inline=False,
        )
        public = f"127.0.0.1:{_free_port()}"
        try:
            backend_port = await server.start(0, host="127.0.0.1")
            await server.load_config(config)
            await asyncio.sleep(0)  # election callbacks land
            server.current_master = public
            # Ticks are manual below (the push-latency lap times the
            # tick edge itself); the workers pump on their own clocks.
            for task in server._tasks:
                task.cancel()
            server._tasks.clear()
            await pool.start(public, f"127.0.0.1:{backend_port}")
            ready_deadline = time.monotonic() + FRONTEND_READY_SECONDS
            while time.monotonic() < ready_deadline:
                held = pool.control.status()["worker_held"]
                if len(held) == FRONTEND_WORKERS:
                    break
                await asyncio.sleep(0.2)
            else:
                diagnostic({
                    "diagnostic": "frontend_pool_not_ready",
                    "workers": FRONTEND_WORKERS,
                    "note": (
                        "spawned listener workers never heartbeat the "
                        "control surface within "
                        f"{FRONTEND_READY_SECONDS:.0f}s — no metric row"
                    ),
                })
                return

            loop = asyncio.get_running_loop()
            storm_procs = max(2, min(cores - FRONTEND_WORKERS - 1, 8))

            # -- leg 1: establishment storm -------------------------
            # Enough streams that establishing them saturates the
            # whole window at the floor rate: the merged ok/elapsed
            # IS the sustained establishment rate, not a burst tail.
            est_streams = int(FRONTEND_EST_FLOOR * FRONTEND_EST_SECONDS)
            est_workers = storm_procs * 16
            try:
                est = await loop.run_in_executor(None, lambda: (
                    run_storm_procs(
                        public, "storm", procs=storm_procs,
                        workers=est_workers,
                        duration=FRONTEND_EST_SECONDS, bands=(0,),
                        wants=5.0, stream=True, seed=7,
                        streams_per_worker=max(
                            est_streams // est_workers, 1
                        ),
                        resource_spread=max(
                            est_streams // FANOUT_SUBS_PER_RESOURCE, 1
                        ),
                    )
                ))
            except RuntimeError as exc:
                diagnostic({
                    "diagnostic": "frontend_storm_failed",
                    "leg": "establishment",
                    "error": str(exc),
                })
                return
            est_rate = est["ok"] / max(est["duration_s"], 1e-9)
            specs = [
                slo_mod.SloSpec(
                    name="server_frontend_establishment_storm:rate",
                    kind="min", target=FRONTEND_EST_FLOOR,
                    unit="est_per_s",
                    source={"type": "scalar", "key": "est_rate"},
                    description=(
                        "sustained WatchCapacity establishments/sec "
                        "through the pool's forwarded admission gate "
                        "(ramp-batched, merged across storm processes)"
                    ),
                ),
            ]
            verdicts = slo_mod.SloEngine(specs).evaluate(
                slo_mod.SloInputs(scalars={"est_rate": est_rate})
            )
            emit(
                {
                    "metric": "server_frontend_establishment_storm",
                    "value": round(est_rate, 1),
                    "unit": "est_per_s",
                    "frontend_workers": FRONTEND_WORKERS,
                    "storm_procs": storm_procs,
                    "established": est["ok"],
                    "shed": est["shed"],
                    "errors": est["errors"],
                    "establish_p50_s": est["p50_s"],
                    "establish_p99_s": est["p99_s"],
                    "duration_s": est["duration_s"],
                    "slo": verdicts,
                },
                artifact_extra={"storm": est},
            )
            # The establishment population dropped at its deadline;
            # let the workers' Drop forwards settle before holding.
            settle = time.monotonic() + 10.0
            while time.monotonic() < settle and _held_total(pool) > 0:
                await asyncio.sleep(0.2)

            # -- leg 2: held streams + push latency -----------------
            held_kwargs = dict(
                procs=storm_procs, workers=storm_procs * 32,
                duration=FRONTEND_HOLD_SECONDS, bands=(0,), wants=5.0,
                stream=True, seed=11,
                streams_per_worker=max(
                    FRONTEND_HELD_TARGET // (storm_procs * 32), 1
                ),
                resource_spread=max(
                    FRONTEND_HELD_TARGET // FANOUT_SUBS_PER_RESOURCE, 1
                ),
            )
            hold_future = loop.run_in_executor(None, lambda: (
                run_storm_procs(public, "storm", **held_kwargs)
            ))
            held_max = 0
            try:
                # Track the held ceiling while the population ramps
                # (heartbeats lag by their 1s interval; the max over
                # the hold window is the honest ceiling).
                ramp_deadline = time.monotonic() + (
                    FRONTEND_HOLD_SECONDS / 2
                )
                while time.monotonic() < ramp_deadline:
                    held_max = max(held_max, _held_total(pool))
                    if held_max >= FRONTEND_HELD_TARGET:
                        break
                    await asyncio.sleep(0.5)

                # Sentinel stream through the pool: its resource is
                # churned each manual tick; the lap from the tick
                # edge to the sentinel's WatchCapacity message is the
                # client-observed push latency (publisher frame ->
                # ring -> holding worker's pump -> gRPC write).
                push_lat = []
                async with grpc.aio.insecure_channel(public) as chan:
                    stub = CapacityStub(chan)
                    wreq = _spb.WatchCapacityRequest(
                        client_id="bench-sentinel"
                    )
                    rr = wreq.resource.add()
                    rr.resource_id = "sentinel"
                    rr.wants = 10.0
                    rr.priority = 1
                    call = stub.WatchCapacity(wreq)
                    # Establishment snapshot first (not a push).
                    while True:
                        msg = await asyncio.wait_for(
                            call.read(), timeout=30.0
                        )
                        if msg is grpc.aio.EOF:
                            raise ConnectionResetError(
                                "sentinel stream ended at establish"
                            )
                        if msg.response:
                            break
                    for t in range(FRONTEND_PUSH_TICKS):
                        wants = 500.0 if t % 2 == 0 else 1.0
                        server._decide(
                            "sentinel",
                            _Request("churner", 0.0, wants, 1,
                                     priority=0),
                        )
                        t0 = time.monotonic()
                        await server.tick_once()
                        while True:
                            msg = await asyncio.wait_for(
                                call.read(),
                                timeout=10.0 * FRONTEND_TICK_INTERVAL,
                            )
                            if msg is grpc.aio.EOF:
                                raise ConnectionResetError(
                                    "sentinel stream reset mid-lap"
                                )
                            if msg.response:
                                break
                        push_lat.append(time.monotonic() - t0)
                        held_max = max(held_max, _held_total(pool))
                    call.cancel()
            except (TimeoutError, asyncio.TimeoutError,
                    ConnectionResetError, grpc.aio.AioRpcError) as exc:
                diagnostic({
                    "diagnostic": "frontend_push_lap_failed",
                    "held_max": held_max,
                    "error": f"{type(exc).__name__}: {exc}",
                    "note": (
                        "the sentinel push lap did not complete; no "
                        "held-streams metric row"
                    ),
                })
                return
            finally:
                try:
                    hold = await hold_future
                except RuntimeError as exc:
                    hold = {"error": str(exc)}
            push_lat.sort()
            push_p99_ms = 1000.0 * percentile(push_lat, 0.99)
            if held_max < FRONTEND_HELD_TARGET * 0.9:
                diagnostic({
                    "diagnostic": "frontend_held_under_target",
                    "note": (
                        f"the pool held {held_max} of "
                        f"{FRONTEND_HELD_TARGET} target streams within "
                        f"{FRONTEND_HOLD_SECONDS:.0f}s on this box"
                    ),
                    "held_max": held_max,
                })
            specs = [
                slo_mod.SloSpec(
                    name="server_frontend_held_streams:held",
                    kind="min", target=float(FRONTEND_HELD_TARGET),
                    unit="streams",
                    source={"type": "scalar", "key": "held_max"},
                    description=(
                        "WatchCapacity streams held across the "
                        "listener workers (control-surface heartbeat "
                        "ceiling over the hold window)"
                    ),
                ),
                slo_mod.SloSpec(
                    name="server_frontend_held_streams:push_p99",
                    kind="max",
                    target=1000.0 * FRONTEND_TICK_INTERVAL, unit="ms",
                    source={"type": "scalar", "key": "push_p99_ms"},
                    description=(
                        "client-observed push latency (tick edge -> "
                        "sentinel WatchCapacity message through the "
                        "ring and the holding worker) under the held "
                        "population, p99 vs one tick interval"
                    ),
                ),
            ]
            verdicts = slo_mod.SloEngine(specs).evaluate(
                slo_mod.SloInputs(scalars={
                    "held_max": float(held_max),
                    "push_p99_ms": push_p99_ms,
                })
            )
            emit(
                {
                    "metric": "server_frontend_held_streams",
                    "value": held_max,
                    "unit": "streams",
                    "frontend_workers": FRONTEND_WORKERS,
                    "held_target": FRONTEND_HELD_TARGET,
                    "push_p50_ms": round(
                        1000.0 * percentile(push_lat, 0.50), 3
                    ),
                    "push_p99_ms": round(push_p99_ms, 3),
                    "push_ticks": len(push_lat),
                    "storm_pushes": hold.get("pushes", 0),
                    "storm_resets": hold.get("resets", 0),
                    "storm_errors": hold.get("errors", 0),
                    "slo": verdicts,
                },
                artifact_extra={"storm": hold},
            )
        finally:
            await server.stop()

    asyncio.run(run())


def gate_pallas_kernels() -> None:
    """Real-TPU pallas regression gate: compile and run BOTH pallas
    kernels (dense lanes + banded priority water-fill) on the chip and
    hold them to BASELINE.md's f32 parity bound. CI runs them in
    interpret mode, which proves semantics but not Mosaic lowering —
    without this gate a lowering break ships silently. Runs before the
    timed benchmarks; any failure raises, so the driver records a
    non-zero rc (the red signal)."""
    import jax

    from doorman_tpu.algorithms.tick import oracle_row
    from doorman_tpu.solver.dense import DenseBatch
    from doorman_tpu.solver.pallas_dense import solve_dense_pallas
    from doorman_tpu.solver.priority import PriorityBatch, solve_priority

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(
            f"the pallas gate compiles for a TPU; platform is "
            f"{device.platform}"
        )

    bound = PALLAS_GATE_REL_BOUND
    rng = np.random.default_rng(3)
    R, K = 1024, 128

    # -- dense lanes vs the f64 numpy oracles --------------------------
    n = rng.integers(1, K, R)
    act = np.arange(K)[None, :] < n[:, None]
    wants = (rng.random((R, K)) * 1000 * act).astype(np.float32)
    has = (rng.random((R, K)) * 500 * act).astype(np.float32)
    sub = (rng.integers(1, 5, (R, K)) * act).astype(np.float32)
    cap = (rng.random(R) * 50_000 + 100).astype(np.float32)
    statc = (rng.random(R) * 100).astype(np.float32)
    kind = rng.choice(
        np.array([0, 1, 2, 3, 4], np.int32), R,
        p=[0.1, 0.1, 0.4, 0.2, 0.2],
    )
    put = lambda a: jax.device_put(a, device)
    batch = DenseBatch(
        wants=put(wants), has=put(has), subclients=put(sub),
        active=put(act), capacity=put(cap), algo_kind=put(kind),
        learning=put(np.zeros(R, bool)), static_capacity=put(statc),
    )
    gets = np.asarray(
        jax.device_get(jax.jit(solve_dense_pallas)(batch)), np.float64
    )
    dense_err = 0.0
    for r in range(R):  # every row: the oracle loop is cheap host numpy
        m = act[r]
        w = wants[r, m].astype(np.float64)
        c = float(cap[r])
        expected = oracle_row(
            int(kind[r]), c, float(statc[r]), w,
            has[r, m].astype(np.float64), sub[r, m].astype(np.float64),
        )
        scale = max(c, float(w.max()) if len(w) else 0.0, 1e-30)
        err = float(np.abs(gets[r, m] - expected).max()) / scale
        dense_err = max(dense_err, err)
        if err > bound:
            raise AssertionError(
                f"pallas_dense on-chip error {err:.3g} exceeds "
                f"{bound:g} (row {r}, kind {int(kind[r])})"
            )

    # -- banded priority water-fill: pallas vs XLA, on chip, with
    #    group caps engaged (the bisection evaluates the kernel) -------
    band = (rng.integers(0, 4, (R, K)) * act).astype(np.int32)
    group = rng.choice(np.array([-1, 0, 1], np.int32), R)
    group_cap = np.asarray(
        [cap[group == 0].sum() * 0.5, cap[group == 1].sum() * 0.25],
        np.float32,
    )
    pbatch = PriorityBatch(
        wants=put(wants), weights=put(np.maximum(sub, act)),
        band=put(band), active=put(act), capacity=put(cap),
        group=put(group), group_cap=put(group_cap),
    )
    g_xla = np.asarray(
        jax.device_get(solve_priority(pbatch, num_bands=4)), np.float64
    )
    g_pal = np.asarray(
        jax.device_get(
            solve_priority(pbatch, num_bands=4, use_pallas=True)
        ),
        np.float64,
    )
    scale = np.maximum(cap.astype(np.float64), 1e-30)[:, None]
    prio_err = float((np.abs(g_pal - g_xla) / scale).max())
    if prio_err > bound:
        raise AssertionError(
            f"pallas_priority on-chip divergence {prio_err:.3g} vs the "
            f"XLA solve exceeds {bound:g}"
        )
    emit(
        {
            "metric": "pallas_tpu_gate",
            "value": 1,
            "unit": "ok",
            "dense_rel_err": float(f"{dense_err:.3g}"),
            "priority_rel_err": float(f"{prio_err:.3g}"),
            "bound": bound,
        }
    )


# BASELINE.md parity ladder bound: ONE constant, shared with the
# off-chip pin in tests/test_f32_parity.py via algorithms.tick.
from doorman_tpu.algorithms.tick import (
    F32_PARITY_REL_BOUND as PALLAS_GATE_REL_BOUND,
)

# The server tick has its own target: the BASELINE.md north star is
# <100 ms per recompute of the full 1M-lease table, measured here
# end-to-end through the store of record.
SERVER_TICK_TARGET_MS = 100.0
# Scoped-solve churn tiers (fraction of resources whose demand changes
# per tick); override with --churn. The headline tier is the 1% steady
# state; 100% pins the worst case against the unscoped full solve.
SCOPED_CHURN_TIERS = (0.001, 0.01, 0.1, 1.0)
SCOPED_HEADLINE_CHURN = 0.01
SERVER_ROTATE_TICKS = 16  # grant delivery rides the 16s refresh cadence
PIPELINE_DEPTH_SERVER = 4
SERVER_WARMUP = 6
# >= 100 measured ticks so the reported p90/p99 mean something (the
# round-4 verdict asked for percentiles over a long window on record).
TICKS_SERVER = 100
TICKS_WIDE = 40
# --mesh-devices: how many devices the mesh bench shards over (0 = all
# visible). Fewer available than requested (or than 2) => diagnostic.
MESH_BENCH_DEVICES = 0


# ----------------------------------------------------------------------
# server_tick_federated_roots: the federated root tier (POP-sharded
# multi-master roots, doorman_tpu/federation). N shards each hold a
# FULL per-shard 1M-lease table on their own device and tick
# CONCURRENTLY; the row reports aggregate leases/sec across the tier,
# the scaling vs one root, and the per-shard tick p50 — which must stay
# under the 100 ms north star while the total lease count scales ~N x.
# ----------------------------------------------------------------------

FED_SHARD_COUNTS = (2, 4)
FED_WARMUP = 3
FED_TICKS = 12
FED_PIPELINE_DEPTH = 2
# scaling_vs_1root SLO floors: concurrency loss must stay under ~25%
# at 4 shards (the ISSUE-10 acceptance bar) and ~25% at 2.
FED_SCALING_FLOOR = {2: 1.5, 4: 3.0}


def bench_server_tick_federated_roots() -> None:
    """Aggregate tick throughput of N federated root shards.

    Each shard is the bench_server_tick workload (native C++ engine as
    the store of record, 5% demand churn per tick, device-resident
    solve + rotation delivery) built on ITS OWN device of the
    forced-multi-device inventory, exactly as `--shard i/N` deployments
    run one CapacityServer per shard. A thread pool ticks all shards in
    lockstep rounds; the aggregate rate is (N x per-shard leases) /
    round wall, and per-shard tick times ride each shard's own clock.
    Fewer visible devices than a shard count => a diagnostic for that
    count, never a metric row (the <2-shards convention). No straddle
    beat here: the reconciler costs one summary + template write per
    straddling resource per tick and is benched by its own tests — this
    row isolates what federation buys on the solve path."""
    import concurrent.futures
    import os

    import jax

    from doorman_tpu import native
    from doorman_tpu.core.resource import Resource
    from doorman_tpu.obs import slo as slo_mod
    from doorman_tpu.proto import doorman_pb2 as pb
    from doorman_tpu.solver.resident import ResidentDenseSolver

    devices = jax.devices()
    dtype = np.float32

    # Smoke knob for local validation runs only; the recorded rounds
    # use the full per-shard 1M-lease shape.
    R = int(
        os.environ.get("DOORMAN_BENCH_FED_RESOURCES", NUM_RESOURCES)
    )
    C = CLIENTS_PER_RESOURCE
    churn_resources = max(R // 20, 1)
    n_ticks = FED_WARMUP + FED_TICKS

    def build_shard(shard: int, device):
        """One root shard: engine + 1M leases + resident solver on its
        own device, plus its pre-generated churn stream (per-shard
        seed: shards must not churn in lockstep rows)."""
        rng = np.random.default_rng(1100 + shard)
        engine = native.StoreEngine()
        kinds = rng.choice(
            np.array(
                [
                    pb.Algorithm.NO_ALGORITHM,
                    pb.Algorithm.STATIC,
                    pb.Algorithm.PROPORTIONAL_SHARE,
                    pb.Algorithm.FAIR_SHARE,
                ],
                dtype=np.int64,
            ),
            size=R,
            p=[0.05, 0.05, 0.65, 0.25],
        )
        capacity = rng.integers(100, 100_000, R).astype(np.float64)
        resources = []
        rids = np.empty(R * C, np.int32)
        for r in range(R):
            tpl = pb.ResourceTemplate(
                identifier_glob=f"s{shard}-res{r}",
                capacity=float(capacity[r]),
                algorithm=pb.Algorithm(
                    kind=int(kinds[r]), lease_length=600,
                    refresh_interval=16,
                ),
            )
            res = Resource(
                f"s{shard}-res{r}", tpl, store_factory=engine.store
            )
            resources.append(res)
            rids[r * C : (r + 1) * C] = res.store._rid
        cids = np.array(
            [
                engine.client_handle(f"s{shard}-c{i}")
                for i in range(R * C)
            ],
            np.int64,
        )
        wants = rng.integers(0, 100, R * C).astype(np.float64)
        now = time.time()
        engine.bulk_assign(
            rids, cids,
            np.full(R * C, now + 600.0),
            np.full(R * C, 16.0),
            np.zeros(R * C),
            wants,
            np.ones(R * C, np.int32),
        )
        solver = ResidentDenseSolver(
            engine, dtype=dtype, device=device,
            rotate_ticks=SERVER_ROTATE_TICKS,
        )
        churn_rows = [
            rng.choice(R, churn_resources, replace=False)
            for _ in range(n_ticks)
        ]
        churn_wants = [
            rng.integers(0, 100, churn_resources * C).astype(np.float64)
            for _ in range(n_ticks)
        ]
        return {
            "engine": engine,
            "resources": resources,
            "solver": solver,
            "rids": rids,
            "cids": cids,
            "churn_rows": churn_rows,
            "churn_wants": churn_wants,
            "handles": [],
            "tick_ms": [],
        }

    def step_shard(shard_state, t: int) -> None:
        """One shard's tick for round t: apply the churn (the RPC
        handlers' store writes), dispatch, collect the oldest in-flight
        handle at depth — measured on the shard's own clock."""
        t0 = time.perf_counter()
        sel = shard_state["churn_rows"][t]
        edge = (sel[:, None] * C + np.arange(C)).ravel()
        shard_state["engine"].bulk_refresh(
            shard_state["rids"][edge],
            shard_state["cids"][edge],
            np.full(len(edge), time.time() + 600.0),
            np.full(len(edge), 16.0),
            shard_state["churn_wants"][t],
        )
        solver = shard_state["solver"]
        shard_state["handles"].append(
            solver.dispatch(shard_state["resources"])
        )
        if len(shard_state["handles"]) >= FED_PIPELINE_DEPTH:
            solver.collect(shard_state["handles"].pop(0))
        shard_state["tick_ms"].append(
            (time.perf_counter() - t0) * 1000.0
        )

    def measure(n_shards: int):
        """Round-lockstep concurrent ticks of n_shards shards; returns
        (round_ms over the measured window, per-shard tick_ms flat)."""
        shards = [
            build_shard(i, devices[i % len(devices)])
            for i in range(n_shards)
        ]
        round_ms = []
        with concurrent.futures.ThreadPoolExecutor(n_shards) as pool:
            for t in range(n_ticks):
                t0 = time.perf_counter()
                futures = [
                    pool.submit(step_shard, s, t) for s in shards
                ]
                for f in futures:
                    f.result()
                wall = (time.perf_counter() - t0) * 1000.0
                if t >= FED_WARMUP:
                    round_ms.append(wall)
        for s in shards:
            for h in s["handles"]:
                s["solver"].collect(h)
        per_shard = [
            ms for s in shards for ms in s["tick_ms"][FED_WARMUP:]
        ]
        return round_ms, per_shard

    # Single-root baseline: the same workload, one shard, same
    # round-lockstep harness (comparability: identical measurement
    # overhead).
    base_round_ms, base_ticks = measure(1)
    base_med = float(np.median(base_round_ms))
    base_rate = (R * C) / (base_med / 1e3)
    emit(
        {
            "metric": "server_tick_federated_roots_1root_leases_per_s",
            "value": round(base_rate, 0),
            "unit": "leases_per_s",
            "n_shards": 1,
            "leases_per_shard": R * C,
            "round_p50_ms": round(base_med, 3),
            "per_shard_tick_p50_ms": round(
                float(np.percentile(base_ticks, 50)), 3
            ),
            "selection": f"median_of_{FED_TICKS}",
        }
    )

    for n in FED_SHARD_COUNTS:
        # N shards are "available" only when N devices can tick them
        # CONCURRENTLY; fewer would timeslice the shards and record a
        # meaningless ~1.0x scaling. The convention: a diagnostic,
        # never a metric row.
        if len(devices) < n:
            diagnostic(
                {
                    "diagnostic": "federated_shards_unavailable",
                    "n_shards": n,
                    "devices": len(devices),
                    "note": (
                        f"{n}-shard federated bench needs {n} devices; "
                        f"only {len(devices)} available — no metric row"
                    ),
                }
            )
            continue
        round_ms, per_shard = measure(n)
        med = float(np.median(round_ms))
        agg_rate = (n * R * C) / (med / 1e3)
        scaling = agg_rate / base_rate
        p50 = float(np.percentile(per_shard, 50))
        p90 = float(np.percentile(per_shard, 90))
        specs = [
            slo_mod.SloSpec(
                f"server_tick_federated_roots_n{n}:per_shard_tick_p50",
                "max", SERVER_TICK_TARGET_MS,
                {"type": "scalar", "key": "tick_p50_ms"}, unit="ms",
                description=(
                    "per-shard tick p50 under concurrent N-shard load "
                    "stays inside the north-star tick budget"
                ),
            ),
            slo_mod.SloSpec(
                f"server_tick_federated_roots_n{n}:scaling_vs_1root",
                "min", FED_SCALING_FLOOR[n],
                {"type": "scalar", "key": "scaling"}, unit="x",
                description=(
                    "aggregate leases/sec across the shard tier vs the "
                    "single root (POP split + concurrent ticks)"
                ),
            ),
        ]
        verdicts = slo_mod.SloEngine(specs).evaluate(
            slo_mod.SloInputs(
                scalars={"tick_p50_ms": p50, "scaling": scaling}
            )
        )
        emit(
            {
                "metric": (
                    f"server_tick_federated_roots_n{n}_agg_leases_per_s"
                ),
                "value": round(agg_rate, 0),
                "unit": "leases_per_s",
                "n_shards": n,
                "leases_per_shard": R * C,
                "leases_total": n * R * C,
                "round_p50_ms": round(med, 3),
                "per_shard_tick_p50_ms": round(p50, 3),
                "per_shard_tick_p90_ms": round(p90, 3),
                "scaling_vs_1root": round(scaling, 3),
                "pipeline_depth": FED_PIPELINE_DEPTH,
                "rotate_ticks": SERVER_ROTATE_TICKS,
                "selection": f"median_of_{FED_TICKS}",
                "slo": verdicts,
            }
        )


def require_tpu() -> None:
    """Fail before measuring unless JAX's first device is a TPU: every
    number this bench prints is a chip measurement or nothing. Records
    the device identity for the artifact."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"bench.py measures on a TPU; JAX found {len(devices)} "
            f"{devices[0].platform} device(s)"
        )
    _PLATFORM_INFO.update(
        platform=devices[0].platform,
        device=str(devices[0]),
        device_kind=devices[0].device_kind,
        device_count=len(devices),
    )


def bench_workload_scenarios() -> None:
    """Closed-loop workload scenarios as standing bench rows: each
    named scenario (doorman_tpu/workload) runs at its default scale on
    the virtual clock and emits one row carrying its SLO verdict list
    — so an admission, allocation, or election regression that moves a
    scenario gate shows in the same artifact as the device rows, with
    delta_vs_prev vs the prior BENCH round per verdict. No device
    work; the run is seeded and virtual-clocked, so the row's
    log_sha256 is a replay pin, not a measurement."""
    from doorman_tpu.workload.scenarios import run_scenario

    names = (
        "diurnal", "flash_crowd", "rolling_deploy", "multi_region",
        "elastic_preempt", "flash_crowd_predictive",
        "diurnal_streaming_pooled", "reshard_diurnal",
    )
    for name in names:
        try:
            v = run_scenario(name, scale=1.0, seed=0)
        except Exception as e:
            diagnostic({
                "diagnostic": "workload_scenario_failed",
                "scenario": name, "error": repr(e),
            })
            continue
        verdicts = v["slo"]["verdicts"]
        for verdict in verdicts:
            # Let the bench's repo-rooted trajectory comparator supply
            # the cross-round delta (the harness's in-run one has no
            # prior artifact to diff against).
            verdict.pop("delta_vs_prev", None)
        emit(
            {
                "metric": f"workload_{name}",
                "value": round(
                    float(v["summary"].get("top_band_satisfaction", 0.0)),
                    6,
                ),
                "unit": "top_band_satisfaction",
                "ok": v["ok"],
                "scenario": name,
                "ticks": v["ticks"],
                "log_sha256": v["log_sha256"],
                "slo": verdicts,
            },
            artifact_extra={"summary": v["summary"]},
        )


# workload_population_scaling: the array-backed vector population
# engine (ISSUE 19). Resident-population tiers with CONSTANT due
# refreshes per tick (refresh_spread scales with the tier), so the
# per-tick driver wall measures cost in TOTAL resident clients — the
# parked-rows-cost-nothing claim. The SLO floor is the log-log
# exponent < 0.3 (obs.slo.population_scaling_verdict).
POPSCALE_TIERS = (1_000, 10_000, 100_000, 1_000_000)
POPSCALE_DUE_PER_TICK = 500
POPSCALE_TICKS = 12
POPSCALE_WARM_TICKS = 2
POPSCALE_TIER_BUDGET_SECONDS = 300.0


def bench_workload_population_scaling() -> None:
    """Per-tick vector-population driver wall across resident-client
    tiers (1k -> 1M), constant due-set per tick.

    Per tier: a single-server workload spec parks N clients as compact
    base_population rows on the vector engine with refresh_spread =
    N / 500, so every tick refreshes ~500 due rows through the grouped
    decide seam while the resident arrays grow three orders of
    magnitude. No admission, no RTT model, leases sized past a full
    wheel lap — the measured wall is the driver's tick pass alone
    (population.step_refresh), warm ticks excluded. The emitted value
    is the log-log exponent of mean per-tick driver wall vs resident
    population; < 0.3 is the sublinearity SLO floor (flat is the
    design point — the due set is constant by construction). A tier
    that cannot finish inside its budget degrades the row to the
    achieved tiers (diagnostic-not-row below two tiers)."""
    import asyncio

    from doorman_tpu import native as _native
    from doorman_tpu.obs import slo as slo_mod
    from doorman_tpu.workload.harness import WorkloadRunner
    from doorman_tpu.workload.spec import WorkloadSpec

    def tier_spec(n: int) -> WorkloadSpec:
        spread = max(1, n // POPSCALE_DUE_PER_TICK)
        return WorkloadSpec.make(
            f"popscale_{n}", POPSCALE_TICKS, seed=0,
            capacity=float(n),
            lease_length=4.0 * max(spread, POPSCALE_TICKS),
            population_engine="vector", refresh_spread=spread,
            native_store=True,
            base_population=[[n, 0, 1.0]],
        )

    async def run_tier(n: int) -> dict:
        runner = WorkloadRunner(tier_spec(n))
        t0 = time.monotonic()
        verdict = await asyncio.wait_for(
            runner.run(), POPSCALE_TIER_BUDGET_SECONDS
        )
        wall = time.monotonic() - t0
        engine = runner._vector
        walls = engine.step_walls[POPSCALE_WARM_TICKS:]
        return {
            "population": n,
            "refresh_spread": tier_spec(n).refresh_spread,
            "driver_tick_ms_mean": round(
                1000.0 * sum(walls) / len(walls), 4
            ),
            "driver_tick_ms_max": round(1000.0 * max(walls), 4),
            "fast_rows": engine.fast_rows_total,
            "seq_rows": engine.seq_rows_total,
            "refresh_ok_ratio": float(
                verdict["summary"].get("refresh_ok_ratio", 0.0)
            ),
            "run_wall_s": round(wall, 3),
        }

    async def run():
        import math

        tiers, failures = [], []
        for n in POPSCALE_TIERS:
            try:
                tiers.append(await run_tier(n))
            except (asyncio.TimeoutError, MemoryError) as exc:
                failures.append({
                    "population": n,
                    "error": f"{type(exc).__name__}: {exc}",
                })
                break
        if len(tiers) < 2:
            diagnostic({
                "diagnostic": "population_scaling_unmeasured",
                "note": (
                    "fewer than two population tiers completed; no "
                    "scaling claim from one point"
                ),
                "tiers": tiers,
                "failures": failures,
            })
            return
        xs = [math.log(t["population"]) for t in tiers]
        ys = [
            math.log(max(t["driver_tick_ms_mean"], 1e-4))
            for t in tiers
        ]
        k = len(xs)
        mx, my = sum(xs) / k, sum(ys) / k
        exponent = round(
            sum((x - mx) * (y - my) for x, y in zip(xs, ys))
            / sum((x - mx) ** 2 for x in xs),
            4,
        )
        verdict = slo_mod.population_scaling_verdict(exponent)
        emit(
            {
                "metric": "workload_population_scaling",
                "value": exponent,
                "unit": "exponent",
                "population_max": tiers[-1]["population"],
                "due_per_tick": POPSCALE_DUE_PER_TICK,
                "native_store": _native.native_available(),
                "driver_tick_ms_at_max": tiers[-1][
                    "driver_tick_ms_mean"
                ],
                "tiers": tiers,
                "slo": [verdict],
            },
            artifact_extra={"failures": failures},
        )

    asyncio.run(run())


def _preseed_artifact() -> None:
    """Load the previous doc/bench_last.json rows so an --only run's
    artifact keeps the stages it did not re-measure."""
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "doc",
        "bench_last.json",
    )
    try:
        with open(path) as f:
            prior = json.load(f)
    except Exception:
        return
    _PRESEEDED.extend(
        row for row in prior.get("results", []) if isinstance(row, dict)
    )


if __name__ == "__main__":
    import argparse

    from doorman_tpu.obs import trace as _trace_mod
    from doorman_tpu.utils.compile_cache import place_compile_cache

    _ap = argparse.ArgumentParser(description="doorman-tpu benchmarks")
    _ap.add_argument(
        "--trace", default="",
        help="enable the span tracer for the run and write a Chrome "
             "trace (Perfetto-loadable) of the server-tick benches' "
             "per-phase spans to this path",
    )
    _ap.add_argument(
        "--jax-trace", default="",
        help="capture a device-side jax.profiler trace of the headline "
             "measured solve into this directory (xprof/tensorboard)",
    )
    _ap.add_argument(
        "--churn", default="",
        help="comma-separated churn fractions for the scoped "
             "server-tick tiers (e.g. '0.001,0.01,0.1,1.0'; default "
             "the standing tier set). The 1%% tier — added if missing "
             "— is the headline scoped row",
    )
    _ap.add_argument(
        "--mesh-devices", type=int, default=0,
        help="devices for the mesh-sharded wide bench (0 = all "
             "visible; a diagnostic is emitted when fewer than "
             "max(requested, 2) are available)",
    )
    _STAGES = {
        "solver": main,
        "tick_wide": bench_server_tick_wide,
        "tick_wide_mesh": bench_server_tick_wide_mesh,
        "rpc_storm": bench_server_rpc_storm,
        "push_vs_poll": bench_server_push_vs_poll,
        "stream_fanout": bench_server_stream_fanout_scaling,
        "frontend": bench_server_frontend,
        "federated_roots": bench_server_tick_federated_roots,
        "workload": bench_workload_scenarios,
        "population_scaling": bench_workload_population_scaling,
        "server_tick": bench_server_tick,
    }
    _ap.add_argument(
        "--only", default="",
        help="comma-separated stage subset to run instead of the full "
             f"sequence (stages: {','.join(_STAGES)}). The artifact "
             "pre-seeds from the existing doc/bench_last.json, so "
             "rows from stages not re-run carry forward",
    )
    _args = _ap.parse_args()
    if _args.churn:
        _tiers = sorted(
            {float(x) for x in _args.churn.split(",") if x.strip()}
            | {SCOPED_HEADLINE_CHURN}
        )
        if any(not (0.0 < f <= 1.0) for f in _tiers):
            _ap.error("--churn fractions must be in (0, 1]")
        SCOPED_CHURN_TIERS = tuple(_tiers)
    MESH_BENCH_DEVICES = max(_args.mesh_devices, 0)
    _only = [s.strip() for s in _args.only.split(",") if s.strip()]
    _unknown = [s for s in _only if s not in _STAGES]
    if _unknown:
        _ap.error(f"unknown --only stages: {','.join(_unknown)}")
    if _only:
        # Before anything emits: the preflight/gate rows below already
        # rewrite the artifact, which would clobber what we carry over.
        _preseed_artifact()
    if _args.trace:
        _trace_mod.default_tracer().enable()
    place_compile_cache()
    require_tpu()
    gate_pallas_kernels()
    try:
        if _only:
            for _stage in _only:
                if _stage == "solver":
                    with _trace_mod.jax_capture(_args.jax_trace or None):
                        main()
                else:
                    _STAGES[_stage]()
        else:
            # Opt-in device-side timeline around the measured solve.
            with _trace_mod.jax_capture(_args.jax_trace or None):
                main()
            bench_server_tick_wide()
            # After the 1-device wide bench, so scaling_vs_1device can
            # read its median from this run's emitted results.
            bench_server_tick_wide_mesh()
            # RPC front-end under storm (no device work; rides along so
            # admission regressions show in the same artifact).
            bench_server_rpc_storm()
            # Streaming lease push vs the polling population (no device
            # work): steady-state RPC reduction + grant propagation.
            bench_server_push_vs_poll()
            # Sharded fan-out engine: fan-out wall time vs subscriber
            # count (sublinearity SLO floor), quiet-tick independence,
            # and the multiplexed storm driver's held-stream count.
            bench_server_stream_fanout_scaling()
            # Serving-plane pool: establishment storm + held-stream
            # ceiling through the real SO_REUSEPORT worker pool
            # (cores-gated — a diagnostic on single-core boxes).
            bench_server_frontend()
            # Federated root tier: N shards ticking concurrently on
            # their own devices — aggregate leases/sec + scaling_vs_1root.
            bench_server_tick_federated_roots()
            # Closed-loop workload scenarios: SLO-gated verdict rows
            # (no device work; replay-pinned by log_sha256).
            bench_workload_scenarios()
            # Vector population engine: per-tick driver wall vs
            # resident population (sublinearity SLO floor < 0.3).
            bench_workload_population_scaling()
            # The narrow server tick stays LAST: the driver parses the
            # final JSON line as the round's headline metric.
            bench_server_tick()
    finally:
        # A crash mid-sequence still flushes everything emitted so far
        # (emit() also writes incrementally; this is the completeness
        # marker — complete=True only when the whole sequence ran).
        import sys as _sys

        write_artifact(complete=_sys.exc_info()[0] is None)
        if _args.trace:
            try:
                with open(_args.trace, "w") as _f:
                    _f.write(_trace_mod.default_tracer().chrome_json())
                print(f"wrote Chrome trace to {_args.trace}",
                      file=_sys.stderr)
            except Exception:
                pass  # trace trouble must never mask the bench outcome
