"""BatchSolver: the per-tick driver around `solve_tick`.

Owns the snapshot/solve/write-back cycle over a collection of Resources:

    snapshot: lease stores -> EdgeBatch/ResourceBatch   (host, numpy)
    solve:    one jitted XLA executable over all edges  (device)
    write-back: grants -> store.assign per edge          (host)

Grant write-back stamps fresh expiries with each resource's configured
lease length, so a tick is equivalent to every client refreshing at once —
the batch recast of the reference's refresh cadence.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Iterable, List

import jax
import jax.numpy as jnp
import numpy as np

from dataclasses import dataclass

from doorman_tpu.algorithms.kinds import AlgoKind
from doorman_tpu.solver.lanes import ITERATIVE_KINDS
from doorman_tpu.core.resource import Resource, algo_kind_for, static_param
from doorman_tpu.obs.phases import PhaseRecorder
from doorman_tpu.core.snapshot import (
    ResourceSpec,
    Snapshot,
    _bucket,
    pack_edge_arrays,
    pack_snapshot,
)
from doorman_tpu.solver.kernels import solve_tick_jit
from doorman_tpu.utils.transfer import chunked_device_get

# Engine-packed ticks use the dense [R, K] layout up to this bucket
# width; a resource with more clients than this drops the whole tick to
# the edge-list executable (correct everywhere, slower on TPU).
DENSE_MAX_K = 4096


def _dense_solver(use_pallas: bool, lanes=None, iter_kinds: tuple = ()):
    """Jitted dense solve with the output sliced to the filled extent
    inside the same executable (one dispatch, download-sized output).
    `lanes`/`iter_kinds` are the host-knowledge fast paths of
    solver.lanes (skip absent algorithm lanes; restrict each iterative
    fill — FAIR_SHARE's bisection and the fairness portfolio's bounded
    iterations — to its own rows) — byte-identical to the full solve;
    the pallas kernel ignores them (its fused body computes all lanes
    in VMEM). `iter_kinds` is the static tuple of AlgoKind ints whose
    row sets ride the `lane_rows` dict argument."""
    key = (use_pallas, lanes, iter_kinds)
    fn = _dense_solvers.get(key)
    if fn is None:
        from functools import partial

        if use_pallas:
            from doorman_tpu.solver.pallas_dense import solve_dense_pallas

            @partial(jax.jit, static_argnums=(1, 2))
            def fn(dense, n_rows, kfill, lane_rows=None):
                return solve_dense_pallas(dense)[:n_rows, :kfill]

        else:
            from doorman_tpu.solver.dense import solve_dense

            @partial(jax.jit, static_argnums=(1, 2))
            def fn(dense, n_rows, kfill, lane_rows=None):
                return solve_dense(
                    dense, lanes=lanes,
                    lane_rows=lane_rows if iter_kinds else None,
                )[:n_rows, :kfill]

        _dense_solvers[key] = fn
    return fn


_dense_solvers: Dict[tuple, Callable] = {}


def _rebuild_grant_map(
    engine,
    by_id: Dict[str, Resource],
    resource_ids: List[str],
    ridx: np.ndarray,
    cids: np.ndarray,
    applied: np.ndarray,
    flat: np.ndarray,
    keep_has: np.ndarray,
    out: Dict[str, Dict[str, float]],
) -> None:
    """Rebuild {resource: {client: grant}} from an engine.apply result;
    learning-mode (keep_has) segments report the store's live has."""
    name = engine.client_name
    for i in np.nonzero(applied)[0]:
        seg = int(ridx[i])
        resource_id = resource_ids[seg]
        client_id = name(int(cids[i]))
        if keep_has[seg]:
            grant = by_id[resource_id].store.get(client_id).has
        else:
            grant = float(flat[i])
        out.setdefault(resource_id, {})[client_id] = grant


def _committed_platform(arr) -> str:
    """Platform of the device an array is committed to (the default
    backend may differ, e.g. a CPU-pinned solver on a TPU host).
    Raises for anything not committed to a device: guessing would hide
    which kernel the tick compiles."""
    return next(iter(arr.devices())).platform


def _round_rows(n: int) -> int:
    """Dense row padding: powers of two while small (few compile
    variants), multiples of 1024 beyond (padding waste bounded at ~10%
    instead of ~2x)."""
    if n <= 1024:
        return _bucket(max(n, 1), 16)
    return -(-n // 1024) * 1024


@dataclass
class PrioritySnapshot:
    """The PRIORITY_BANDS resources of one tick in the dense layout
    (solver.priority); built by BatchSolver.snapshot, solved alongside
    the lane snapshot, written back by BatchSolver.apply.

    Two pack flavors (mirroring Snapshot): the Python-store pack carries
    per-slot client names; the native pack carries the flat
    ridx/cids/pos handle arrays plus the engine, and write-back is one
    dm_apply call."""

    resource_ids: List[str]
    learning: List[bool]
    batch: object  # solver.priority.PriorityBatch
    num_bands: int
    # Python-store pack:
    clients: "List[List[str]] | None" = None  # per resource, per K slot
    # Native pack:
    engine: object = None
    ridx: "np.ndarray | None" = None  # [E] segment per edge
    cids: "np.ndarray | None" = None  # [E] client handles
    pos: "np.ndarray | None" = None  # [E] slot within the resource row
    gets: "np.ndarray | None" = None  # [R, K], filled by solve()


def _shared_native_engine(stores) -> "object | None":
    """The one StoreEngine behind every store, or None if the stores are
    not all native views on a single engine."""
    try:
        from doorman_tpu.native import NativeLeaseStore
    except Exception:  # pragma: no cover - native module always importable
        return None
    engines = set()
    for store in stores:
        if not isinstance(store, NativeLeaseStore):
            return None
        engines.add(id(store._engine))
    if len(engines) != 1:
        return None
    return stores[0]._engine


class BatchSolver:
    def __init__(
        self,
        *,
        dtype=np.float64,
        device: "jax.Device | None" = None,
        clock: Callable[[], float] = time.time,
    ):
        if np.dtype(dtype) == np.float64 and not jax.config.jax_enable_x64:
            raise RuntimeError(
                "BatchSolver dtype=float64 (the oracle-parity default) "
                "requires jax_enable_x64; enable it "
                "(jax.config.update('jax_enable_x64', True) or "
                "JAX_ENABLE_X64=True) or pass dtype=np.float32 explicitly "
                "to accept f32 grants."
            )
        self._dtype = dtype
        self._device = device
        self._clock = clock
        self._solve = solve_tick_jit
        self._group_caps: Dict[str, float] = {}
        self.ticks = 0
        self.last_tick_seconds = 0.0
        self._tick_start = 0.0
        # Cumulative per-phase wall time (seconds); every phase also
        # lands in the default metrics registry and the trace ring
        # (obs.phases.PhaseRecorder). Keys pre-created so concurrent
        # readers can iterate while a tick updates values.
        self.phase_s: Dict[str, float] = {
            name: 0.0 for name in ("pack", "solve", "apply")
        }

    def set_groups(self, group_caps: Dict[str, float]) -> None:
        """Install the config's capacity groups (name -> shared cap);
        referenced by PRIORITY_BANDS resources via
        ResourceTemplate.capacity_group."""
        self._group_caps = dict(group_caps)

    def _to_device(self, arr: np.ndarray):
        return jax.device_put(arr, self._device)

    def snapshot(self, resources: Iterable[Resource]) -> Snapshot:
        all_res: List[Resource] = list(resources)
        # PRIORITY_BANDS resources solve in their own dense part; the
        # solve_lanes kernels carry every other kind.
        res_list, prio_res = [], []
        for r in all_res:
            is_prio = (
                algo_kind_for(r.template) == AlgoKind.PRIORITY_BANDS
            )
            (prio_res if is_prio else res_list).append(r)
        part = self._snapshot_priority(prio_res) if prio_res else None
        by_id: Dict[str, Resource] = {r.id: r for r in res_list}
        specs = [
            ResourceSpec(
                resource_id=r.id,
                capacity=r.capacity,
                algo_kind=algo_kind_for(r.template),
                learning=r.in_learning_mode,
                static_capacity=static_param(r.template),
            )
            for r in res_list
        ]

        # Native fast path: one C call dumps every lease of every resource
        # as flat edge arrays — no per-lease Python objects.
        stores = [r.store for r in res_list]
        engine = _shared_native_engine(stores) if stores else None
        if engine is not None:
            ridx, cid, wants, has, sub, _prio = engine.pack(stores)
            counts = (
                np.bincount(ridx, minlength=len(specs))
                if len(ridx)
                else np.zeros(len(specs), np.int64)
            )
            kmax = int(counts.max()) if len(counts) else 0
            if len(ridx) and kmax <= DENSE_MAX_K:
                # TPU-optimal layout: [R, K] rows solve as pure
                # elementwise + row reductions (no scatter — the edge
                # executable's segment sums serialize on TPU at ~1M
                # edges), then an on-device gather restores flat edge
                # order so only num_edges floats cross the link.
                snap = self._pack_dense(
                    specs, ridx, cid, wants, has, sub, counts, engine
                )
            else:
                snap = pack_edge_arrays(
                    specs,
                    ridx,
                    wants.astype(self._dtype, copy=False),
                    has.astype(self._dtype, copy=False),
                    sub.astype(self._dtype, copy=False),
                    dtype=self._dtype,
                    to_device=self._to_device,
                    engine=engine,
                    cids=cid,
                )
            snap.priority_part = part
            return snap

        def rows(resource_id: str):
            store = by_id[resource_id].store
            return [
                (client, lease.wants, lease.has, lease.subclients)
                for client, lease in store.items()
            ]

        snap = pack_snapshot(
            specs, rows, dtype=self._dtype, to_device=self._to_device
        )
        snap.priority_part = part
        return snap

    def _pack_dense(
        self,
        specs: List[ResourceSpec],
        ridx: np.ndarray,
        cid: np.ndarray,
        wants: np.ndarray,
        has: np.ndarray,
        sub: np.ndarray,
        counts: np.ndarray,
        engine: object,
    ) -> Snapshot:
        """Scatter the engine's flat edge arrays into the [R, K] dense
        layout (rows filled contiguously from lane 0, resource-major
        order preserved)."""
        from doorman_tpu.solver.dense import DenseBatch

        dtype = self._dtype
        n_spec = len(specs)
        R = _round_rows(n_spec)
        K = _bucket(int(counts.max()), 8)
        starts = np.zeros(n_spec + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        pos = np.arange(len(ridx), dtype=np.int64) - starts[ridx]

        w = np.zeros((R, K), dtype)
        h = np.zeros((R, K), dtype)
        s = np.zeros((R, K), dtype)
        act = np.zeros((R, K), bool)
        w[ridx, pos] = wants
        h[ridx, pos] = has
        s[ridx, pos] = sub
        act[ridx, pos] = True

        cap = np.zeros(R, dtype)
        kind = np.zeros(R, np.int32)
        learn = np.zeros(R, bool)
        statc = np.zeros(R, dtype)
        for i, spec in enumerate(specs):
            cap[i] = spec.capacity
            kind[i] = int(spec.algo_kind)
            learn[i] = spec.learning
            statc[i] = spec.static_capacity

        dev = self._to_device
        dense = DenseBatch(
            wants=dev(w),
            has=dev(h),
            subclients=dev(s),
            active=dev(act),
            capacity=dev(cap),
            algo_kind=dev(kind),
            learning=dev(learn),
            static_capacity=dev(statc),
        )
        # Download slice: rows and lanes round up to multiples of 8 so
        # the solve executable (shaped by these static args) does not
        # recompile every time a resource or client count drifts by one.
        n_rows = min(R, -(-n_spec // 8) * 8)
        kfill = min(K, -(-int(counts.max()) // 8) * 8)
        snap = Snapshot(
            edges=None,
            resources=None,
            edge_keys=[],
            resource_ids=[spec.resource_id for spec in specs],
            num_edges=len(ridx),
            learning=[bool(spec.learning) for spec in specs],
            engine=engine,
            ridx=ridx,
            cids=cid,
            dense=dense,
            pos=pos,
            dense_fill=(n_rows, kfill),
        )
        # Host lane knowledge for the solve (solver.lanes fast paths):
        # the specs name every algorithm kind present, and each
        # iterative lane's rows pad to a bucketed static shape (repeats
        # are harmless) so its fill runs only over its own rows.
        snap.dense_lanes = frozenset(int(k) for k in np.unique(kind[:n_spec]))
        iter_rows = {}
        for k in sorted(ITERATIVE_KINDS & snap.dense_lanes):
            rows = np.nonzero(kind[:n_spec] == int(k))[0].astype(np.int32)
            if len(rows):
                iter_rows[k] = np.resize(rows, _bucket(len(rows), 8))
        snap.dense_iter = iter_rows or None
        return snap

    def _snapshot_priority(
        self, prio_res: List[Resource]
    ) -> PrioritySnapshot:
        """Dense pack of the PRIORITY_BANDS resources: higher wire
        priority = lower band rank; capacity groups resolved against the
        config's group caps. Stores sharing a native engine pack via one
        dm_pack call (no per-lease Python objects)."""
        from doorman_tpu.solver.priority import PriorityBatch

        R = len(prio_res)
        dtype = self._dtype
        capacity = np.zeros(R, dtype)
        group = np.full(R, -1, np.int32)
        learning: List[bool] = []
        group_ids: Dict[str, int] = {}
        group_caps: List[float] = []
        for i, res in enumerate(prio_res):
            capacity[i] = res.capacity
            learning.append(res.in_learning_mode)
            tpl = res.template
            if tpl.HasField("capacity_group"):
                name = tpl.capacity_group
                if name in self._group_caps:
                    if name not in group_ids:
                        group_ids[name] = len(group_caps)
                        group_caps.append(float(self._group_caps[name]))
                    group[i] = group_ids[name]

        stores = [r.store for r in prio_res]
        engine = _shared_native_engine(stores)
        num_bands = 1
        clients: "List[List[str]] | None" = None
        ridx = cids = pos = None
        if engine is not None:
            ridx, cids, wants_f, _has_f, sub_f, prio_f = engine.pack(stores)
            counts = np.bincount(ridx, minlength=R)
            K = _bucket(int(counts.max()) if len(ridx) else 1, 8)
            starts = np.zeros(R + 1, np.int64)
            np.cumsum(counts, out=starts[1:])
            pos = np.arange(len(ridx), dtype=np.int64) - starts[ridx]
            wants = np.zeros((R, K), dtype)
            weights = np.zeros((R, K), dtype)
            band = np.zeros((R, K), np.int32)
            active = np.zeros((R, K), bool)
            wants[ridx, pos] = wants_f
            weights[ridx, pos] = sub_f
            active[ridx, pos] = True
            band_f = np.zeros(len(ridx), np.int32)
            for i in range(R):
                s, e = starts[i], starts[i + 1]
                if s == e:
                    continue
                levels = np.unique(prio_f[s:e])[::-1]  # descending
                num_bands = max(num_bands, len(levels))
                band_f[s:e] = np.searchsorted(-levels, -prio_f[s:e])
            band[ridx, pos] = band_f
        else:
            counts = [len(r.store) for r in prio_res]
            K = _bucket(max(counts + [1]), 8)
            wants = np.zeros((R, K), dtype)
            weights = np.zeros((R, K), dtype)
            band = np.zeros((R, K), np.int32)
            active = np.zeros((R, K), bool)
            clients = []
            for i, res in enumerate(prio_res):
                row = []
                leases = list(res.store.items())[:K]
                levels = sorted(
                    {lease.priority for _, lease in leases}, reverse=True
                )
                rank = {p: j for j, p in enumerate(levels)}
                num_bands = max(num_bands, len(levels))
                for j, (client, lease) in enumerate(leases):
                    row.append(client)
                    wants[i, j] = lease.wants
                    weights[i, j] = lease.subclients
                    band[i, j] = rank[lease.priority]
                    active[i, j] = True
                clients.append(row)

        batch = PriorityBatch(
            wants=self._to_device(wants),
            weights=self._to_device(weights),
            band=self._to_device(band),
            active=self._to_device(active),
            capacity=self._to_device(capacity),
            group=self._to_device(group),
            group_cap=self._to_device(np.asarray(group_caps, dtype)),
        )
        return PrioritySnapshot(
            resource_ids=[r.id for r in prio_res],
            learning=learning,
            batch=batch,
            num_bands=_bucket(num_bands, 1),
            clients=clients,
            engine=engine,
            ridx=ridx,
            cids=cids,
            pos=pos,
        )

    def prepare(self, resources: Iterable[Resource]) -> Snapshot:
        """Phase 1 (host, must run in the thread that owns the stores):
        sweep expired leases and pack the snapshot."""
        self._tick_start = self._clock()
        ph = PhaseRecorder("batch", self.phase_s)
        res_list = list(resources)
        for r in res_list:
            r.store.clean()
        snap = self.snapshot(res_list)
        ph.lap("pack")
        return snap

    def solve(self, snap: Snapshot) -> np.ndarray:
        """Phase 2 (device; blocking — safe to run in an executor thread,
        touches no host store state)."""
        ph = PhaseRecorder("batch", self.phase_s)
        try:
            return self._solve_timed(snap)
        finally:
            ph.lap("solve")

    def _solve_timed(self, snap: Snapshot) -> np.ndarray:
        part = snap.priority_part
        if part is not None:
            from doorman_tpu.solver.priority import solve_priority

            # Dispatch the priority part first so both solves overlap;
            # on TPU the banded water-fill runs as the fused VMEM kernel
            # (f32 only — Mosaic does not lower f64).
            use_pallas = (
                _committed_platform(part.batch.wants) == "tpu"
                and part.batch.wants.dtype == jnp.float32
            )
            prio_gets = solve_priority(
                part.batch, num_bands=part.num_bands, use_pallas=use_pallas
            )
        # Large grant tables download as several overlapping copies
        # (utils.transfer).
        if snap.dense is not None:
            use_pallas = (
                _committed_platform(snap.dense.wants) == "tpu"
                and snap.dense.wants.dtype == jnp.float32
            )
            n_rows, kfill = snap.dense_fill
            lanes = getattr(snap, "dense_lanes", None)
            iter_rows = getattr(snap, "dense_iter", None)
            dense_gets = _dense_solver(
                use_pallas, lanes,
                tuple(sorted(iter_rows)) if iter_rows else (),
            )(snap.dense, n_rows, kfill, iter_rows)
            got = chunked_device_get(dense_gets)
            gets = got[snap.ridx, snap.pos]
        else:
            gets = chunked_device_get(
                self._solve(snap.edges, snap.resources)
            )
        if part is not None:
            # The batch solver is the synchronous reference path: its
            # solve lap deliberately includes the downloads (there is
            # no pipelining seam to hand the transfer off to).
            # doorman: allow[device-sync-taint] synchronous path by design
            part.gets = chunked_device_get(prio_gets)
        return gets

    def apply(
        self,
        resources: Iterable[Resource],
        snap: Snapshot,
        gets: np.ndarray,
        *,
        return_grants: bool = True,
    ) -> Dict[str, Dict[str, float]]:
        """Phase 3 (host, store-owning thread): write grants back.
        Grants ONLY — lease expiry/refresh advance when each client
        itself refreshes (the decide path), never on delivery, so a
        client that stops refreshing expires after one lease length
        even while the server stays busy (reference semantics). Demand
        that changed while the solve was in flight is preserved, and
        clients released mid-solve stay released.

        `return_grants=False` skips materializing the per-client grant
        map — the tick loop only needs the store side effects, and at
        100k+ leases the map rebuild is per-edge Python work."""
        ph = PhaseRecorder("batch", self.phase_s)
        by_id = {r.id: r for r in resources}
        if snap.engine is not None:
            out = self._apply_native(
                by_id, snap, gets, return_grants=return_grants
            )
        else:
            out = {}
            learn_ids = {
                rid
                for rid, flag in zip(snap.resource_ids, snap.learning or [])
                if flag
            }
            for (resource_id, client_id), grant in snap.unpack(
                gets[: snap.num_edges]
            ).items():
                res = by_id.get(resource_id)
                if res is None or not res.store.has_client(client_id):
                    continue
                if resource_id in learn_ids:
                    # Learning mode replays the client's reported has —
                    # the store's live value already IS the grant, so
                    # there is nothing to write back.
                    grant = res.store.get(client_id).has
                else:
                    # Grants only: expiry/refresh advance when the
                    # client itself refreshes, never on delivery
                    # (reference semantics — a dead client must expire
                    # on schedule).
                    res.store.regrant(client_id, grant)
                if return_grants:
                    out.setdefault(resource_id, {})[client_id] = grant
        self._apply_priority_part(by_id, snap, out, return_grants)
        ph.lap("apply")
        self.ticks += 1
        self.last_tick_seconds = self._clock() - self._tick_start
        return out

    def _apply_priority_part(
        self,
        by_id: Dict[str, Resource],
        snap: Snapshot,
        out: Dict[str, Dict[str, float]],
        return_grants: bool,
    ) -> None:
        """Write the priority part's grants back (same skip/preserve rules
        as the lane path; learning-mode resources replay reported has)."""
        part = snap.priority_part
        if part is None:
            return
        if part.engine is not None:
            self._apply_priority_native(by_id, part, out, return_grants)
            return
        for i, resource_id in enumerate(part.resource_ids):
            res = by_id.get(resource_id)
            if res is None:
                continue
            for j, client_id in enumerate(part.clients[i]):
                if not res.store.has_client(client_id):
                    continue
                if part.learning[i]:
                    grant = res.store.get(client_id).has  # replay: no-op
                else:
                    grant = float(part.gets[i, j])
                    res.store.regrant(client_id, grant)
                if return_grants:
                    out.setdefault(resource_id, {})[client_id] = grant

    def _apply_priority_native(
        self,
        by_id: Dict[str, Resource],
        part: PrioritySnapshot,
        out: Dict[str, Dict[str, float]],
        return_grants: bool,
    ) -> None:
        """One dm_apply call writes the priority part back (grants only;
        expiry/refresh are client-driven); learning-mode segments keep
        the reported has."""
        engine = part.engine
        n_seg = len(part.resource_ids)
        order = np.full(n_seg, -1, np.int32)
        keep_has = np.zeros(n_seg, np.uint8)
        for i, resource_id in enumerate(part.resource_ids):
            res = by_id.get(resource_id)
            if res is None:
                continue
            if getattr(res.store, "_engine", None) is not engine:
                continue
            order[i] = res.store._rid
            keep_has[i] = 1 if part.learning[i] else 0
        flat = np.asarray(
            part.gets[part.ridx, part.pos], np.float64
        )
        applied = engine.apply(order, part.ridx, part.cids, flat, keep_has)
        if not return_grants:
            return
        _rebuild_grant_map(
            engine, by_id, part.resource_ids, part.ridx, part.cids,
            applied, flat, keep_has, out,
        )

    def _apply_native(
        self,
        by_id: Dict[str, Resource],
        snap: Snapshot,
        gets: np.ndarray,
        *,
        return_grants: bool = True,
    ) -> Dict[str, Dict[str, float]]:
        """One C call writes every grant back into the engine (same
        skip/preserve semantics as the Python loop); the returned grant
        map is rebuilt from the applied mask."""
        engine = snap.engine
        n_seg = len(snap.resource_ids)
        order = np.full(n_seg, -1, np.int32)
        keep_has = np.zeros(n_seg, np.uint8)
        for i, resource_id in enumerate(snap.resource_ids):
            res = by_id.get(resource_id)
            if res is None:
                continue  # resource vanished mid-solve: skip its edges
            if getattr(res.store, "_engine", None) is not engine:
                continue  # store replaced mid-solve (mastership reset)
            order[i] = res.store._rid
            if snap.learning and snap.learning[i]:
                # Learning mode: keep the store's live has (a client
                # report landing mid-solve wins over the snapshot-stale
                # replay the solve produced).
                keep_has[i] = 1
        flat = np.asarray(gets[: snap.num_edges], np.float64)
        applied = engine.apply(order, snap.ridx, snap.cids, flat, keep_has)
        out: Dict[str, Dict[str, float]] = {}
        if not return_grants:
            return out
        _rebuild_grant_map(
            engine, by_id, snap.resource_ids, snap.ridx, snap.cids,
            applied, flat, keep_has, out,
        )
        return out

    def tick(self, resources: Iterable[Resource]) -> Dict[str, Dict[str, float]]:
        """Run one synchronous batched tick (prepare + solve + apply); for
        concurrent servers, run the three phases separately so only `solve`
        leaves the store-owning thread."""
        res_list = list(resources)
        snap = self.prepare(res_list)
        gets = self.solve(snap)
        return self.apply(res_list, snap, gets)
