"""The tick engine: one abstraction behind every solver path.

Four paths used to duplicate the upload/solve/deliver shape —
`solver/batch.py` (snapshot ticks), `solver/resident.py` (device-resident
narrow rows, single-device and mesh), `solver/resident_wide.py` (chunked
wide rows, single-device and mesh) — and each new feature paid 4x. This
module owns everything those paths share, as pluggable stages over shared
placement/transfer chokepoints:

  staging   — what reaches the device this tick: the drained dirty set,
              the (optional) admission-fused pack cache that moves the
              store pack off the tick's critical path and into the RPC
              window that caused it (`FusedStaging`), and the compact
              transfer encodings (`bf16_exact`, `compact_index_dtype`);
  solve     — the jitted table solve, shaped by host knowledge: the
              config mirror (`ConfigTable`) knows which algorithm lanes
              exist and which rows run FAIR_SHARE, so the executable
              skips absent lanes and restricts the water-fill bisection
              to the fair rows (both byte-identical by construction, see
              solver.lanes);
  delivery  — the rotation-and-dirty download back into the store of
              record (`RotationCursor`, `TickEngineBase.collect`), with
              pipelining owned by `PipelinedTicker` so several ticks
              keep their uploads, solves, and downloads in flight.

`TickEngineBase` is the contract the resident solvers implement (the
dispatch skeleton lives here; the per-layout staging tails live in the
solvers); `BatchTickAdapter` wraps the snapshot BatchSolver in the same
dispatch/collect surface so drivers and the conformance suite
(tests/test_engine.py) treat all four paths uniformly.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from doorman_tpu.core.resource import Resource, algo_kind_for, static_param
from doorman_tpu.obs.phases import PhaseRecorder
from doorman_tpu.utils import dispatch as dispatch_mod

log = logging.getLogger(__name__)

__all__ = [
    "PHASES",
    "TickHandle",
    "TickEngineBase",
    "ConfigTable",
    "RotationCursor",
    "FusedStaging",
    "ScopeTracker",
    "PipelinedTicker",
    "BatchTickAdapter",
    "place",
    "count_launch",
    "landed_rows",
    "landed_changed",
    "bf16_exact",
    "compact_index_dtype",
    "ceil_to",
    "pow2_bucket",
]

# Every tick engine exposes this phase vocabulary (cumulative seconds in
# phase_s; bench.py, /debug/status, and the flight recorder all read it).
# "staging" is the host-side assembly of this tick's upload blocks —
# split from "upload" (the device placement) so the admission-fused
# pipeline stage is triaged like the others. "delta" is the host tail of
# delivered-grant delta extraction (streaming lease push): resolving the
# device-compared changed-row mask to engine rids — the mask itself
# lands with the delivery download. "aggregate" is the federated
# intermediate's band-masked subtree summation (the launch half of its
# device tick, federation/aggregate.py) — its own name because it is a
# different executable than "solve", not a lease solve at all.
# "match" is the stream fanout's device-side changed-row -> subscriber
# intersection (server/match.py): the incidence staging scatters plus
# the masked-gather launch; the matched-pair landing rides "download"
# like any delivery byte. "fused" is the fused-tick device window: the
# SINGLE staged-buffer placement plus the one-launch
# staging->solve->delta executable plus the download kickoff — in
# fused mode it replaces the separate "upload" and "solve" laps (which
# the round-trip mode keeps), so a flight-recorder dump says at a
# glance which mode a tick ran in.
PHASES = (
    "sweep", "drain", "config", "pack", "staging", "upload", "solve",
    "fused", "aggregate", "match", "download", "apply", "delta",
    "rebuild",
)


def pow2_bucket(n: int, minimum: int = 8) -> int:
    """Round up to the next power of two (>= minimum). The scoped-solve
    compact table uses geometric buckets, not multiples: its shape is a
    jit key and the scope size swings with churn from a handful of rows
    to the whole table, so the recompile count must stay O(log R)
    (match.py's incidence extents use the same rule)."""
    size = max(int(minimum), 1)
    while size < n:
        size *= 2
    return size


def ceil_to(n: int, m: int) -> int:
    """Round up to a multiple of m (>= m). Per-tick scatter/delivery
    shapes use multiples, not powers of two: the host<->device link is
    the tick's bottleneck, and a power-of-two bucket ships up to 2x the
    bytes for the same work (2048x128 vs 1280x104 is half a megabyte per
    tick at the bench shape). Multiples keep the recompile count bounded
    (shapes per axis <= axis_max / m) while tracking the true size."""
    return max(m, ((n + m - 1) // m) * m)


def place(arr, *, device=None, sharding=None):
    """The tick engines' single placement chokepoint: every device
    table, config column, and staged per-tick block lands through here,
    so the single-device path (explicit device or backend default) and
    the mesh path (a NamedSharding) cannot drift apart. Each call is
    one host->device transfer op and counts as one dispatch
    (utils.dispatch) — the fused-tick accounting's upload half."""
    import jax

    dispatch_mod.count_dispatch()
    if sharding is not None:
        return jax.device_put(arr, sharding)
    return jax.device_put(arr, device)


def count_launch(n: int = 1) -> None:
    """Record a tick-executable launch in the dispatch accounting
    (utils.dispatch). Every engine's jitted tick call site counts
    itself here — the launch half of the per-tick `dispatches`
    number the flight recorder and bench report."""
    dispatch_mod.count_dispatch(n)


def landed_rows(handle: "TickHandle") -> np.ndarray:
    """Land a tick's download into [n_sel, W] float64 rows (shared by
    the narrow and wide collect paths). Single-device ticks land as one
    padded [Sb, W] slab; mesh ticks as [n_dev, Sb, W] per-shard blocks
    whose real rows concatenate in shard-major order — exactly the
    sorted order of handle.sel_rows."""
    from doorman_tpu.utils.transfer import land_parts

    gets = np.asarray(land_parts(handle.out), np.float64)
    if handle.shard_counts is None:
        return gets[: handle.n_sel]
    parts = [
        gets[d, : int(c)]
        for d, c in enumerate(handle.shard_counts)
        if int(c)
    ]
    if not parts:
        return np.zeros((0, gets.shape[-1]))
    return np.concatenate(parts)


def landed_changed(handle: "TickHandle") -> "np.ndarray | None":
    """Land a tick's changed-row mask into a [n_sel] host bool array
    (None when the engine does not track deltas). Mesh masks land as
    [n_dev, Sb] per-shard blocks whose real slots concatenate in
    shard-major order — exactly like landed_rows."""
    if handle.changed is None:
        return None
    if not isinstance(handle.changed, np.ndarray):
        # Landing a device mask is one device->host sync (the fused
        # path avoids it by packing the mask into the delivery slab).
        dispatch_mod.count_host_sync()
    ch = np.asarray(handle.changed)
    if handle.shard_counts is None:
        return ch[: handle.n_sel].astype(bool)
    parts = [
        ch[d, : int(c)]
        for d, c in enumerate(handle.shard_counts)
        if int(c)
    ]
    if not parts:
        return np.zeros(0, bool)
    return np.concatenate(parts).astype(bool)


try:
    from ml_dtypes import bfloat16 as _BF16
except Exception:  # pragma: no cover - ml_dtypes ships with jax
    _BF16 = None


def bf16_exact(arr: np.ndarray) -> bool:
    """True when `arr` round-trips bfloat16 exactly — then shipping the
    block as bf16 and casting back on device is byte-identical at half
    (f32) or a quarter (f64) of the upload bytes. Demand expressed in
    small integers (the common case) is exact up to 256; one vectorized
    host check per staged block decides per tick."""
    if _BF16 is None or arr.size == 0:
        return False
    return bool((arr.astype(_BF16).astype(arr.dtype) == arr).all())


def compact_index_dtype(limit: int):
    """int32 when every index below `limit` fits (halves index-upload
    bytes vs int64), else int64."""
    return np.int32 if limit < 2**31 else np.int64


@dataclass
class TickHandle:
    """One in-flight tick: the device output plus everything collect()
    needs to write it back. out=None marks an idle tick (nothing to
    download or apply)."""

    out: object  # list of device slices of [Sb, kfill], copies in flight
    sel_rows: np.ndarray  # [n_sel] row indices (unique)
    rids: np.ndarray  # [n_sel] engine resource handles
    versions: np.ndarray  # [n_sel] membership epochs at upload
    keep_has: np.ndarray  # [n_sel] uint8 (learning rows)
    n_sel: int = 0
    dispatched_at: float = 0.0
    collected: bool = False
    # Wide (chunked) ticks only: the chunk number per selected row
    # (solver.resident_wide writes back via apply_chunks).
    chunks: "np.ndarray | None" = None
    # Mesh ticks only: real delivered rows per shard. out lands as
    # [n_dev, Sb, W] (one padded block per shard) and collect
    # reassembles the first shard_counts[d] rows of each block — in
    # shard-major order, which IS the sorted global order of sel_rows.
    shard_counts: "np.ndarray | None" = None
    # Fused-staging bookkeeping for this tick (flight recorder / bench):
    # windows folded in, rows served from the window-time pack cache.
    fused_windows: int = 0
    fused_rows: int = 0
    # Delta tracking (streaming lease push): device bool mask over the
    # delivery slots — True where the delivered grants differ from the
    # resident previous-grants table. Single device: [Sb]; mesh:
    # [n_dev, Sb] per-shard blocks aligned with `out`. None when the
    # engine does not track deltas.
    changed: object = None
    # Fused ticks pack the changed mask INTO the delivered slab as its
    # last `mask_rows` rows ({0,1} in the download dtype, flattened
    # slot-major), so the grants and the mask land in one download
    # stream instead of two. 0 = the mask (if any) rides `changed`.
    mask_rows: int = 0
    # Scoped-solve bookkeeping (solve_mode == "scoped" ticks only): the
    # scope unit ids this tick solved (narrow: table rows; wide: segment
    # ids), and the per-unit solve-moved mask — gets != has at the
    # solve's input, the fixpoint test that retires units from the host
    # frontier at collect. Single-device fused ticks pack the moved
    # mask into the slab as `moved_rows` extra rows AFTER the changed
    # mask; mesh ticks land it as the separate `moved` device array
    # ([n_dev, Cb] shard blocks sliced by `scope_counts`, or a
    # replicated [Scb] per-segment mask for the wide mesh). `seq` is
    # the dispatch sequence number guarding frontier exits against
    # handles collected after a newer re-dirty (ScopeTracker).
    scope_ids: "np.ndarray | None" = None
    moved_rows: int = 0
    moved: object = None
    scope_counts: "np.ndarray | None" = None
    seq: int = 0


def idle_handle(now: float) -> TickHandle:
    return TickHandle(
        out=None,
        sel_rows=np.zeros(0, np.int64),
        rids=np.zeros(0, np.int32),
        versions=np.zeros(0, np.uint64),
        keep_has=np.zeros(0, np.uint8),
        n_sel=0,
        dispatched_at=now,
    )


class ConfigTable:
    """Per-entity config mirror shared by the resident solvers (narrow:
    one entity per table row; wide: one per segment). One pass over the
    templates only when the caller's config epoch moves (10k protobuf
    reads cost ~30ms at 1M-lease scale); time-driven drift (learning-mode
    end, parent-lease expiry) recomputed vectorized every tick.

    `put` places the per-entity vectors (the narrow solver shards them
    with the table rows, the wide solver replicates per-segment config on
    every mesh device); `pad` is the padded entity count."""

    def __init__(self, dtype, put: Callable):
        self._dtype = np.dtype(dtype)
        self._put = put
        self.pad = 0
        self.n_real = 0
        self.cap_h = self.learn_h = self.kind_h = self.statc_h = None
        self.cap_d = self.kind_d = self.statc_d = self.learn_d = None
        self.refresh = None
        self._cap_raw = self._learn_end = self._parent_exp = None
        self._epoch = -1

    def reset(self, pad: int) -> None:
        """New layout (rebuild): drop every mirror so the next refresh
        re-reads and re-places everything."""
        self.pad = pad
        self.cap_h = self.learn_h = self.kind_h = self.statc_h = None
        self._cap_raw = None

    def lanes(self) -> frozenset:
        """The AlgoKind values present among the real entities — the
        static lane mask for the solve executable (solver.lanes)."""
        if self.kind_h is None or self.n_real == 0:
            return frozenset()
        return frozenset(int(k) for k in np.unique(self.kind_h[: self.n_real]))

    def derived_rotate(self, tick_interval: "float | None") -> "int | None":
        """Delivery must cover the whole table at least once per refresh
        interval, else a client can refresh against a store row older
        than its own cadence. Capped at 64: beyond that the per-tick
        rotation slice is already tiny (R/64 rows), while an uncapped
        derivation from a slow-refresh config (say 3600s refresh at 50ms
        ticks) would stretch a full delivery cycle — and the idle fast
        path's two-rotation threshold — into the tens of thousands of
        ticks."""
        if not tick_interval or self.refresh is None or self.n_real == 0:
            return None
        return max(
            1,
            min(int(self.refresh[: self.n_real].min() / tick_interval), 64),
        )

    def _read(self, rows: Sequence[Resource]) -> None:
        pad = self.pad
        dtype = self._dtype
        cap = np.zeros(pad, dtype)
        kind = np.zeros(pad, np.int32)
        statc = np.zeros(pad, dtype)
        refresh = np.full(pad, 1.0, np.float64)
        learn_end = np.zeros(pad, np.float64)
        parent_exp = np.full(pad, np.inf, np.float64)
        for i, r in enumerate(rows):
            tpl = r.template
            cap[i] = tpl.capacity
            kind[i] = algo_kind_for(tpl)
            statc[i] = static_param(tpl)
            refresh[i] = float(tpl.algorithm.refresh_interval)
            learn_end[i] = r.learning_mode_end
            if r.parent_expiry is not None:
                parent_exp[i] = r.parent_expiry
        self.n_real = len(rows)
        self._cap_raw = cap
        self._learn_end = learn_end
        self._parent_exp = parent_exp
        self.refresh = refresh
        if self.kind_h is None or not np.array_equal(kind, self.kind_h):
            self.kind_h, self.kind_d = kind, self._put(kind)
        if self.statc_h is None or not np.array_equal(statc, self.statc_h):
            self.statc_h, self.statc_d = statc, self._put(statc)

    def refresh_view(
        self, rows: Sequence[Resource], config_epoch: int, now: float
    ) -> "np.ndarray | None":
        """Per-tick config view; returns the entities whose effective
        config changed this tick (they must be DELIVERED this tick — the
        solve sees new config immediately, and the store of record must
        too, matching the reference's config-at-next-decide semantics,
        go/server/doorman/resource.go:117-140). None means "everything
        may have changed" (epoch moved / first tick): deliver all."""
        epoch_moved = config_epoch != self._epoch or self._cap_raw is None
        if epoch_moved:
            self._epoch = config_epoch
            self._read(rows)
        # Expired parent lease => capacity 0 (core/resource.py:capacity).
        cap = np.where(
            self._parent_exp < now, 0.0, self._cap_raw
        ).astype(self._dtype)
        learn = self._learn_end > now
        if epoch_moved or self.cap_h is None or self.learn_h is None:
            changed: "np.ndarray | None" = None
        else:
            mask = (cap != self.cap_h) | (learn != self.learn_h)
            changed = np.nonzero(mask)[0]
        if self.cap_h is None or not np.array_equal(cap, self.cap_h):
            self.cap_h, self.cap_d = cap, self._put(cap)
        if self.learn_h is None or not np.array_equal(learn, self.learn_h):
            self.learn_h, self.learn_d = learn, self._put(learn)
        return changed


class RotationCursor:
    """The delivery rotation: every tick downloads 1/rotate of the table
    so the whole store of record refreshes once per `rotate` ticks.
    Single device: one cursor walks all rows. Mesh: per-shard cursors
    walk each shard's own real rows, so every tick's delivery download
    stays balanced across shards instead of one contiguous window
    marching through them."""

    def __init__(self):
        self.cursor = 0
        self.shard_cursors: "np.ndarray | None" = None

    def reset(self, n_dev: "int | None" = None) -> None:
        self.cursor = 0
        self.shard_cursors = (
            np.zeros(n_dev, np.int64) if n_dev else None
        )

    def rows(self, meshrows, n_real: int, rows_per_shard: int,
             rotate: int) -> np.ndarray:
        if meshrows is None or self.shard_cursors is None:
            rot_block = -(-n_real // rotate) if n_real else 1
            rot = (
                self.cursor + np.arange(rot_block, dtype=np.int64)
            ) % max(n_real, 1)
            self.cursor = (self.cursor + rot_block) % max(n_real, 1)
            return rot
        return meshrows.rotation_rows(
            self.shard_cursors, n_real, rows_per_shard, rotate
        )


class FusedStaging:
    """Admission-fused dirty-row staging: the window-time pack cache.

    The admission coalescer already groups a window's decisions per
    resource; right after the grouped pass writes the store, it hands
    the touched rows here (`stage`) and the engine packs them from the
    (authoritative) store immediately — in the RPC window, overlapped
    with whatever tick is in flight — instead of at the next dispatch.
    Dispatch consumes the cache (`take`) after its drain: the drained
    dirty set stays the single source of truth for WHICH rows upload
    and deliver (so fused and round-trip ticks build identical delivery
    sets), the cache only short-circuits packing their VALUES.

    Byte-identity contract: a cache entry is valid only while no store
    write touched its row after it was staged. Tracked writers
    (admission windows) refresh entries by re-staging; every untracked
    writer must `invalidate` the row (the server hooks its release and
    server-capacity paths), and an expiry sweep that removed anything
    invalidates wholesale (the sweep does not say which rows). A stale
    entry can otherwise only under-report writes that landed after this
    tick's drain — which the round-trip pack would have shipped one
    tick early; both paths converge on the next tick (the write's dirty
    flag is still set), the same one-tick window resident_wide.py
    documents for its drain/pack interleaving.

    Thread-safe: windows stage from the coalescer's executor while the
    tick executor takes.
    """

    def __init__(self, engine):
        self._engine = engine
        self._lock = threading.Lock()
        self._cache: Dict[int, tuple] = {}  # guarded-by: self._lock
        # Window tallies staged since the last take(), and lifetime
        # totals (status pages); same lock as the cache they describe.
        self.windows = 0  # guarded-by: self._lock
        self.staged_rows = 0  # guarded-by: self._lock
        self.total_windows = 0  # guarded-by: self._lock
        self.total_staged_rows = 0  # guarded-by: self._lock

    def stage(self, rids, kfill: int) -> int:
        """Pack the given engine rids from the store at the current lane
        width; returns rows staged. Called at window close (and by the
        bench's synthetic windows)."""
        rids = np.unique(np.asarray(rids, np.int32))
        if kfill <= 0 or not len(rids):
            return 0
        w, h, s, act, counts, versions = self._engine.pack_rows(
            rids, kfill
        )
        with self._lock:
            self.windows += 1
            self.total_windows += 1
            self.staged_rows += len(rids)
            self.total_staged_rows += len(rids)
            for i, rid in enumerate(rids):
                self._cache[int(rid)] = (
                    kfill, w[i], h[i], s[i], act[i],
                    int(counts[i]), versions[i],
                )
        return len(rids)

    def invalidate(self, rid: "int | None" = None) -> None:
        """Drop one row's entry (an untracked write touched it) or the
        whole cache (rid=None: sweep removals, mastership transitions —
        the clean fallback to the round-trip pack)."""
        with self._lock:
            if rid is None:
                self._cache.clear()
            else:
                self._cache.pop(int(rid), None)

    def take(self) -> Tuple[Dict[int, tuple], int, int]:
        """Consume the cache for one tick: (entries, windows, rows).
        Entries staged after this call belong to the next tick."""
        with self._lock:
            cache, self._cache = self._cache, {}
            windows, self.windows = self.windows, 0
            rows, self.staged_rows = self.staged_rows, 0
            return cache, windows, rows

    def status(self) -> dict:
        with self._lock:
            return {
                "pending_rows": len(self._cache),
                "windows_total": self.total_windows,
                "staged_rows_total": self.total_staged_rows,
            }


class ScopeTracker:
    """Host mirror of the not-yet-at-fixpoint unit set (the scoped
    solve's "frontier"): which solve units (narrow rows / wide
    segments) may still move if re-solved.

    The scoped tick only solves the units in scope and carries every
    other unit's resident grants forward untouched, so byte identity
    with the full solve rests on one invariant: **any unit whose next
    solve would differ from its resident grants is in the frontier.**
    The protocol that maintains it:

      entry — a unit enters (or refreshes) at dispatch when the host
              already knows it may move: its row went dirty, its
              effective config drifted, or a rebuild / config-epoch
              tick invalidated host knowledge wholesale (seed_all).
              Entries are stamped with the dispatch sequence.
      exit  — a unit leaves only when a collected tick REPORTS it
              unmoved: the scoped executable compares each scoped
              unit's fresh solve against its input `has` (the fixpoint
              test, in the solve dtype) and the mask rides the
              delivery download. A unit solved-and-unmoved at tick N
              is at its fixpoint, and a per-unit-independent solve of
              unchanged inputs is the identity from then on.
      guard — exits apply only when the unit's entry seq <= the
              reporting tick's seq: with depth-3 pipelining (and
              across rebuilds, which renumber unit ids) a stale moved
              mask must never evict a unit that re-entered after the
              reporting tick dispatched. Staleness is one-sided by
              construction: late collects can only keep a unit in
              scope longer, never drop a moving one.

    Not thread-safe by itself: dispatch and collect run on the tick
    executor (the server serializes them), matching the engines' other
    host mirrors.
    """

    def __init__(self):
        self._entry: Dict[int, int] = {}  # unit id -> entry seq

    def __len__(self) -> int:
        return len(self._entry)

    def add(self, ids, seq: int) -> None:
        """Enter (or refresh) units at dispatch seq `seq`. One dict
        update, not a per-unit loop: at a 100%-churn tick this runs
        over every row."""
        ids = np.asarray(ids).ravel()
        if len(ids):
            self._entry.update(
                zip(ids.tolist(), (seq,) * len(ids))
            )

    def seed_all(self, n_units: int, seq: int) -> None:
        """Rebuild / config-epoch tick: any unit may move (and old ids
        may now name different units) — replace the whole frontier."""
        self._entry = {i: seq for i in range(int(n_units))}

    def apply_moved(self, ids: np.ndarray, moved: np.ndarray,
                    seq: int) -> None:
        """Collect feedback from the tick dispatched at `seq`: retire
        units reported unmoved, unless re-entered since (seq guard)."""
        entry = self._entry
        ids = np.asarray(ids).ravel()
        moved = np.asarray(moved).ravel()
        for i in ids[~moved[: len(ids)]].tolist():
            if entry.get(i, seq + 1) <= seq:
                del entry[i]

    def ids(self) -> np.ndarray:
        """The current frontier, sorted (the scope build wants a stable
        order: sorted unit ids keep mesh shard grouping contiguous and
        gather hints truthful)."""
        if not self._entry:
            return np.zeros(0, np.int64)
        return np.sort(np.fromiter(self._entry, np.int64, len(self._entry)))

    def clear(self) -> None:
        self._entry = {}


class TickEngineBase:
    """The shared half of a device-resident tick engine.

    Owns the stage skeleton (sweep -> drain -> config -> idle gate ->
    staging/solve/delivery launch), the placement chokepoints, config
    mirroring, rotation, idle accounting, and the collect/apply tail;
    subclasses implement the layout-specific hooks:

      _needs_rebuild(resources) / rebuild(resources)
      _drain(ph)          -> layout-specific dirty set (laps "drain")
      _drained_empty(d)   -> bool
      _launch(resources, drained, config_changed, now, ph) -> TickHandle
      _apply_grants(handle, gets) -> rows applied
    """

    component = "resident"

    def __init__(
        self,
        engine,
        *,
        dtype=np.float32,
        device=None,
        mesh=None,
        clock: Callable[[], float] = time.time,
        rotate_ticks: "int | None" = 8,
        tick_interval: "float | None" = None,
        download_dtype=None,
        config_put: "Callable | None" = None,
        fused: bool = True,
        scoped: bool = True,
    ):
        import jax

        if np.dtype(dtype) == np.float64 and not jax.config.jax_enable_x64:
            raise RuntimeError(
                f"{type(self).__name__} dtype=float64 requires "
                "jax_enable_x64"
            )
        self._engine = engine
        self._dtype = np.dtype(dtype)
        self._device = device
        # A parallel.mesh Mesh shards the table rows (and the per-tick
        # scatter/delivery traffic) across every mesh axis; `device` is
        # ignored under a mesh (placement follows the mesh's devices).
        self._mesh = mesh
        self._meshrows = None
        if mesh is not None:
            from doorman_tpu.solver.resident_mesh import MeshRows

            self._meshrows = MeshRows(mesh)
        self._clock = clock
        self._tick_interval = tick_interval
        self._rotate_override: "int | None" = None
        if rotate_ticks is None:
            self._rotate = 8
        else:
            self.rotate_ticks = rotate_ticks
        # Grants download in the solve dtype by default: bf16 would halve
        # the bytes but its ~0.4% rounding can push sum(has) over
        # capacity in the store; correctness wins by default.
        self._out_dtype = download_dtype or self._dtype
        self.ticks = 0
        self.idle_ticks = 0  # ticks served by the idle fast path
        self.last_tick_seconds = 0.0
        self._quiet_ticks = 0
        self._just_rebuilt = False
        self._rotation = RotationCursor()
        self._config = ConfigTable(
            self._dtype, config_put or self._put_rows
        )
        # Delivered-grant delta tracking (streaming lease push): when
        # enabled (and the engine supports it — see supports_delta), the
        # tick executable compares each delivered row against a resident
        # previous-grants table and the collect tail accumulates the
        # rids whose delivered values moved; the server's stream fanout
        # drains them (take_changed_rids) so only subscribers of rows
        # that actually changed pay a decide+serialize.
        self._track_deltas = False
        self._changed_lock = threading.Lock()
        self._changed_rids: set = set()  # guarded-by: self._changed_lock
        # Fused-tick mode (the default): one packed staged-buffer
        # placement + ONE jitted staging->solve->delta launch + one
        # download stream per tick, instead of a device dispatch per
        # staged block. Byte-identical to the round-trip mode (the
        # executable runs the same scatter/solve/compare ops; only the
        # transfer packing differs — pinned by tests/test_fused_tick
        # .py); `fused=False` keeps the multi-dispatch path for
        # baseline measurement and triage (doc/operations.md).
        self._fused = bool(fused)
        # Scoped solve (the default): each fused tick solves only the
        # resource-group closure of the staged dirty set plus the
        # frontier of units not yet back at their fixpoint
        # (ScopeTracker), gathered into a pow2-bucketed compact table;
        # everything else carries forward bit-identically in the
        # resident grant slab. Any tick whose scope the host cannot
        # bound (rebuild, config-epoch move, time-driven config drift,
        # an expiry sweep that removed leases, round-trip mode)
        # escalates loudly to a full solve — `last_solve_mode` /
        # `last_full_reason` record the per-tick decision.
        # scoped=False keeps the always-full solve for triage
        # (doc/operations.md).
        self._scoped = bool(scoped)
        self._scope = ScopeTracker()
        self._seq = 0  # dispatch sequence (frontier entry/exit guard)
        self._swept_removed = 0  # leases removed by this tick's sweep
        self._scope_reset = False  # seed the frontier on the next tick
        # Scope index buffer cache: the placed device copy of the last
        # scope vector, reused while the scope bytes are unchanged (the
        # quiet-tick fixpoint: repeated identical scopes must not
        # re-place the buffer — pinned by tests/test_scoped_solve.py's
        # dispatch-count test).
        self._scope_buf_key: "tuple | None" = None
        self._scope_buf_dev = None
        self.last_solve_mode = "full"
        self.last_full_reason: "str | None" = "startup"
        self.last_scope: Dict[str, int] = {"rows": 0, "resources": 0}
        self.solve_modes: Dict[str, int] = {"scoped": 0, "full": 0}
        # Admission-fused staging (narrow path); attach_staging() wires
        # it. None keeps the round-trip pack on every tick.
        self._staging: "FusedStaging | None" = None
        self.last_fused: Dict[str, int] = {"windows": 0, "rows": 0}
        # Anomaly hook (e.g. the server's flight recorder): called with
        # (kind, detail) when the engine detects an invariant at risk —
        # loud, but never fatal to the tick unless the caller raises.
        self.on_anomaly: "Callable[[str, dict], None] | None" = None
        self._tick_fns: Dict[tuple, Callable] = {}
        # Names of the pallas kernels traced into this engine's tick
        # programs (added at trace time, so once per compile): the
        # proof that a tick on the chip did not take the XLA solve.
        self.traced_kernels: set = set()
        # Per-phase wall-time accumulators (seconds) for the perf
        # breakdown; bench.py reports them per tick, and every lap also
        # lands in the default metrics registry and the trace ring
        # (obs.phases.PhaseRecorder). All keys exist from construction
        # so readers (e.g. /debug/status on the event loop) can iterate
        # while a tick in an executor thread updates values — the dict
        # never resizes, only stores floats.
        self.phase_s: Dict[str, float] = {name: 0.0 for name in PHASES}

    def _traced(self, kernel: Callable) -> Callable:
        """`kernel`, recorded in `traced_kernels` (call while tracing)."""
        self.traced_kernels.add(kernel.__name__)
        return kernel

    # -- configuration ------------------------------------------------

    @property
    def rotate_ticks(self) -> int:
        return self._rotate

    @rotate_ticks.setter
    def rotate_ticks(self, value: int) -> None:
        self._rotate_override = max(int(value), 1)
        self._rotate = self._rotate_override

    @property
    def fused_tick(self) -> bool:
        return self._fused

    @fused_tick.setter
    def fused_tick(self, value: bool) -> None:
        value = bool(value)
        if value != self._fused:
            self._fused = value
            self._tick_fns.clear()
            self._drop_scope_cache()

    @property
    def scoped_solve(self) -> bool:
        return self._scoped

    @scoped_solve.setter
    def scoped_solve(self, value: bool) -> None:
        """Runtime triage toggle. Turning scoped mode ON re-seeds the
        whole frontier on the next tick (while it was off, no moved
        masks flowed, so host knowledge of who is at fixpoint is
        stale)."""
        value = bool(value)
        if value != self._scoped:
            self._scoped = value
            self._drop_scope_cache()
            if value:
                self._scope_reset = True

    def _drop_scope_cache(self) -> None:
        self._scope_buf_key = None
        self._scope_buf_dev = None

    def _place_scope(self, host_arr: np.ndarray, put: Callable):
        """Place (or reuse) the scope index buffer. An unchanged scope
        vector — the quiet-tick fixpoint, where the same dirty set (or
        none) repeats — reuses the resident device copy without a new
        placement dispatch; any byte change re-places."""
        key = (host_arr.shape, host_arr.tobytes())
        if key != self._scope_buf_key or self._scope_buf_dev is None:
            self._scope_buf_dev = put(host_arr)
            self._scope_buf_key = key
        return self._scope_buf_dev

    def scope_status(self) -> dict:
        """The /debug/status scope block (read from the event loop
        while ticks run in an executor: plain ints and strings only)."""
        return {
            "enabled": self._scoped,
            "last_mode": self.last_solve_mode,
            "last_full_reason": self.last_full_reason,
            "last_scope_rows": int(self.last_scope.get("rows", 0)),
            "last_scope_resources": int(
                self.last_scope.get("resources", 0)
            ),
            "frontier": len(self._scope),
            "scoped_ticks": int(self.solve_modes.get("scoped", 0)),
            "full_ticks": int(self.solve_modes.get("full", 0)),
        }

    def _scope_for_tick(
        self,
        dirty_units: np.ndarray,
        config_changed: "np.ndarray | None",
        n_units: int,
    ) -> "Tuple[np.ndarray | None, str | None]":
        """Per-tick solve-mode decision (called once per launched tick,
        AFTER any mid-launch rebuild settled). Returns (scope_ids,
        forced_full_reason): scope_ids is the sorted unit closure to
        solve, or None with the reason when this tick must solve the
        full table. Host-side only — the scope is derived from the
        mirrored dirty set and the host frontier, never from device
        data, so every compact shape is host-known (no shape sync).

        Escalation matrix (each reason recorded, doc/design.md):
          rebuild       — unit ids renumbered; seed_all + full solve
          config-epoch  — refresh_view returned None (templates
                          re-read): any unit's config may have moved
          config-drift  — time-driven capacity/learning flips this
                          tick (learning-mode end, parent-lease
                          expiry): the affected units must re-solve
                          AND deliver under reference same-tick config
                          freshness; full solve keeps that path on the
                          one executable that already pins it
          expiry-sweep  — the sweep removed leases it does not name
          scope-reset   — scoped mode just re-enabled (stale frontier)
          round-trip    — fused=False keeps the multi-dispatch
                          baseline, which has no scoped variant
          disabled      — --no-scoped-solve triage
        """
        self._seq += 1
        seq = self._seq
        reason: "str | None" = None
        if not self._scoped:
            reason = "disabled"
        elif not self._fused:
            reason = "round-trip"
        elif self._swept_removed:
            reason = "expiry-sweep"
        if self._just_rebuilt or config_changed is None:
            self._scope.seed_all(n_units, seq)
            if reason is None:
                reason = "rebuild" if self._just_rebuilt else "config-epoch"
        elif self._scope_reset:
            self._scope.seed_all(n_units, seq)
            if reason is None:
                reason = "scope-reset"
        else:
            if len(dirty_units):
                self._scope.add(dirty_units, seq)
            if len(config_changed):
                cc = np.asarray(config_changed)
                self._scope.add(cc[cc < n_units], seq)
                if reason is None:
                    reason = "config-drift"
        self._scope_reset = False
        if reason is not None:
            self.last_solve_mode = "full"
            self.last_full_reason = reason
            self.solve_modes["full"] += 1
            return None, reason
        scope = self._scope.ids()
        # Stale ids past the table (defensive: seed_all covers every
        # renumbering path, but a frontier must never index out of the
        # current layout).
        if len(scope) and scope[-1] >= n_units:
            scope = scope[scope < n_units]
        self.last_solve_mode = "scoped"
        self.last_full_reason = None
        self.solve_modes["scoped"] += 1
        return scope, None

    def attach_staging(self) -> FusedStaging:
        """Enable admission-fused staging; returns the buffer the
        window path feeds. Idempotent."""
        if self._staging is None:
            self._staging = FusedStaging(self._engine)
        return self._staging

    # Engines that keep a resident previous-grants table and compare
    # delivered rows on device set this True (the narrow resident
    # solver); others return False from enable_delta_tracking and the
    # caller must treat every tick as potentially-changed.
    supports_delta = False

    def enable_delta_tracking(self) -> bool:
        """Turn on delivered-grant delta extraction for the streaming
        lease push. Returns True when this engine supports it; the next
        dispatch rebuilds so the previous-grants table exists.
        Idempotent; there is no disable (the table dies with the
        solver)."""
        if not self.supports_delta:
            return False
        if not self._track_deltas:
            self._track_deltas = True
            self._tick_fns.clear()
            self._invalidate_layout()
        return True

    def _invalidate_layout(self) -> None:
        """Subclass hook: drop the device tables so the next dispatch
        rebuilds (enable_delta_tracking needs the prev-grants table
        allocated alongside them)."""

    @property
    def delta_tracking(self) -> bool:
        return self._track_deltas

    def take_changed_rids(self) -> list:
        """Drain the engine rids whose delivered grants changed since
        they were last delivered (accumulated at collect). Thread-safe:
        collect may run in an executor while the fanout drains on the
        event loop."""
        with self._changed_lock:
            out = list(self._changed_rids)
            self._changed_rids.clear()
        return out

    @property
    def staging(self) -> "FusedStaging | None":
        return self._staging

    def _put(self, arr, sharding=None):
        return place(arr, device=self._device, sharding=sharding)

    def _put_rows(self, arr):
        """Row-axis placement: table rows / per-row config split over
        the mesh (axis 0 is always a multiple of the device count),
        per-shard staged blocks split by their leading device axis.
        Without a mesh this is the plain single-device put."""
        if self._meshrows is None:
            return self._put(arr)
        return self._put(arr, self._meshrows.shard0(np.ndim(arr)))

    def _put_rep(self, arr):
        """Per-SEGMENT config vectors: replicated on every mesh device
        (each shard's solve reads all segment config)."""
        if self._meshrows is None:
            return self._put(arr)
        return self._put(arr, self._meshrows.replicated())

    def _anomaly(self, kind: str, detail: dict) -> None:
        log.warning("%s: %s: %s", type(self).__name__, kind, detail)
        if self.on_anomaly is not None:
            try:
                self.on_anomaly(kind, detail)
            except Exception:
                log.exception("anomaly hook failed")

    def _refresh_config(
        self, rows: Sequence[Resource], config_epoch: int, now: float
    ) -> "np.ndarray | None":
        changed = self._config.refresh_view(rows, config_epoch, now)
        if self._rotate_override is None:
            derived = self._config.derived_rotate(self._tick_interval)
            if derived is not None:
                self._rotate = derived
        return changed

    def _rotation_rows(self, n_real: int, rows_per_shard: int) -> np.ndarray:
        return self._rotation.rows(
            self._meshrows, n_real, rows_per_shard, self.rotate_ticks
        )

    # -- the stage skeleton -------------------------------------------

    def dispatch(
        self, resources: Sequence[Resource], config_epoch: int = 0
    ) -> TickHandle:
        """Host+device phase: sweep expiries, stage the dirty deltas,
        launch the solve, and start the grant download for this tick's
        deliverable rows. Safe to run in an executor thread (the native
        engine is mutex-guarded).

        `config_epoch`: bump whenever templates / learning windows /
        parent leases changed outside the store (config reload,
        mastership change) — template reads are cached against it."""
        ph = PhaseRecorder(self.component, self.phase_s)

        now = self._clock()
        removed = self._engine.clean_all(now)
        # The sweep dirties the rows it touched but does not name them;
        # a removal therefore escalates this tick to a full solve
        # (_scope_for_tick's "expiry-sweep" reason).
        self._swept_removed = int(removed)
        if removed and self._staging is not None:
            # The sweep dirtied rows it does not name: the window-time
            # pack cache can no longer prove freshness — fall back to
            # the round-trip pack for this tick's rows.
            self._staging.invalidate()
        ph.lap("sweep")
        res_list = list(resources)
        if self._needs_rebuild(res_list):
            self.rebuild(res_list)
            ph.lap("rebuild")  # rebuilds are rare; timed as their own phase

        drained = self._drain(ph)
        config_changed = self._refresh_config(res_list, config_epoch, now)
        ph.lap("config")

        # Idle fast path: with no store changes and no config movement
        # for TWO full rotations, the store of record provably holds the
        # device fixpoint, and an idle server then costs NO device work
        # per tick instead of a full solve + delivery forever. Two
        # rotations, not one: the `has` chain is an iteration — a row
        # delivered early in the FIRST quiet rotation can carry a
        # pre-convergence value (proportional lanes redistribute freed
        # capacity over ~2 ticks) — while every delivery in the second
        # rotation is at least a full rotation of iterations past the
        # last change, far beyond any lane's convergence depth. Any
        # store write, expiry sweep removal (it dirties the row), config
        # epoch bump, or time-driven capacity/learning flip resumes real
        # ticks on the very next dispatch.
        quiet = (
            self._drained_empty(drained)
            and not self._just_rebuilt
            and config_changed is not None
            and len(config_changed) == 0
        )
        if quiet:
            self._quiet_ticks += 1
            if self._quiet_ticks > max(2 * self.rotate_ticks,
                                       self.rotate_ticks + 3):
                return idle_handle(now)
        else:
            self._quiet_ticks = 0
        return self._launch(res_list, drained, config_changed, now, ph)

    def collect(self, handle: TickHandle) -> int:
        """Write one tick's downloaded grants back into the engine; rows
        whose membership moved mid-flight are skipped (they re-deliver
        next tick). Returns the rows applied."""
        if handle.collected:
            return 0
        handle.collected = True
        if handle.out is None:
            # Idle tick: the store already holds the fixpoint; this
            # still counts as an applied tick (the table is current).
            self.ticks += 1
            self.idle_ticks += 1
            self.last_tick_seconds = self._clock() - handle.dispatched_at
            return 0
        ph = PhaseRecorder(self.component, self.phase_s)
        # Parts were split (and their async copies started) at
        # dispatch; land them in order into one buffer. The changed-row
        # mask (delta tracking) rides the same download lap — it is a
        # delivery byte like the grants themselves. Fused ticks land
        # grants AND mask from the one packed slab (see
        # TickHandle.mask_rows); round-trip ticks land them separately.
        moved: "np.ndarray | None" = None
        if handle.mask_rows or handle.moved_rows:
            from doorman_tpu.utils.transfer import land_parts

            slab = np.asarray(land_parts(handle.out), np.float64)
            n_slots = (
                slab.shape[0] - handle.mask_rows - handle.moved_rows
            )
            gets = slab[: handle.n_sel]
            changed = (
                slab[n_slots : n_slots + handle.mask_rows]
                .reshape(-1)[: handle.n_sel]
                != 0.0
            ) if handle.mask_rows else None
            if handle.moved_rows and handle.scope_ids is not None:
                moved = (
                    slab[n_slots + handle.mask_rows :]
                    .reshape(-1)[: len(handle.scope_ids)]
                    != 0.0
                )
        else:
            gets = landed_rows(handle)
            changed = landed_changed(handle)
            moved = self._landed_moved(handle)
        ph.lap("download")
        applied = self._apply_grants(handle, gets)
        ph.lap("apply")
        if changed is not None:
            # Resolve the mask to engine rids for the stream fanout
            # (rid -1 is the reserved padding row — never a real
            # resource). Host-side numpy only; the device compare and
            # its download already happened.
            if changed.any():
                rids = handle.rids[changed]
                rids = rids[rids >= 0]
                if len(rids):
                    with self._changed_lock:
                        self._changed_rids.update(int(r) for r in rids)
            ph.lap("delta")
        if moved is not None and handle.scope_ids is not None:
            # Frontier maintenance: scoped units the solve left at
            # their fixpoint retire (seq-guarded against re-dirties
            # that raced this handle through the pipeline). Host numpy
            # only — the mask landed with the delivery above.
            self._scope.apply_moved(handle.scope_ids, moved, handle.seq)
        self.ticks += 1
        self.last_tick_seconds = self._clock() - handle.dispatched_at
        return applied

    def _landed_moved(self, handle: TickHandle) -> "np.ndarray | None":
        """Land a mesh tick's separate solve-moved mask into a host
        bool array aligned with handle.scope_ids. Narrow mesh ticks
        carry per-shard [n_dev, Cb] blocks sliced by scope_counts
        (shard-major order IS the sorted scope order); wide mesh ticks
        carry one replicated per-segment mask."""
        if handle.moved is None or handle.scope_ids is None:
            return None
        if not isinstance(handle.moved, np.ndarray):
            # One device->host landing, like the round-trip delta mask.
            dispatch_mod.count_host_sync()
        mv = np.asarray(handle.moved)
        if handle.scope_counts is None:
            return mv.reshape(-1)[: len(handle.scope_ids)].astype(bool)
        parts = [
            mv[d, : int(c)]
            for d, c in enumerate(handle.scope_counts)
            if int(c)
        ]
        if not parts:
            return np.zeros(0, bool)
        return np.concatenate(parts).astype(bool)

    def step(
        self, resources: Sequence[Resource], config_epoch: int = 0
    ) -> int:
        """Sequential convenience: dispatch a tick and collect it
        immediately (the pipelined callers keep their own handle queue)."""
        return self.collect(self.dispatch(resources, config_epoch))

    # -- subclass hooks ------------------------------------------------

    def _needs_rebuild(self, resources: List[Resource]) -> bool:
        raise NotImplementedError

    def rebuild(self, resources: Sequence[Resource]) -> None:
        raise NotImplementedError

    def _drain(self, ph: PhaseRecorder):
        raise NotImplementedError

    def _drained_empty(self, drained) -> bool:
        raise NotImplementedError

    def _launch(self, resources, drained, config_changed, now, ph):
        raise NotImplementedError

    def _apply_grants(self, handle: TickHandle, gets: np.ndarray) -> int:
        raise NotImplementedError


class PipelinedTicker:
    """Depth-N dispatch/collect pipeline over tick engines: up to
    `depth` ticks stay in flight, so the delivery download of tick N
    lands concurrent with the staging and solve of ticks N+1..N+depth-1
    (the server's tick loop and bench.py both drive through this).
    Default depth 3 (>2): with the fused one-launch tick the download
    is the dominant async leg, and depth 3 keeps a tick's delivery
    landing while the NEXT tick stages its upload and the one after
    solves — the write-back deferral stays bounded by the delivery
    rotation's freshness argument exactly as at depth 2. Handles are
    stored WITH their engine, and a handle whose engine was replaced
    (mastership flip swapped the store engine) is dropped, not
    collected — its row ids belong to a different engine."""

    def __init__(self, depth: int = 3):
        self.depth = max(int(depth), 1)
        self._queue: deque = deque()

    def __len__(self) -> int:
        return len(self._queue)

    def step(self, solver, resources, config_epoch: int = 0) -> TickHandle:
        """Collect the oldest in-flight tick once the pipeline is full,
        then dispatch the next."""
        while len(self._queue) >= self.depth:
            s, h = self._queue.popleft()
            if s is solver:
                s.collect(h)
        handle = solver.dispatch(resources, config_epoch)
        self._queue.append((solver, handle))
        return handle

    def flush(self, solver=None) -> int:
        """Collect everything in flight (optionally only one solver's
        handles); returns the ticks collected."""
        n = 0
        remaining: deque = deque()
        while self._queue:
            s, h = self._queue.popleft()
            if solver is None or s is solver:
                s.collect(h)
                n += 1
            else:
                remaining.append((s, h))
        self._queue = remaining
        return n

    def drop(self) -> None:
        """Forget every in-flight handle WITHOUT collecting (standby
        transitions: no tick may apply on a non-master)."""
        self._queue.clear()


@dataclass
class _BatchHandle:
    resources: List[Resource]
    snap: object
    gets: np.ndarray
    dispatched_at: float = 0.0
    collected: bool = False
    out: object = None
    n_sel: int = 0


class BatchTickAdapter:
    """The snapshot BatchSolver behind the tick-engine dispatch/collect
    surface: dispatch() packs and solves (prepare + solve — the phases
    that may leave the store-owning thread), collect() applies. Lets
    drivers and the conformance suite treat the batch path as a fourth
    engine rather than a special case."""

    component = "batch"

    def __init__(self, solver):
        self.solver = solver
        self.idle_ticks = 0

    @property
    def phase_s(self) -> Dict[str, float]:
        return self.solver.phase_s

    @property
    def ticks(self) -> int:
        return self.solver.ticks

    @property
    def last_tick_seconds(self) -> float:
        return self.solver.last_tick_seconds

    def dispatch(self, resources, config_epoch: int = 0) -> _BatchHandle:
        res = list(resources)
        snap = self.solver.prepare(res)
        gets = self.solver.solve(snap)
        return _BatchHandle(resources=res, snap=snap, gets=gets)

    def collect(self, handle: _BatchHandle) -> int:
        if handle.collected:
            return 0
        handle.collected = True
        self.solver.apply(
            handle.resources, handle.snap, handle.gets,
            return_grants=False,
        )
        return int(handle.snap.num_edges)

    def step(self, resources, config_epoch: int = 0) -> int:
        return self.collect(self.dispatch(resources, config_epoch))
