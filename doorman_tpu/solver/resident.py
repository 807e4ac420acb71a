"""Device-resident steady-state tick solver (narrow rows).

The BatchSolver (solver/batch.py) re-uploads every lease and downloads
every grant each tick — robust, but at 1M leases the host link dominates
the tick (the round-trip costs ~25x the device solve). This module keeps
the dense [R, K] demand tables RESIDENT on device between ticks and
moves only what changed:

  staging:  rows whose solver-visible inputs changed since the last tick
            (the native engine tracks dirtiness per resource — pure
            expiry refreshes with unchanged demand don't count), as a
            row scatter into the donated tables. With admission-fused
            staging (engine.FusedStaging) the row pack happens at the
            RPC window that caused the change, off the tick's critical
            path; the drained dirty set remains the source of truth for
            WHICH rows ship. Wants-only blocks ship as bf16 when that
            round-trips exactly (engine.bf16_exact — byte-identical at
            a quarter of the f64 bytes).
  solve:    scoped by default to the dirty rows plus the
            not-yet-converged frontier, gathered into a pow2-bucketed
            compact table and scattered back into the resident grant
            slab — byte-identical to the full solve because per-row
            arithmetic is row-independent (engine.ScopeTracker; any
            escalation — rebuild, config epoch/drift, expiry sweep —
            solves the full table loudly). `has` chains on device from
            the previous tick's grants either way. The executable is
            shaped by host config knowledge: absent algorithm lanes
            are skipped and the FAIR_SHARE water-fill bisection runs
            only over the fair rows (solver.lanes — byte-identical by
            construction).
  delivery: only the grant rows being DELIVERED this tick — every dirty
            row (so demand changes land in the store within one tick),
            every row whose effective config changed (capacity cut,
            parent-lease expiry, learning-mode flip: the reference
            applies new config at the very next decide,
            go/server/doorman/resource.go:117-140, so the store of
            record must never serve grants computed under dead config),
            plus a rotating slice that covers the whole table every
            `rotate_ticks` ticks (steady-state grants only need to reach
            the store as often as clients refresh; the reference's own
            information model is exactly this stale — client-reported
            `has` lags by a refresh interval,
            go/server/doorman/server.go:732-817). `rotate_ticks` derives
            from min(refresh_interval)/tick_interval (capped at 64)
            unless pinned.

Idle servers cost no device work: once two full rotations have
delivered with no store or config changes, the store provably equals
the device fixpoint and ticks return immediately until something
changes.

Write-back safety: each row records the resource's membership epoch at
upload; `dm_apply_dense` skips rows whose epoch moved while the solve
was in flight (the change dirtied the row, so the next tick re-solves
and re-delivers it). The engine itself is mutex-guarded, so dispatch and
collect may run in an executor thread while RPC handlers keep mutating
leases on the event loop.

The stage skeleton (sweep -> drain -> config -> idle gate -> launch)
and the shared chokepoints (placement, config mirror, rotation, fused
staging, collect) live in solver/engine.py; this module owns the dense
row layout. Replaces the reference's per-request algorithm invocation at
scale (go/server/doorman/server.go:732-817); the lane math is
byte-identical to BatchSolver's (both call solver.dense/solve_lanes).
"""

from __future__ import annotations

import time
from typing import Callable, List, Sequence

import numpy as np

from doorman_tpu.core.resource import Resource
from doorman_tpu.core.snapshot import _bucket
from doorman_tpu.obs.phases import PhaseRecorder

# Dense row padding (shared rule with solver.batch._round_rows).
from doorman_tpu.solver.batch import DENSE_MAX_K, _round_rows
from doorman_tpu.solver.engine import (
    TickEngineBase,
    TickHandle,
    bf16_exact,
    ceil_to,
    count_launch,
    place,
    pow2_bucket,
)
from doorman_tpu.solver.engine import _BF16

from doorman_tpu.solver.lanes import ITERATIVE_KINDS

# Back-compat aliases (resident_wide and tests import these from here).
_ceil_to = ceil_to


def _compact_iter_positions(kind_c: np.ndarray, lanes: frozenset):
    """(clayout, concatenated position segments or None): the positions
    of each iterative lane's rows WITHIN a compact scope table, one
    pow2-bucketed segment per ITERATIVE_KINDS lane in `lanes` (padding
    repeats position 0 — duplicate gathers read, and duplicate
    scatters write, the same row). The layout is static per (lanes,
    per-kind bucket) combination, so the scoped executable cache stays
    bounded the same way the scope bucket itself is."""
    present = sorted(ITERATIVE_KINDS & set(lanes))
    if not present:
        return (), None
    segments = []
    layout = []
    off = 0
    for k in present:
        p = np.nonzero(kind_c == int(k))[0]
        Lb = pow2_bucket(max(len(p), 1), 8)
        segments.append(
            np.resize(p, Lb).astype(np.int32)
            if len(p)
            else np.zeros(Lb, np.int32)
        )
        layout.append((int(k), off, Lb))
        off += Lb
    return tuple(layout), np.concatenate(segments)


def _lane_rows_slicer(layout: tuple, lanes: frozenset):
    """Closure slicing a placed iter-rows buffer into solve_lanes'
    per-kind `lane_rows` dict, from the static (kind, offset, length)
    layout — only the kinds actually in this executable's lane set
    (absent lanes' segments would just be dead gathers)."""
    entries = [e for e in layout if e[0] in lanes]
    if not entries:
        return lambda buf: None

    def slice_rows(buf):
        return {k: buf[off : off + ln] for (k, off, ln) in entries}

    return slice_rows


class ResidentOverflow(RuntimeError):
    """A resource outgrew the dense bucket cap; callers should fall back
    to the BatchSolver path (its edge layout has no width limit)."""


class ResidentDenseSolver(TickEngineBase):
    """Steady-state batched ticks with the device as the table of record.

    Covers lane-algorithm resources backed by one native StoreEngine;
    PRIORITY_BANDS resources take the BatchSolver's priority part, and
    Python-store servers take the BatchSolver path entirely.
    """

    component = "resident"
    supports_delta = True

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
        fused: bool = True,
        scoped: bool = True,
    ):
        super().__init__(
            engine,
            dtype=dtype,
            device=device,
            mesh=mesh,
            clock=clock,
            rotate_ticks=rotate_ticks,
            tick_interval=tick_interval,
            download_dtype=download_dtype,
            fused=fused,
            scoped=scoped,
        )
        self._rows: List[Resource] = []
        self._row_lut = np.full(1, -1, np.int64)
        self._R = 0  # real rows
        self._Rp = 0  # padded rows
        self._K = 8
        self._kfill = 8
        self._uploaded_versions = np.zeros(0, np.uint64)
        self._rids = np.zeros(0, np.int32)

        # Device tables (donated through each tick executable).
        self._wants = self._has = self._sub = self._act = None
        # Resident previous-DELIVERED-grants table (delta tracking for
        # the streaming lease push): what the store of record last saw
        # for each row, kept on device so the per-tick compare against
        # fresh grants never re-ships full rows to the host — only the
        # [Sb]-bool changed mask rides the delivery download. None until
        # enable_delta_tracking() + the next rebuild.
        self._prev = None
        # Iterative-lane row indices (device, one padded segment per
        # ITERATIVE_KINDS lane present — FAIR_SHARE's bisection and the
        # fairness portfolio's bounded fills each restrict to their own
        # rows; see solver.lanes waterfill_level_compact /
        # iterfill_level_compact) — rebuilt when the config's kind
        # vector moves. `_iter_layout` is the static (kind, offset,
        # length) tuple the tick executables slice the buffer by.
        self._iter_rows_d = None
        self._iter_layout = ()
        self._iter_kinds_src = None

    # -- build / rebuild ----------------------------------------------

    def rebuild(self, resources: Sequence[Resource]) -> None:
        """Full pack: (re)upload every table. Called on first use and
        whenever the resource set, bucket width, or config shape moves."""
        rows = list(resources)
        self._rows = rows
        self._R = len(rows)
        # Vectorized rid -> row mapping (one fancy-index per tick); the
        # trailing extra slot is -1 so clamped out-of-range rids (other
        # resources sharing the engine) resolve to "not ours".
        max_rid = max((r.store._rid for r in rows), default=-1)
        self._row_lut = np.full(max_rid + 2, -1, np.int64)
        for i, r in enumerate(rows):
            self._row_lut[r.store._rid] = i
        # +1 reserves a padding row: ticks with no dirty rows scatter a
        # zero row there instead of disturbing a live row's has chain.
        self._Rp = _round_rows(self._R + 1)
        if self._meshrows is not None:
            # Equal row blocks per shard; fresh per-shard rotation
            # cursors (the old ones indexed the old partition).
            self._Rp = self._meshrows.round_rows(self._Rp)
            self._rotation.reset(self._meshrows.n_dev)
        else:
            self._rotation.reset()
        self._rids = np.full(self._Rp, -1, np.int32)
        for i, r in enumerate(rows):
            self._rids[i] = r.store._rid

        # Drain BEFORE packing: a store write landing between the pack
        # and a drain would have its flag cleared without its data ever
        # reaching the device. Post-drain writes re-flag and upload next
        # tick; the pack below reads state at least as fresh as the
        # drain point. drain2 so dirty_full flags reset with the drain.
        # A rebuild also invalidates the fused pack cache: cached rows
        # were packed against the old layout's lane width.
        self._engine.drain_dirty2()
        if self._staging is not None:
            self._staging.invalidate()
        # One C call packs all rows; a second pass only if K was too
        # small for the widest resource.
        K = self._K
        while True:
            w, h, s, act, counts, versions = self._engine.pack_rows(
                self._rids, K
            )
            kmax = int(counts.max()) if len(counts) else 1
            if kmax <= K:
                break
            K = _bucket(kmax, 8)
        if kmax > DENSE_MAX_K:
            # The rebuild already mutated row maps and drained dirty
            # flags; invalidate the device tables so a LATER dispatch
            # (e.g. the resident path resuming after the wide resource
            # shrank or a config change) forces a clean rebuild instead
            # of scattering into stale-shape tables.
            self._wants = None
            raise ResidentOverflow(
                f"resource with {kmax} clients exceeds the dense bucket "
                f"cap {DENSE_MAX_K}"
            )
        self._K = K
        self._kfill = min(K, ceil_to(kmax, 8))
        dtype = self._dtype
        self._wants = self._put_rows(w.astype(dtype))
        self._has = self._put_rows(h.astype(dtype))
        self._sub = self._put_rows(s.astype(dtype))
        self._act = self._put_rows(act.astype(bool))
        # The previous-grants table starts at the store's current has:
        # the first tracked tick's changed set is exactly the rows whose
        # fresh solve moves the store of record. Kept in the download
        # dtype so the compare sees the very bytes the host would.
        self._prev = (
            self._put_rows(h.astype(self._out_dtype))
            if self._track_deltas
            else None
        )
        self._uploaded_versions = versions
        self._config.reset(self._Rp)
        self._iter_kinds_src = None
        self._refresh_config(rows, self._config._epoch, self._clock())
        self._just_rebuilt = True
        self._tick_fns.clear()
        self._drop_scope_cache()

    def _invalidate_layout(self) -> None:
        # Force a rebuild at the next dispatch so the prev-grants table
        # is allocated alongside the demand tables.
        self._wants = None
        self._prev = None

    def _needs_rebuild(self, resources: List[Resource]) -> bool:
        # Full identity scan every tick: a mid-list replacement with
        # matching endpoints must trigger a rebuild, and 10k `is`
        # comparisons cost well under a millisecond.
        return (
            self._wants is None
            or len(resources) != self._R
            or any(a is not b for a, b in zip(resources, self._rows))
        )

    def _iter_rows(self):
        """(device buffer, layout) of per-lane row indices for every
        iterative lane present (solver.lanes ITERATIVE_KINDS ∩ the
        config's kind set), each segment padded to a bucketed static
        shape and concatenated — single device: [ΣLb]; mesh: per-shard
        [n_dev, ΣLb] shard-local blocks. `layout` is the static
        (kind, offset, length) tuple; the tick executables slice the
        buffer by it and hand solve_lanes a per-kind `lane_rows` dict,
        so each lane's fill gathers only its own rows. A cached zeros
        block (empty layout) when no row runs an iterative lane — the
        solve never reads it then, and caching it keeps the per-tick
        dispatch count at its floor instead of re-placing a throwaway
        block every tick. Rebuilt when the config's kind vector object
        moves (epoch changes)."""
        kind_h = self._config.kind_h
        if kind_h is self._iter_kinds_src:
            return self._iter_rows_d, self._iter_layout
        self._iter_kinds_src = kind_h
        present = sorted(
            ITERATIVE_KINDS
            & {int(k) for k in np.unique(kind_h[: self._R])}
        )
        if not present:
            self._iter_layout = ()
            if self._meshrows is None:
                self._iter_rows_d = self._put(np.zeros(8, np.int32))
            else:
                self._iter_rows_d = self._put_rows(
                    np.zeros((self._meshrows.n_dev, 8), np.int32)
                )
            return self._iter_rows_d, self._iter_layout
        if self._meshrows is None:
            segments = []
            layout = []
            off = 0
            for k in present:
                rows = np.nonzero(kind_h[: self._R] == int(k))[0]
                Lb = ceil_to(len(rows), 8)
                segments.append(np.resize(rows, Lb).astype(np.int32))
                layout.append((int(k), off, Lb))
                off += Lb
            self._iter_layout = tuple(layout)
            self._iter_rows_d = self._put(np.concatenate(segments))
            return self._iter_rows_d, self._iter_layout
        from doorman_tpu.solver.resident_mesh import (
            group_by_shard,
            pad_shard_indices,
        )

        n_dev = self._meshrows.n_dev
        Rl = self._Rp // n_dev
        blocks = []
        layout = []
        off = 0
        for k in present:
            rows = np.nonzero(kind_h[: self._R] == int(k))[0].astype(
                np.int64
            )
            owner = rows // Rl
            counts, (loc,) = group_by_shard(
                owner, n_dev, [rows - owner * Rl]
            )
            Lb = ceil_to(int(counts.max()) if len(rows) else 1, 8)
            blocks.append(
                pad_shard_indices(counts, Lb, loc).astype(np.int32)
            )
            layout.append((int(k), off, Lb))
            off += Lb
        self._iter_layout = tuple(layout)
        self._iter_rows_d = self._put_rows(
            np.concatenate(blocks, axis=1)
        )
        return self._iter_rows_d, self._iter_layout

    # -- the tick executable ------------------------------------------

    def _use_pallas(self) -> bool:
        """The pallas row-tile kernels serve f32 tables on a TPU."""
        from doorman_tpu.solver.batch import _committed_platform

        return (
            _committed_platform(self._wants) == "tpu"
            and self._dtype == np.float32
        )

    def _tick_fn_mesh(self, Da: int, Df: int, Sb: int, lanes: frozenset,
                      ilayout: tuple = ()):
        """The shard_mapped tick: tables row-sharded over the mesh,
        staged blocks pre-partitioned per shard (leading device axis),
        no collectives (rows are independent). Scatter indices are
        shard-LOCAL; padded scatter slots carry the out-of-range index
        Rl and drop, padded gather slots repeat a valid index and are
        sliced off at collect."""
        track = self._track_deltas
        key = ("mesh", Da, Df, Sb, self._kfill, lanes, track, ilayout)
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        import jax.numpy as jnp
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from doorman_tpu.parallel.compat import shard_map
        from doorman_tpu.solver.dense import DenseBatch, solve_dense

        use_pallas = self._use_pallas()
        if use_pallas:
            from doorman_tpu.solver.pallas_dense import solve_dense_pallas

        kfill = self._kfill
        dtype = self._dtype
        out_dtype = self._out_dtype
        axes = self._meshrows.axes
        lane_rows_of = _lane_rows_slicer(ilayout, lanes)

        def _core(wants, has, sub, act, idx, a_w, f_block, f_act, fair,
                  cap, kind, learn, statc):
            # Per-shard staged blocks arrive as [1, ...]; tables and
            # per-row config as this shard's [Rl, ...] block.
            idx = idx[0]
            a_idx = idx[:Da]
            f_idx = idx[Da:Da + Df]
            sel_idx = idx[Da + Df:]
            # Wants blocks may arrive bf16 (exact-round-trip compact
            # upload); the cast back is the identity then.
            wants = wants.at[a_idx, :kfill].set(
                a_w[0].astype(dtype), mode="drop"
            )
            has = has.at[f_idx, :kfill].set(f_block[0, 0], mode="drop")
            sub = sub.at[f_idx, :kfill].set(f_block[0, 1], mode="drop")
            act = act.at[f_idx, :kfill].set(f_act[0], mode="drop")
            batch = DenseBatch(
                wants=wants, has=has, subclients=sub, active=act,
                capacity=cap, algo_kind=kind, learning=learn,
                static_capacity=statc,
            )
            if use_pallas:
                gets = self._traced(solve_dense_pallas)(batch)
            else:
                gets = solve_dense(
                    batch, lanes=lanes, lane_rows=lane_rows_of(fair[0]),
                )
            out = jnp.take(
                gets, sel_idx, axis=0, mode="clip",
                indices_are_sorted=True,
            )[:, :kfill].astype(out_dtype)
            return wants, gets, sub, act, out, sel_idx

        rowk = P(axes, None)
        row = P(axes)
        dev2 = P(axes, None, None)
        in_specs_tail = (
            rowk,  # fused idx [n_dev, Da+Df+Sb]
            dev2,  # a_w [n_dev, Da, kfill]
            P(axes, None, None, None),  # f_block [n_dev, 2, Df, kfill]
            dev2,  # f_act [n_dev, Df, kfill]
            rowk,  # iter-lane rows [n_dev, ΣLb] (shard-local)
            row, row, row, row,  # per-row config
        )

        if track:
            # Delta tracking, shard-local: every shard compares its own
            # delivery slots against its slice of the prev-grants table
            # (padded gather slots repeat a real index — their compare
            # result is sliced off with them at collect).
            def body(wants, has, sub, act, prev, idx, a_w, f_block,
                     f_act, fair, cap, kind, learn, statc):
                wants, gets, sub, act, out, sel_idx = _core(
                    wants, has, sub, act, idx, a_w, f_block, f_act,
                    fair, cap, kind, learn, statc,
                )
                prev_sel = jnp.take(
                    prev, sel_idx, axis=0, mode="clip",
                    indices_are_sorted=True,
                )[:, :kfill]
                changed = (out != prev_sel).any(axis=1)
                prev = prev.at[sel_idx, :kfill].set(out, mode="drop")
                return wants, gets, sub, act, prev, out[None], changed[None]

            mapped = shard_map(
                body,
                mesh=self._mesh,
                in_specs=(rowk, rowk, rowk, rowk, rowk) + in_specs_tail,
                out_specs=(
                    rowk, rowk, rowk, rowk, rowk, dev2, P(axes, None),
                ),
            )

            @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
            def tick(*args):
                return mapped(*args)
        else:
            def body(wants, has, sub, act, idx, a_w, f_block, f_act,
                     fair, cap, kind, learn, statc):
                wants, gets, sub, act, out, _ = _core(
                    wants, has, sub, act, idx, a_w, f_block, f_act,
                    fair, cap, kind, learn, statc,
                )
                return wants, gets, sub, act, out[None]

            mapped = shard_map(
                body,
                mesh=self._mesh,
                in_specs=(rowk, rowk, rowk, rowk) + in_specs_tail,
                out_specs=(rowk, rowk, rowk, rowk, dev2),
            )

            @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
            def tick(*args):
                return mapped(*args)

        self._tick_fns[key] = tick
        return tick

    def _tick_fn(self, Da: int, Df: int, Sb: int, lanes: frozenset,
                 ilayout: tuple = ()):
        track = self._track_deltas
        key = (Da, Df, Sb, self._kfill, lanes, track, ilayout)
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        from functools import partial

        from doorman_tpu.solver.dense import DenseBatch, solve_dense

        use_pallas = self._use_pallas()
        if use_pallas:
            from doorman_tpu.solver.pallas_dense import solve_dense_pallas

        kfill = self._kfill
        dtype = self._dtype
        out_dtype = self._out_dtype
        lane_rows_of = _lane_rows_slicer(ilayout, lanes)

        # Scatters touch only the first `kfill` lanes: the table is
        # zeroed beyond every row's count at rebuild and `kfill` never
        # shrinks between rebuilds, so lanes >= kfill stay inactive.
        # Wants-only rows (`a_*`, the steady-state churn) ship just the
        # wants lane; rows whose shape changed (`f_*`: membership, has,
        # subclients) ship everything. One fused int32 index upload
        # carries all three index sets: each transfer op has a fixed
        # cost on top of its bytes.
        def _core(wants, has, sub, act, idx, a_w, f_block, f_act, fair,
                  cap, kind, learn, statc):
            a_idx = idx[:Da]
            f_idx = idx[Da:Da + Df]
            sel_idx = idx[Da + Df:]
            # a_w may arrive bf16 (compact upload): cast is identity.
            wants = wants.at[a_idx, :kfill].set(a_w.astype(dtype))
            has = has.at[f_idx, :kfill].set(f_block[0])
            sub = sub.at[f_idx, :kfill].set(f_block[1])
            act = act.at[f_idx, :kfill].set(f_act)
            batch = DenseBatch(
                wants=wants, has=has, subclients=sub, active=act,
                capacity=cap, algo_kind=kind, learning=learn,
                static_capacity=statc,
            )
            if use_pallas:
                gets = self._traced(solve_dense_pallas)(batch)
            else:
                gets = solve_dense(
                    batch, lanes=lanes, lane_rows=lane_rows_of(fair),
                )
            # `gets` IS the next tick's has: grants chain on device
            # (learning rows replay has, so the chain preserves them;
            # inactive lanes solve to 0).
            out = gets[sel_idx, :kfill].astype(out_dtype)
            return wants, gets, sub, act, out, sel_idx

        if track:
            # Delta tracking: compare the delivered rows against the
            # resident previous-grants table ON DEVICE and update it in
            # place (donated like the demand tables); the host downloads
            # a [Sb] bool mask, never the prev rows.
            @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
            def tick(wants, has, sub, act, prev, idx, a_w, f_block,
                     f_act, fair, cap, kind, learn, statc):
                wants, gets, sub, act, out, sel_idx = _core(
                    wants, has, sub, act, idx, a_w, f_block, f_act,
                    fair, cap, kind, learn, statc,
                )
                changed = (out != prev[sel_idx, :kfill]).any(axis=1)
                prev = prev.at[sel_idx, :kfill].set(out)
                return wants, gets, sub, act, prev, out, changed
        else:
            @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
            def tick(wants, has, sub, act, idx, a_w, f_block, f_act,
                     fair, cap, kind, learn, statc):
                wants, gets, sub, act, out, _ = _core(
                    wants, has, sub, act, idx, a_w, f_block, f_act,
                    fair, cap, kind, learn, statc,
                )
                return wants, gets, sub, act, out

        self._tick_fns[key] = tick
        return tick

    def _tick_fn_fused(self, Da: int, Df: int, Sb: int, lanes: frozenset,
                       use_bf16: bool, ilayout: tuple = ()):
        """The one-launch fused tick: the staged blocks arrive as ONE
        uint8 buffer (packed host-side in `_launch`), bitcast apart
        in-program at static offsets, scattered, solved, delta-compared
        — and in tracked mode the changed mask is packed INTO the
        delivered slab so grants and mask land in one download stream.
        Every per-block op is byte-for-byte the round-trip executable's
        (same scatters, same solve, same compare); only the transfer
        packing differs, which is what makes fused-vs-unfused byte
        identity hold by construction (pinned by tests/test_fused_tick
        .py). On TPU the solve+delta run in the fused pallas row-tile
        kernel (pallas_dense.fused_tick_pallas): one VMEM pass per row
        tile instead of XLA re-reading gets/prev from HBM."""
        track = self._track_deltas
        # The bf16 flag stays LAST in the narrow fused keys (pinned by
        # tests/test_fused_tick.py's both-encodings check).
        key = (
            "fused", Da, Df, Sb, self._kfill, lanes, track, ilayout,
            use_bf16,
        )
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        import jax.numpy as jnp
        from functools import partial

        from doorman_tpu.solver.dense import DenseBatch, solve_dense

        use_pallas = self._use_pallas()
        if use_pallas:
            from doorman_tpu.solver.pallas_dense import (
                fused_tick_pallas,
                solve_dense_pallas,
            )

        kfill = self._kfill
        dtype = self._dtype
        jdtype = jnp.dtype(dtype)
        out_dtype = self._out_dtype
        itemsize = int(np.dtype(dtype).itemsize)
        aw_item = 2 if use_bf16 else itemsize
        # Static buffer layout (byte offsets; assembly order matches
        # `_pack_fused_buffer`): fused int32 index vector, wants-only
        # block (bf16 when the round trip is exact), full-row
        # has/subclients block, active flags as raw uint8 last (no
        # alignment constraint).
        n_idx = (Da + Df + Sb) * 4
        n_aw = Da * kfill * aw_item
        n_fb = 2 * Df * kfill * itemsize
        Mb = -(-Sb // kfill)  # changed-mask rows appended to the slab
        lane_rows_of = _lane_rows_slicer(ilayout, lanes)

        def unpack(buf):
            idx = jax.lax.bitcast_convert_type(
                buf[:n_idx].reshape(-1, 4), jnp.int32
            )
            o = n_idx
            a_w = jax.lax.bitcast_convert_type(
                buf[o : o + n_aw].reshape(-1, aw_item),
                jnp.bfloat16 if use_bf16 else jdtype,
            ).reshape(Da, kfill)
            o += n_aw
            f_block = jax.lax.bitcast_convert_type(
                buf[o : o + n_fb].reshape(-1, itemsize), jdtype
            ).reshape(2, Df, kfill)
            o += n_fb
            f_act = (buf[o : o + Df * kfill] != 0).reshape(Df, kfill)
            return idx, a_w, f_block, f_act

        def stage_and_batch(wants, has, sub, act, buf, cap, kind,
                            learn, statc):
            idx, a_w, f_block, f_act = unpack(buf)
            a_idx = idx[:Da]
            f_idx = idx[Da : Da + Df]
            sel_idx = idx[Da + Df :]
            wants = wants.at[a_idx, :kfill].set(a_w.astype(dtype))
            has = has.at[f_idx, :kfill].set(f_block[0])
            sub = sub.at[f_idx, :kfill].set(f_block[1])
            act = act.at[f_idx, :kfill].set(f_act)
            batch = DenseBatch(
                wants=wants, has=has, subclients=sub, active=act,
                capacity=cap, algo_kind=kind, learning=learn,
                static_capacity=statc,
            )
            return wants, sub, act, batch, sel_idx

        if track:
            @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
            def tick(wants, has, sub, act, prev, buf, fair, cap, kind,
                     learn, statc):
                wants, sub, act, batch, sel_idx = stage_and_batch(
                    wants, has, sub, act, buf, cap, kind, learn, statc
                )
                if use_pallas:
                    # Delivered-row mask from the gather set: padding
                    # slots repeat real rows, so duplicate scatters
                    # write the same 1.
                    delivered = (
                        jnp.zeros(batch.wants.shape[0], dtype)
                        .at[sel_idx]
                        .set(jnp.ones((), dtype))
                    )
                    gets, prev, changed_rows = self._traced(fused_tick_pallas)(
                        batch, prev, delivered
                    )
                    out = gets[sel_idx, :kfill].astype(out_dtype)
                    changed = changed_rows[sel_idx]
                else:
                    gets = solve_dense(
                        batch, lanes=lanes, lane_rows=lane_rows_of(fair),
                    )
                    out = gets[sel_idx, :kfill].astype(out_dtype)
                    changed = (out != prev[sel_idx, :kfill]).any(axis=1)
                    prev = prev.at[sel_idx, :kfill].set(out)
                mask = jnp.pad(
                    changed.astype(out_dtype), (0, Mb * kfill - Sb)
                ).reshape(Mb, kfill)
                slab = jnp.concatenate([out, mask], axis=0)
                return wants, gets, sub, act, prev, slab
        else:
            @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
            def tick(wants, has, sub, act, buf, fair, cap, kind, learn,
                     statc):
                wants, sub, act, batch, sel_idx = stage_and_batch(
                    wants, has, sub, act, buf, cap, kind, learn, statc
                )
                if use_pallas:
                    gets = self._traced(solve_dense_pallas)(batch)
                else:
                    gets = solve_dense(
                        batch, lanes=lanes, lane_rows=lane_rows_of(fair),
                    )
                out = gets[sel_idx, :kfill].astype(out_dtype)
                return wants, gets, sub, act, out

        self._tick_fns[key] = tick
        return tick

    def _tick_fn_fused_scoped(self, Da: int, Df: int, Sb: int, Cb: int,
                              clayout: tuple, lanes: frozenset,
                              use_bf16: bool):
        """The scoped fused tick: staging scatters run over the full
        resident tables exactly as in `_tick_fn_fused`, then the scope
        rows (a separate cached int32 buffer: [Cb] row indices + one
        padded segment of compact iterative-lane positions per
        ITERATIVE_KINDS lane present, laid out by `clayout`) gather
        into a pow2-bucketed compact [Cb, K] table, ALL lanes solve
        over the compact table (each iterative fill restricted to its
        own compact positions), and the fresh grants scatter back into
        the donated resident grant slab — rows outside the scope keep
        their resident fixpoint grants untouched. Delivery gathers from the updated
        slab, so the delivered bytes (and the delta compare against
        the prev table) are byte-identical to the full solve whenever
        the scope holds every unit not at its fixpoint — the invariant
        ScopeTracker maintains (doc/design.md "Churn-proportional
        solve"). The per-scope-row solve-moved mask (gets != input has,
        in the solve dtype — the fixpoint test) packs into the slab
        after the changed mask, so the frontier feedback rides the one
        delivery download. Padding scope slots point at the reserved
        padding row: duplicates gather identical inputs and scatter
        identical values."""
        track = self._track_deltas
        key = (
            "fused_scoped", Da, Df, Sb, Cb, clayout, self._kfill, lanes,
            track, use_bf16,
        )
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        import jax.numpy as jnp
        from functools import partial

        from doorman_tpu.solver.dense import DenseBatch, solve_dense

        use_pallas = self._use_pallas()
        if use_pallas:
            from doorman_tpu.solver.pallas_dense import solve_dense_pallas

        kfill = self._kfill
        dtype = self._dtype
        jdtype = jnp.dtype(dtype)
        out_dtype = self._out_dtype
        itemsize = int(np.dtype(dtype).itemsize)
        aw_item = 2 if use_bf16 else itemsize
        n_idx = (Da + Df + Sb) * 4
        n_aw = Da * kfill * aw_item
        n_fb = 2 * Df * kfill * itemsize
        Mb = -(-Sb // kfill)  # changed-mask rows (tracked mode)
        Mv = -(-Cb // kfill)  # solve-moved mask rows
        lane_rows_of = _lane_rows_slicer(clayout, lanes)

        def unpack(buf):
            idx = jax.lax.bitcast_convert_type(
                buf[:n_idx].reshape(-1, 4), jnp.int32
            )
            o = n_idx
            a_w = jax.lax.bitcast_convert_type(
                buf[o : o + n_aw].reshape(-1, aw_item),
                jnp.bfloat16 if use_bf16 else jdtype,
            ).reshape(Da, kfill)
            o += n_aw
            f_block = jax.lax.bitcast_convert_type(
                buf[o : o + n_fb].reshape(-1, itemsize), jdtype
            ).reshape(2, Df, kfill)
            o += n_fb
            f_act = (buf[o : o + Df * kfill] != 0).reshape(Df, kfill)
            return idx, a_w, f_block, f_act

        def stage_and_solve(wants, has, sub, act, buf, scope_buf, cap,
                            kind, learn, statc):
            idx, a_w, f_block, f_act = unpack(buf)
            a_idx = idx[:Da]
            f_idx = idx[Da : Da + Df]
            sel_idx = idx[Da + Df :]
            wants = wants.at[a_idx, :kfill].set(a_w.astype(dtype))
            has = has.at[f_idx, :kfill].set(f_block[0])
            sub = sub.at[f_idx, :kfill].set(f_block[1])
            act = act.at[f_idx, :kfill].set(f_act)
            scope = scope_buf[:Cb]
            iterpos = scope_buf[Cb:]
            h_c = has[scope]
            batch = DenseBatch(
                wants=wants[scope], has=h_c, subclients=sub[scope],
                active=act[scope], capacity=cap[scope],
                algo_kind=kind[scope], learning=learn[scope],
                static_capacity=statc[scope],
            )
            if use_pallas:
                gets_c = self._traced(solve_dense_pallas)(batch)
            else:
                gets_c = solve_dense(
                    batch, lanes=lanes, lane_rows=lane_rows_of(iterpos),
                )
            # The fixpoint test, in the solve dtype: a scope row whose
            # fresh solve equals its input has is back at rest.
            moved = (gets_c != h_c).any(axis=1)
            has = has.at[scope].set(gets_c)
            out = has[sel_idx, :kfill].astype(out_dtype)
            return wants, has, sub, act, out, sel_idx, moved

        def moved_mask_rows(moved):
            return jnp.pad(
                moved.astype(out_dtype), (0, Mv * kfill - Cb)
            ).reshape(Mv, kfill)

        if track:
            @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
            def tick(wants, has, sub, act, prev, buf, scope_buf, cap,
                     kind, learn, statc):
                wants, has, sub, act, out, sel_idx, moved = (
                    stage_and_solve(
                        wants, has, sub, act, buf, scope_buf, cap,
                        kind, learn, statc,
                    )
                )
                changed = (out != prev[sel_idx, :kfill]).any(axis=1)
                prev = prev.at[sel_idx, :kfill].set(out)
                mask = jnp.pad(
                    changed.astype(out_dtype), (0, Mb * kfill - Sb)
                ).reshape(Mb, kfill)
                slab = jnp.concatenate(
                    [out, mask, moved_mask_rows(moved)], axis=0
                )
                return wants, has, sub, act, prev, slab
        else:
            @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
            def tick(wants, has, sub, act, buf, scope_buf, cap, kind,
                     learn, statc):
                wants, has, sub, act, out, _, moved = stage_and_solve(
                    wants, has, sub, act, buf, scope_buf, cap, kind,
                    learn, statc,
                )
                slab = jnp.concatenate(
                    [out, moved_mask_rows(moved)], axis=0
                )
                return wants, has, sub, act, slab

        self._tick_fns[key] = tick
        return tick

    def _tick_fn_mesh_fused_scoped(self, Da: int, Df: int, Sb: int,
                                   Cb: int, clayout: tuple,
                                   lanes: frozenset, use_bf16: bool):
        """Mesh variant of the scoped fused tick: each shard gathers
        its OWN scoped rows (the per-shard scoped extent: shard-local
        indices in its slice of the cached scope buffer, padded with
        the out-of-range index Rl so padded slots gather-clip and
        scatter-drop), solves the compact per-shard block, and
        scatters back into its resident slab — rows are independent,
        so no collective is needed and per-row bits match the
        single-device compact solve. The solve-moved mask lands as a
        separate [n_dev, Cb] output (the mesh delivery already lands
        grants and changed mask as separate per-shard streams)."""
        track = self._track_deltas
        key = (
            "fused_mesh_scoped", Da, Df, Sb, Cb, clayout, self._kfill,
            lanes, track, use_bf16,
        )
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        import jax.numpy as jnp
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from doorman_tpu.parallel.compat import shard_map
        from doorman_tpu.solver.dense import DenseBatch, solve_dense

        use_pallas = self._use_pallas()
        if use_pallas:
            from doorman_tpu.solver.pallas_dense import solve_dense_pallas

        kfill = self._kfill
        dtype = self._dtype
        jdtype = jnp.dtype(dtype)
        out_dtype = self._out_dtype
        axes = self._meshrows.axes
        itemsize = int(np.dtype(dtype).itemsize)
        aw_item = 2 if use_bf16 else itemsize
        n_idx = (Da + Df + Sb) * 4
        n_aw = Da * kfill * aw_item
        n_fb = 2 * Df * kfill * itemsize
        lane_rows_of = _lane_rows_slicer(clayout, lanes)

        def unpack(buf):
            idx = jax.lax.bitcast_convert_type(
                buf[:n_idx].reshape(-1, 4), jnp.int32
            )
            o = n_idx
            a_w = jax.lax.bitcast_convert_type(
                buf[o : o + n_aw].reshape(-1, aw_item),
                jnp.bfloat16 if use_bf16 else jdtype,
            ).reshape(Da, kfill)
            o += n_aw
            f_block = jax.lax.bitcast_convert_type(
                buf[o : o + n_fb].reshape(-1, itemsize), jdtype
            ).reshape(2, Df, kfill)
            o += n_fb
            f_act = (buf[o : o + Df * kfill] != 0).reshape(Df, kfill)
            return idx, a_w, f_block, f_act

        def _core(wants, has, sub, act, buf, scope_buf, cap, kind,
                  learn, statc):
            idx, a_w, f_block, f_act = unpack(buf[0])
            a_idx = idx[:Da]
            f_idx = idx[Da : Da + Df]
            sel_idx = idx[Da + Df :]
            wants = wants.at[a_idx, :kfill].set(
                a_w.astype(dtype), mode="drop"
            )
            has = has.at[f_idx, :kfill].set(f_block[0], mode="drop")
            sub = sub.at[f_idx, :kfill].set(f_block[1], mode="drop")
            act = act.at[f_idx, :kfill].set(f_act, mode="drop")
            sb = scope_buf[0]
            scope = sb[:Cb]
            iterpos = sb[Cb:]

            def take_rows(tbl):
                return jnp.take(
                    tbl, scope, axis=0, mode="clip",
                    indices_are_sorted=True,
                )

            h_c = take_rows(has)
            batch = DenseBatch(
                wants=take_rows(wants), has=h_c,
                subclients=take_rows(sub), active=take_rows(act),
                capacity=jnp.take(cap, scope, mode="clip"),
                algo_kind=jnp.take(kind, scope, mode="clip"),
                learning=jnp.take(learn, scope, mode="clip"),
                static_capacity=jnp.take(statc, scope, mode="clip"),
            )
            if use_pallas:
                gets_c = self._traced(solve_dense_pallas)(batch)
            else:
                gets_c = solve_dense(
                    batch, lanes=lanes, lane_rows=lane_rows_of(iterpos),
                )
            moved = (gets_c != h_c).any(axis=1)
            has = has.at[scope].set(gets_c, mode="drop")
            out = jnp.take(
                has, sel_idx, axis=0, mode="clip",
                indices_are_sorted=True,
            )[:, :kfill].astype(out_dtype)
            return wants, has, sub, act, out, sel_idx, moved

        rowk = P(axes, None)
        row = P(axes)
        dev2 = P(axes, None, None)
        in_specs_tail = (
            row,  # fused uint8 buffer [n_dev, B]
            rowk,  # scope buffer [n_dev, Cb + ΣLb] (shard-local)
            row, row, row, row,  # per-row config
        )

        if track:
            def body(wants, has, sub, act, prev, buf, scope_buf, cap,
                     kind, learn, statc):
                wants, has, sub, act, out, sel_idx, moved = _core(
                    wants, has, sub, act, buf, scope_buf, cap, kind,
                    learn, statc,
                )
                prev_sel = jnp.take(
                    prev, sel_idx, axis=0, mode="clip",
                    indices_are_sorted=True,
                )[:, :kfill]
                changed = (out != prev_sel).any(axis=1)
                prev = prev.at[sel_idx, :kfill].set(out, mode="drop")
                return (
                    wants, has, sub, act, prev, out[None],
                    changed[None], moved[None],
                )

            mapped = shard_map(
                body,
                mesh=self._mesh,
                in_specs=(rowk, rowk, rowk, rowk, rowk) + in_specs_tail,
                out_specs=(
                    rowk, rowk, rowk, rowk, rowk, dev2,
                    P(axes, None), P(axes, None),
                ),
            )

            @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
            def tick(*args):
                return mapped(*args)
        else:
            def body(wants, has, sub, act, buf, scope_buf, cap, kind,
                     learn, statc):
                wants, has, sub, act, out, _, moved = _core(
                    wants, has, sub, act, buf, scope_buf, cap, kind,
                    learn, statc,
                )
                return wants, has, sub, act, out[None], moved[None]

            mapped = shard_map(
                body,
                mesh=self._mesh,
                in_specs=(rowk, rowk, rowk, rowk) + in_specs_tail,
                out_specs=(
                    rowk, rowk, rowk, rowk, dev2, P(axes, None),
                ),
            )

            @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
            def tick(*args):
                return mapped(*args)

        self._tick_fns[key] = tick
        return tick

    def _tick_fn_mesh_fused(self, Da: int, Df: int, Sb: int,
                            lanes: frozenset, use_bf16: bool,
                            ilayout: tuple = ()):
        """Mesh variant of the fused upload: each shard's staged
        blocks arrive as one [1, B] uint8 slice of the sharded buffer
        and bitcast apart in-shard; the solve/delta body is the mesh
        round-trip executable's. The delivery keeps the mesh output
        layout (per-shard [Sb, kfill] blocks + separate changed mask):
        the upload side is where the mesh tick pays per-block
        dispatches, the download is already one stream per shard."""
        track = self._track_deltas
        key = (
            "fused_mesh", Da, Df, Sb, self._kfill, lanes, track,
            ilayout, use_bf16,
        )
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        import jax.numpy as jnp
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from doorman_tpu.parallel.compat import shard_map
        from doorman_tpu.solver.dense import DenseBatch, solve_dense

        use_pallas = self._use_pallas()
        if use_pallas:
            from doorman_tpu.solver.pallas_dense import solve_dense_pallas

        kfill = self._kfill
        dtype = self._dtype
        jdtype = jnp.dtype(dtype)
        out_dtype = self._out_dtype
        axes = self._meshrows.axes
        itemsize = int(np.dtype(dtype).itemsize)
        aw_item = 2 if use_bf16 else itemsize
        n_idx = (Da + Df + Sb) * 4
        n_aw = Da * kfill * aw_item
        n_fb = 2 * Df * kfill * itemsize
        lane_rows_of = _lane_rows_slicer(ilayout, lanes)

        def unpack(buf):
            idx = jax.lax.bitcast_convert_type(
                buf[:n_idx].reshape(-1, 4), jnp.int32
            )
            o = n_idx
            a_w = jax.lax.bitcast_convert_type(
                buf[o : o + n_aw].reshape(-1, aw_item),
                jnp.bfloat16 if use_bf16 else jdtype,
            ).reshape(Da, kfill)
            o += n_aw
            f_block = jax.lax.bitcast_convert_type(
                buf[o : o + n_fb].reshape(-1, itemsize), jdtype
            ).reshape(2, Df, kfill)
            o += n_fb
            f_act = (buf[o : o + Df * kfill] != 0).reshape(Df, kfill)
            return idx, a_w, f_block, f_act

        def _core(wants, has, sub, act, buf, fair, cap, kind, learn,
                  statc):
            idx, a_w, f_block, f_act = unpack(buf[0])
            a_idx = idx[:Da]
            f_idx = idx[Da : Da + Df]
            sel_idx = idx[Da + Df :]
            wants = wants.at[a_idx, :kfill].set(
                a_w.astype(dtype), mode="drop"
            )
            has = has.at[f_idx, :kfill].set(f_block[0], mode="drop")
            sub = sub.at[f_idx, :kfill].set(f_block[1], mode="drop")
            act = act.at[f_idx, :kfill].set(f_act, mode="drop")
            batch = DenseBatch(
                wants=wants, has=has, subclients=sub, active=act,
                capacity=cap, algo_kind=kind, learning=learn,
                static_capacity=statc,
            )
            if use_pallas:
                gets = self._traced(solve_dense_pallas)(batch)
            else:
                gets = solve_dense(
                    batch, lanes=lanes, lane_rows=lane_rows_of(fair[0]),
                )
            out = jnp.take(
                gets, sel_idx, axis=0, mode="clip",
                indices_are_sorted=True,
            )[:, :kfill].astype(out_dtype)
            return wants, gets, sub, act, out, sel_idx

        rowk = P(axes, None)
        row = P(axes)
        dev2 = P(axes, None, None)
        in_specs_tail = (
            row,  # fused uint8 buffer [n_dev, B]
            rowk,  # iter-lane rows [n_dev, ΣLb] (shard-local)
            row, row, row, row,  # per-row config
        )

        if track:
            def body(wants, has, sub, act, prev, buf, fair, cap, kind,
                     learn, statc):
                wants, gets, sub, act, out, sel_idx = _core(
                    wants, has, sub, act, buf, fair, cap, kind, learn,
                    statc,
                )
                prev_sel = jnp.take(
                    prev, sel_idx, axis=0, mode="clip",
                    indices_are_sorted=True,
                )[:, :kfill]
                changed = (out != prev_sel).any(axis=1)
                prev = prev.at[sel_idx, :kfill].set(out, mode="drop")
                return wants, gets, sub, act, prev, out[None], changed[None]

            mapped = shard_map(
                body,
                mesh=self._mesh,
                in_specs=(rowk, rowk, rowk, rowk, rowk) + in_specs_tail,
                out_specs=(
                    rowk, rowk, rowk, rowk, rowk, dev2, P(axes, None),
                ),
            )

            @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
            def tick(*args):
                return mapped(*args)
        else:
            def body(wants, has, sub, act, buf, fair, cap, kind, learn,
                     statc):
                wants, gets, sub, act, out, _ = _core(
                    wants, has, sub, act, buf, fair, cap, kind, learn,
                    statc,
                )
                return wants, gets, sub, act, out[None]

            mapped = shard_map(
                body,
                mesh=self._mesh,
                in_specs=(rowk, rowk, rowk, rowk) + in_specs_tail,
                out_specs=(rowk, rowk, rowk, rowk, dev2),
            )

            @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
            def tick(*args):
                return mapped(*args)

        self._tick_fns[key] = tick
        return tick

    # -- phases -------------------------------------------------------

    def _drain(self, ph: PhaseRecorder):
        """Drain the engine's dirty-row flags and resolve them to table
        rows; also consumes the admission-fused pack cache (the drained
        set stays authoritative for WHICH rows ship)."""
        dirty_rids, full_flags = self._engine.drain_dirty2()
        if len(dirty_rids):
            lut = self._row_lut
            clamped = np.minimum(dirty_rids, len(lut) - 1)
            rows_all = lut[clamped]
            oob = dirty_rids != clamped
            if oob.any():
                # Rids above the LUT are resources registered after the
                # last rebuild (wide/priority rows sharing the engine);
                # they must resolve to "not ours" through the reserved
                # trailing -1 slot. A clamped rid landing on a REAL row
                # would silently misattribute another resource's writes
                # to our last row — loud, never silent.
                aliased = rows_all[oob] >= 0
                if aliased.any():
                    detail = {
                        "oob_rids": np.asarray(dirty_rids[oob][:8]).tolist(),
                        "lut_size": int(len(lut)),
                        "aliased_rows": np.asarray(
                            rows_all[oob][aliased][:8]
                        ).tolist(),
                    }
                    self._anomaly("dirty_rid_alias", detail)
                    raise AssertionError(
                        "resident row LUT reserved slot is not -1: "
                        f"out-of-range rids would alias live rows {detail}"
                    )
            valid = rows_all >= 0
            dirty_rows = rows_all[valid]
            dirty_full = full_flags[valid].astype(bool)
        else:
            dirty_rows = np.zeros(0, np.int64)
            dirty_full = np.zeros(0, bool)
        if self._staging is not None:
            fused, fwin, frows = self._staging.take()
        else:
            fused, fwin, frows = None, 0, 0
        ph.lap("drain")
        return dirty_rows, dirty_full, fused, fwin, frows

    def _drained_empty(self, drained) -> bool:
        return len(drained[0]) == 0

    def _pack_rows_fused(self, pack_rids: np.ndarray, kfill: int, fused):
        """Pack the given rids at lane width kfill, serving rows from
        the window-time pack cache where a valid entry exists (same
        kfill; see FusedStaging for the freshness contract) and one C
        pack call for the rest. Returns (w, h, s, act, counts,
        versions, rows_hit)."""
        n = len(pack_rids)
        if not fused:
            w, h, s, act, counts, versions = self._engine.pack_rows(
                pack_rids, kfill
            )
            return w, h, s, act, counts, versions, 0
        hit = np.zeros(n, bool)
        entries = []
        for i, rid in enumerate(pack_rids):
            e = fused.get(int(rid))
            if e is not None and e[0] == kfill:
                hit[i] = True
                entries.append(e)
        if not hit.any():
            w, h, s, act, counts, versions = self._engine.pack_rows(
                pack_rids, kfill
            )
            return w, h, s, act, counts, versions, 0
        w = np.zeros((n, kfill), np.float64)
        h = np.zeros((n, kfill), np.float64)
        s = np.zeros((n, kfill), np.float64)
        act = np.zeros((n, kfill), np.uint8)
        counts = np.zeros(n, np.int32)
        versions = np.zeros(n, np.uint64)
        miss = ~hit
        if miss.any():
            mw, mh, ms, mact, mcounts, mversions = self._engine.pack_rows(
                pack_rids[miss], kfill
            )
            w[miss] = mw
            h[miss] = mh
            s[miss] = ms
            act[miss] = mact
            counts[miss] = mcounts
            versions[miss] = mversions
        # One stacked assignment per field (hundreds of cached rows per
        # tick at the bench shape; a per-row loop here would eat the
        # pack time the cache is saving).
        hit_pos = np.nonzero(hit)[0]
        w[hit_pos] = np.stack([e[1] for e in entries])
        h[hit_pos] = np.stack([e[2] for e in entries])
        s[hit_pos] = np.stack([e[3] for e in entries])
        act[hit_pos] = np.stack([e[4] for e in entries])
        counts[hit_pos] = [e[5] for e in entries]
        versions[hit_pos] = [e[6] for e in entries]
        return w, h, s, act, counts, versions, int(hit.sum())

    def stage_rids(self, rids) -> int:
        """Admission-window entry point: pack the given engine rids into
        the fused staging cache at the current lane width (no-op without
        attached staging). Called from the coalescer's grouped pass (or
        the bench's synthetic windows) right after the store writes."""
        if self._staging is None:
            return 0
        return self._staging.stage(rids, self._kfill)

    def _launch(self, res_list, drained, config_changed, now, ph):
        dirty_rows, dirty_full, fused, fwin, frows = drained
        dirty_real = dirty_rows  # pre-sentinel: the frontier entries
        if len(dirty_rows) == 0:
            # No demand changes: scatter the reserved zero padding row.
            dirty_rows = np.asarray([self._R], np.int64)
            dirty_full = np.asarray([False])
        # Full-upload rows first, wants-only rows after; one C pack call
        # at the fill width (no padding lanes cross the host link).
        order = np.concatenate(
            [dirty_rows[dirty_full], dirty_rows[~dirty_full]]
        )
        n_full = int(dirty_full.sum())
        pack_rids = self._rids[order]
        rows_hit = 0
        while True:
            w, h, s, act, counts, versions, rows_hit = (
                self._pack_rows_fused(pack_rids, self._kfill, fused)
            )
            kmax = int(counts.max()) if len(counts) else 0
            if kmax <= self._kfill:
                break
            if ceil_to(kmax, 8) > self._K:
                # Bucket overflow: a resource outgrew the lane width.
                self.rebuild(res_list)
                order = np.asarray([self._R], np.int64)
                n_full = 0
                pack_rids = self._rids[order]
                fused = None
            else:
                # Lane width grows: cached packs (old kfill) no longer
                # fit and are repacked through the miss path.
                self._kfill = min(self._K, ceil_to(kmax, 8))
        # Rows whose membership epoch moved between the drain and the
        # pack are promoted to full uploads: their packed slot order no
        # longer matches the device tables' act/sub/has lanes.
        is_full = np.zeros(len(order), bool)
        is_full[:n_full] = True
        is_full |= versions != self._uploaded_versions[order]
        self._uploaded_versions[order] = versions
        # Solve-mode decision for this tick (after the pack loop, which
        # may have rebuilt): the scoped path solves only the dirty rows
        # plus the host frontier; any escalation reason forces the full
        # executable. A mid-launch rebuild replaced dirty_real's row
        # ids, but its seed_all covers every row anyway.
        scope, _forced = self._scope_for_tick(
            dirty_real, config_changed, self._R
        )
        if scope is not None:
            self.last_scope = {
                "rows": int(len(scope)), "resources": int(len(scope)),
            }
        else:
            self.last_scope = {"rows": self._R, "resources": self._R}
        ph.lap("pack")

        # Delivery set: every dirty row + every config-changed row + the
        # rotation slice — or every row on a rebuild/epoch-moved tick
        # (the rebuild consumed the dirty set, and an epoch move can
        # change any row's grant, so full delivery keeps same-tick
        # freshness for whatever changed; reference semantics: new
        # config applies at the very next decide,
        # go/server/doorman/resource.go:117-140).
        if self._just_rebuilt or config_changed is None:
            self._just_rebuilt = False
            sel = np.arange(max(self._R, 1), dtype=np.int64)
        else:
            rot = self._rotation_rows(
                self._R,
                self._Rp // self._meshrows.n_dev
                if self._meshrows is not None
                else 0,
            )
            parts = [order, rot]
            if len(config_changed):
                # Config rows at/above _R are padding; never deliver them.
                parts.append(config_changed[config_changed < self._R])
            sel = np.unique(np.concatenate(parts))
        n_sel = len(sel)

        if self._meshrows is not None:
            return self._stage_mesh(
                order, is_full, w, h, s, act, sel, now, ph, fwin,
                rows_hit, scope,
            )

        kfill = self._kfill
        dtype = self._dtype
        Da = ceil_to(len(order), 64)
        Df = ceil_to(int(is_full.sum()), 8)
        Sb = ceil_to(n_sel, 256)
        a_pad = np.resize(np.arange(len(order)), Da)
        a_idx = order[a_pad]
        a_w = np.ascontiguousarray(w[a_pad, :kfill]).astype(dtype)
        # Compact upload: the wants-only block (the steady-state bulk of
        # the upload bytes) ships as bf16 when the values round-trip
        # exactly — byte-identical, half (f32) to a quarter (f64) of the
        # bytes. Checked per tick on the host; the executable casts back.
        if _BF16 is not None and bf16_exact(a_w):
            a_w = a_w.astype(_BF16)
        f_pos = np.nonzero(is_full)[0]
        if len(f_pos):
            f_pad = np.resize(f_pos, Df)
            f_idx = order[f_pad]
            f_block = np.stack(
                [h[f_pad, :kfill], s[f_pad, :kfill]]
            ).astype(dtype)
            f_act = np.ascontiguousarray(act[f_pad, :kfill]).astype(bool)
        else:
            # Nothing full-dirty: aim the shape-lane scatter at the
            # reserved padding row with zero data.
            f_idx = np.full(Df, self._R, np.int64)
            f_block = np.zeros((2, Df, kfill), dtype)
            f_act = np.zeros((Df, kfill), bool)
        sel_pad = np.resize(sel, Sb)
        idx_host = np.concatenate([a_idx, f_idx, sel_pad]).astype(np.int32)
        lanes = self._config.lanes()
        iter_d, ilayout = self._iter_rows()
        cfg = self._config
        from doorman_tpu.utils.transfer import start_download

        if self._fused:
            # One-launch fused tick: pack every staged block into one
            # uint8 buffer (the executable bitcasts it apart at static
            # offsets), one placement, one launch, one download stream
            # — with the changed mask packed INTO the delivered slab
            # when delta tracking is on. Byte-identical to the
            # round-trip tail below (same scatters/solve/compare ops).
            use_bf16 = a_w.dtype != dtype
            buf = np.concatenate([
                idx_host.view(np.uint8),
                np.ascontiguousarray(a_w).view(np.uint8).ravel(),
                np.ascontiguousarray(f_block).view(np.uint8).ravel(),
                f_act.view(np.uint8).ravel(),
            ])
            if scope is not None:
                # Scoped staging: the compact gather set (pow2 bucket,
                # clamped at the padded table — a 100%-churn scope
                # must never gather MORE than the full table) plus one
                # padded segment of each iterative lane's positions
                # WITHIN the compact table, one cached int32 buffer.
                # Padding slots repeat the reserved padding row.
                Cb = min(pow2_bucket(len(scope), 8), self._Rp)
                kind_c = self._config.kind_h[scope]
                clayout, pos_segments = _compact_iter_positions(
                    kind_c, lanes
                )
                scope_host = np.full(
                    Cb + sum(e[2] for e in clayout), 0, np.int32
                )
                scope_host[:Cb] = self._R
                scope_host[: len(scope)] = scope
                if pos_segments is not None:
                    scope_host[Cb:] = pos_segments
            ph.lap("staging")
            mask_rows = 0
            moved_rows = 0
            changed_d = None
            if scope is not None:
                tick = self._tick_fn_fused_scoped(
                    Da, Df, Sb, Cb, clayout, lanes, use_bf16
                )
                buf_d = self._put(buf)
                scope_d = self._place_scope(scope_host, self._put)
                moved_rows = -(-Cb // kfill)
                if self._track_deltas:
                    (
                        self._wants, self._has, self._sub, self._act,
                        self._prev, out
                    ) = tick(
                        self._wants, self._has, self._sub, self._act,
                        self._prev, buf_d, scope_d,
                        cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                    )
                    mask_rows = -(-Sb // kfill)
                else:
                    (
                        self._wants, self._has, self._sub, self._act,
                        out
                    ) = tick(
                        self._wants, self._has, self._sub, self._act,
                        buf_d, scope_d,
                        cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                    )
            else:
                tick = self._tick_fn_fused(
                    Da, Df, Sb, lanes, use_bf16, ilayout
                )
                buf_d = self._put(buf)
                if self._track_deltas:
                    (
                        self._wants, self._has, self._sub, self._act,
                        self._prev, out
                    ) = tick(
                        self._wants, self._has, self._sub, self._act,
                        self._prev, buf_d, iter_d,
                        cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                    )
                    mask_rows = -(-Sb // kfill)
                else:
                    (
                        self._wants, self._has, self._sub, self._act,
                        out
                    ) = tick(
                        self._wants, self._has, self._sub, self._act,
                        buf_d, iter_d,
                        cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                    )
            count_launch()
            # One download stream: the fused slab already carries
            # grants + mask contiguously, and a single async copy is
            # the dispatch floor (one stream of ~1MB is bandwidth-
            # bound on a local accelerator either way).
            out = start_download(out, chunks=1)
            ph.lap("fused")
            self.last_fused = {"windows": fwin, "rows": rows_hit}
            return TickHandle(
                out=out,
                sel_rows=sel,
                rids=self._rids[sel],
                versions=self._uploaded_versions[sel],
                keep_has=cfg.learn_h[sel].astype(np.uint8),
                n_sel=n_sel,
                dispatched_at=now,
                fused_windows=fwin,
                fused_rows=rows_hit,
                changed=changed_d,
                mask_rows=mask_rows,
                scope_ids=scope,
                moved_rows=moved_rows,
                seq=self._seq,
            )

        ph.lap("staging")
        put = self._put
        tick = self._tick_fn(Da, Df, Sb, lanes, ilayout)
        staged = (put(idx_host), put(a_w), put(f_block), put(f_act))
        ph.lap("upload")
        idx_d, a_w_d, f_block_d, f_act_d = staged
        changed_d = None
        if self._track_deltas:
            (
                self._wants, self._has, self._sub, self._act,
                self._prev, out, changed_d
            ) = tick(
                self._wants, self._has, self._sub, self._act, self._prev,
                idx_d, a_w_d, f_block_d, f_act_d, iter_d,
                cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
            )
        else:
            (
                self._wants, self._has, self._sub, self._act, out
            ) = tick(
                self._wants, self._has, self._sub, self._act,
                idx_d, a_w_d, f_block_d, f_act_d, iter_d,
                cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
            )
        count_launch()
        # Start the grant download as SEVERAL async streams, so a
        # whole-slab copy does not serialize behind one round-trip. The
        # split costs a few small on-device slice allocations (whether
        # it pays on the v5e is not measured yet).
        out = start_download(out)
        # "solve": the jitted tick call + download kickoff. On the CPU
        # backend this is the synchronous device solve; on TPU it is
        # the (async) launch of it — the device-side time shows in the
        # JAX profiler capture, not here.
        ph.lap("solve")
        self.last_fused = {"windows": fwin, "rows": rows_hit}
        return TickHandle(
            out=out,
            sel_rows=sel,
            rids=self._rids[sel],
            versions=self._uploaded_versions[sel],
            keep_has=cfg.learn_h[sel].astype(np.uint8),
            n_sel=n_sel,
            dispatched_at=now,
            fused_windows=fwin,
            fused_rows=rows_hit,
            changed=changed_d,
        )

    def _stage_mesh(self, order, is_full, w, h, s, act, sel, now, ph,
                    fwin=0, rows_hit=0, scope=None):
        """Mesh tail of the launch: group this tick's row scatters and
        the delivery set by owning shard, stage per-shard blocks (the
        sharded device_put moves only each shard's slice onto its
        device — a dirty row's upload reaches the owning shard and no
        other), run the shard_mapped tick, and start one download
        stream per shard."""
        from doorman_tpu.solver.resident_mesh import (
            group_by_shard,
            pad_shard_blocks,
            pad_shard_indices,
        )
        from doorman_tpu.utils.transfer import start_sharded_download

        mr = self._meshrows
        n_dev = mr.n_dev
        Rl = self._Rp // n_dev
        kfill = self._kfill
        dtype = self._dtype
        n_sel = len(sel)

        owner_a = order // Rl
        counts_a, (a_idx_l, a_w_l) = group_by_shard(
            owner_a, n_dev, [order - owner_a * Rl, w[:, :kfill]]
        )
        f_pos = np.nonzero(is_full)[0]
        rows_f = order[f_pos]
        owner_f = rows_f // Rl
        counts_f, (f_idx_l, f_h_l, f_s_l, f_a_l) = group_by_shard(
            owner_f, n_dev,
            [
                rows_f - owner_f * Rl, h[f_pos, :kfill],
                s[f_pos, :kfill], act[f_pos, :kfill],
            ],
        )
        # sel is sorted, so owners are nondecreasing and the stable
        # grouping preserves sel's order exactly — the handle's global
        # bookkeeping (rids/versions/keep) needs no permutation.
        owner_sel = sel // Rl
        counts_sel, (sel_l,) = group_by_shard(
            owner_sel, n_dev, [sel - owner_sel * Rl]
        )

        Da = ceil_to(int(counts_a.max()), 64)
        Df = ceil_to(int(counts_f.max()) if len(f_pos) else 1, 8)
        Sb = ceil_to(int(counts_sel.max()), 256)
        a_idx_b, a_w_b = pad_shard_blocks(
            counts_a, Da,
            [(a_idx_l, Rl), (a_w_l.astype(dtype), 0)],
        )
        # Compact upload of the wants blocks (see the single-device
        # tail): bf16 when the round trip is exact.
        if _BF16 is not None and bf16_exact(a_w_b):
            a_w_b = a_w_b.astype(_BF16)
        f_idx_b, f_h_b, f_s_b, f_a_b = pad_shard_blocks(
            counts_f, Df,
            [
                (f_idx_l, Rl), (f_h_l.astype(dtype), 0),
                (f_s_l.astype(dtype), 0), (f_a_l.astype(bool), False),
            ],
        )
        f_block = np.stack([f_h_b, f_s_b], axis=1)  # [n_dev, 2, Df, k]
        sel_b = pad_shard_indices(counts_sel, Sb, sel_l)
        idx_host = np.concatenate(
            [a_idx_b, f_idx_b, sel_b], axis=1
        ).astype(np.int32)
        lanes = self._config.lanes()
        iter_d, ilayout = self._iter_rows()
        fused = self._fused
        counts_c = None
        if scope is not None:
            # Per-shard scoped extents: the global (sorted) scope
            # groups into contiguous shard-local blocks; pads carry the
            # out-of-range index Rl (gather-clip / scatter-drop). The
            # compact iterative-lane positions are per shard too: each
            # ITERATIVE_KINDS lane present gets one padded segment of
            # positions within the shard's compact block, all shards
            # sharing one static layout (max bucket across shards).
            owner_c = scope // Rl
            counts_c, (scope_loc,) = group_by_shard(
                owner_c, n_dev, [scope - owner_c * Rl]
            )
            Cb = min(
                pow2_bucket(
                    int(counts_c.max()) if len(scope) else 1, 8
                ),
                Rl,
            )
            scope_blocks = np.full((n_dev, Cb), Rl, np.int32)
            kind_h = self._config.kind_h
            iter_kinds = sorted(ITERATIVE_KINDS & set(lanes))
            pos_locs = {k: [] for k in iter_kinds}
            pos = 0
            for d in range(n_dev):
                c = int(counts_c[d])
                scope_blocks[d, :c] = scope_loc[pos : pos + c]
                kind_c = kind_h[scope[pos : pos + c]]
                for k in iter_kinds:
                    pos_locs[k].append(
                        np.nonzero(kind_c == int(k))[0]
                    )
                pos += c
            clayout = []
            blocks = [scope_blocks]
            off = 0
            for k in iter_kinds:
                Lb = pow2_bucket(
                    max(max(len(p) for p in pos_locs[k]), 1), 8
                )
                blk = np.zeros((n_dev, Lb), np.int32)
                for d, p in enumerate(pos_locs[k]):
                    if len(p):
                        blk[d] = np.resize(p, Lb)
                blocks.append(blk)
                clayout.append((int(k), off, Lb))
                off += Lb
            clayout = tuple(clayout)
            scope_host = np.concatenate(blocks, axis=1)
        if fused:
            # Fused upload: one [n_dev, B] uint8 buffer whose per-shard
            # slice carries that shard's staged blocks back to back
            # (same static layout the fused executable unpacks); the
            # sharded placement moves each shard's bytes to its own
            # device in ONE dispatch instead of four. The delivery keeps
            # the mesh layout (one stream per shard + separate changed
            # mask) — the mesh download is already at its dispatch
            # floor.
            n_dev_ax = idx_host.shape[0]
            buf_host = np.concatenate(
                [
                    idx_host.view(np.uint8).reshape(n_dev_ax, -1),
                    np.ascontiguousarray(a_w_b)
                    .view(np.uint8).reshape(n_dev_ax, -1),
                    np.ascontiguousarray(f_block)
                    .view(np.uint8).reshape(n_dev_ax, -1),
                    f_a_b.view(np.uint8).reshape(n_dev_ax, -1),
                ],
                axis=1,
            )
        ph.lap("staging")

        itemsize = dtype.itemsize
        ph.shard_bytes(
            "upload",
            counts_a * (kfill * itemsize + 4)
            + counts_f * (kfill * (2 * itemsize + 1) + 4)
            + counts_sel * 4,
        )
        ph.shard_bytes(
            "download",
            counts_sel * kfill * np.dtype(self._out_dtype).itemsize,
        )
        put = self._put_rows
        cfg = self._config
        changed_d = None
        moved_d = None
        if fused:
            use_bf16 = a_w_b.dtype != dtype
            buf_d = put(buf_host)
            if scope is not None:
                tick = self._tick_fn_mesh_fused_scoped(
                    Da, Df, Sb, Cb, clayout, lanes, use_bf16
                )
                scope_d = self._place_scope(scope_host, put)
                if self._track_deltas:
                    (
                        self._wants, self._has, self._sub, self._act,
                        self._prev, out, changed_d, moved_d
                    ) = tick(
                        self._wants, self._has, self._sub, self._act,
                        self._prev, buf_d, scope_d,
                        cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                    )
                else:
                    (
                        self._wants, self._has, self._sub, self._act,
                        out, moved_d
                    ) = tick(
                        self._wants, self._has, self._sub, self._act,
                        buf_d, scope_d,
                        cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                    )
            elif self._track_deltas:
                tick = self._tick_fn_mesh_fused(
                    Da, Df, Sb, lanes, use_bf16, ilayout
                )
                (
                    self._wants, self._has, self._sub, self._act,
                    self._prev, out, changed_d
                ) = tick(
                    self._wants, self._has, self._sub, self._act,
                    self._prev, buf_d, iter_d,
                    cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                )
            else:
                tick = self._tick_fn_mesh_fused(
                    Da, Df, Sb, lanes, use_bf16, ilayout
                )
                (
                    self._wants, self._has, self._sub, self._act, out
                ) = tick(
                    self._wants, self._has, self._sub, self._act,
                    buf_d, iter_d,
                    cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                )
            count_launch()
            out = start_sharded_download(out)
            ph.lap("fused")
        else:
            tick = self._tick_fn_mesh(Da, Df, Sb, lanes, ilayout)
            staged = (put(idx_host), put(a_w_b), put(f_block), put(f_a_b))
            ph.lap("upload")
            idx_d, a_w_d, f_block_d, f_a_d = staged
            if self._track_deltas:
                (
                    self._wants, self._has, self._sub, self._act,
                    self._prev, out, changed_d
                ) = tick(
                    self._wants, self._has, self._sub, self._act,
                    self._prev,
                    idx_d, a_w_d, f_block_d, f_a_d, iter_d,
                    cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                )
            else:
                (
                    self._wants, self._has, self._sub, self._act, out
                ) = tick(
                    self._wants, self._has, self._sub, self._act,
                    idx_d, a_w_d, f_block_d, f_a_d, iter_d,
                    cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                )
            count_launch()
            out = start_sharded_download(out)
            ph.lap("solve")
        self.last_fused = {"windows": fwin, "rows": rows_hit}
        return TickHandle(
            out=out,
            sel_rows=sel,
            rids=self._rids[sel],
            versions=self._uploaded_versions[sel],
            keep_has=cfg.learn_h[sel].astype(np.uint8),
            n_sel=n_sel,
            dispatched_at=now,
            shard_counts=counts_sel,
            fused_windows=fwin,
            fused_rows=rows_hit,
            changed=changed_d,
            scope_ids=scope,
            moved=moved_d,
            scope_counts=counts_c,
            seq=self._seq,
        )

    def _apply_grants(self, handle: TickHandle, gets: np.ndarray) -> int:
        return self._engine.apply_dense(
            handle.rids,
            gets,
            handle.keep_has,
            handle.versions,
        )
