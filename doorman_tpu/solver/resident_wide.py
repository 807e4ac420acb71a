"""Device-resident tick solver for WIDE resources (chunked layout).

Doorman's headline use case is ONE shared resource with a huge client
population (/root/reference/doc/design.md:218 — thousands of clients on
a shared resource; the reference solves it with an O(n) loop per
request, /root/reference/go/server/doorman/algorithm.go:213-292). The
narrow resident solver (solver/resident.py) maps one resource to one
device row, which caps per-resource width at the dense bucket cap; this
module removes that cap by letting a resource span CONSECUTIVE rows
("chunks") of the [R, W] table — slot s of a resource based at row b
lives at flat index b*W + s — and solving with the two-level reduction
in solver.dense.solve_chunked.

What crosses the host<->device link per tick (the link is the tick's
bottleneck at 1M leases, and the whole point of this layout):

  staging:  individual dirty SLOTS as one flat 1D scatter (the engine
            tracks dirtiness per slot for chunk-tracked resources, so a
            single client's wants change ships 8 bytes, not a
            million-lease table). Wants-only churn ships just the wants
            value; slots whose shape changed (membership, has,
            subclients) ship all four lanes. Flat slot indices ship as
            int32 when the table fits (engine.compact_index_dtype —
            half the index bytes), and the wants-value block ships bf16
            when that round-trips exactly (engine.bf16_exact).
  solve:    scoped by default to the SEGMENT closure of the dirty
            slots plus the not-yet-converged frontier — every
            straddling chunk of every touched resource gathers into a
            compact table (per-segment lanes couple all of a
            resource's chunks, so the closure is the correctness
            invariant), solves with the exact two-level reduction, and
            scatters back into the resident slab; byte-identical to
            the full solve, which any escalation still runs loudly
            (engine.ScopeTracker). `has` chains on device either way.
            Absent algorithm lanes are skipped via the config mirror's
            static lane mask (solver.lanes — byte-identical by
            construction; the chunked layout keeps the full-table
            water-fill when a FAIR_SHARE segment exists, since a
            segment spans rows).
  delivery: chunk rows being DELIVERED this tick: rows containing
            full-dirty slots (membership / client-reported has — these
            must land in the store promptly), every row of a resource
            whose effective config changed (same-tick config freshness,
            matching the narrow solver and reference
            go/server/doorman/resource.go:117-140), plus a rotating
            slice covering the whole table every `rotate_ticks` ticks.
            Wants-driven grant movement rides the ROTATION rather than
            forcing same-tick delivery: with a shared waterfill level,
            any demand change moves EVERY client's grant, so same-tick
            delivery of "changed" grants would re-download the entire
            table every tick. The rotation bound — every lease's stored
            grant is at most `rotate_ticks` ticks (<= one refresh
            interval) stale — is exactly the information-staleness the
            reference already has (client-reported `has` lags by a
            refresh interval, go/server/doorman/server.go:732-817).
            When the dirty-row set is small it IS delivered same-tick
            (narrow-solver freshness at low churn); a byte budget keeps
            scattered churn from degenerating into full-table delivery.

Tick programs: the staged blocks are keyed on power-of-two buckets of
the tick's dirty counts (Dw wants-only slots from 1,024, Df full slots
from 256, Sb delivered rows from 32), so a backlog meets O(log) shapes
and not one per multiple. A tick whose dirty slots exceed
WIDE_SCATTER_MAX stages the whole table's columns instead (one
fixed-shape merge program over the resident tables, then the floor-sized
tick program with empty blocks), so a backlog of any size meets programs
the warm-up already compiled: per lane set and delivery/scope shape,
Dw takes at most log2(WIDE_SCATTER_MAX / 1024) + 1 values and the merge
program is one per table layout. Every dirty slot is in its tick's
solve on either path.

The fused staging buffer lays each block out by byte plane (byte 0 of
every element, then byte 1, ...), and the program reassembles elements
with shifts: a u8[n, k] -> wider bitcast makes the TPU compile time grow
with n (~0.5 ms a slot), which a planar decode does not.

Write-back safety: chunk membership versions are read after the slot
drain and before the pack (StoreEngine.chunk_versions), so an apply's
expected version can lag the device state but never lead it — a
mid-flight membership change makes the apply skip that chunk and the
re-marked slots re-deliver it next tick.

The stage skeleton and shared chokepoints live in solver/engine.py
(same contract as ResidentDenseSolver); the server runs one of each
when a config mixes narrow and wide resources.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Sequence

import numpy as np

from doorman_tpu.core.resource import Resource
from doorman_tpu.core.snapshot import _bucket
from doorman_tpu.obs.phases import PhaseRecorder
from doorman_tpu.solver.batch import DENSE_MAX_K, _round_rows
from doorman_tpu.solver.engine import (
    TickEngineBase,
    TickHandle,
    bf16_exact,
    compact_index_dtype,
    count_launch,
    pow2_bucket,
)
from doorman_tpu.solver.engine import _BF16

# Most dirty slots (wants-only + full) one tick scatters; beyond it the
# tick stages the whole table (see the module docstring). One bucket:
# the steady tick stages tens of slots, and a backlog past ~65 ms of a
# 1M-client stream takes the whole table's fixed-shape program, so every
# program a tick can meet is met by a short warm-up.
WIDE_SCATTER_MAX = 1024


def _fused_bytes(blocks: Sequence[np.ndarray]) -> np.ndarray:
    """The fused staging buffer: the blocks back to back along the last
    axis, each laid out by byte plane (byte 0 of every element, then
    byte 1, ...). Mesh blocks are [n_dev, n]: one row per shard."""
    parts = []
    for b in blocks:
        b = np.ascontiguousarray(b)
        lead, n = b.shape[:-1], b.shape[-1]
        planes = b.view(np.uint8).reshape(lead + (n, b.dtype.itemsize))
        parts.append(np.swapaxes(planes, -1, -2).reshape(lead + (-1,)))
    return np.concatenate(parts, axis=-1)


def _unpack_fused(buf, layout):
    """Inverse of _fused_bytes for one buffer row: `layout` is
    [(elements, dtype)] in block order; each element is reassembled
    from its byte planes with shifts and a same-width bitcast."""
    import jax
    import jax.numpy as jnp

    out = []
    o = 0
    for n, dt in layout:
        dt = jnp.dtype(dt)
        k = dt.itemsize
        planes = buf[o : o + n * k].reshape(k, n)
        o += n * k
        if dt == jnp.bool_:
            out.append(planes[0] != 0)
            continue
        word_t = jnp.dtype(f"uint{8 * k}")
        word = planes[0].astype(word_t)
        for b in range(1, k):
            word = word | (planes[b].astype(word_t) << (8 * b))
        out.append(jax.lax.bitcast_convert_type(word, dt))
    return out


class WideResidentSolver(TickEngineBase):
    """Steady-state batched ticks for resources wider than the dense
    bucket cap, with the device as the table of record.

    Covers lane-algorithm resources backed by one native StoreEngine;
    the caller partitions: narrow lane resources -> ResidentDenseSolver,
    PRIORITY_BANDS -> BatchSolver priority part, wide lane -> here.
    """

    component = "resident_wide"

    def __init__(
        self,
        engine,
        *,
        dtype=np.float32,
        device=None,
        mesh=None,
        clock: Callable[[], float] = time.time,
        rotate_ticks: "int | None" = None,
        tick_interval: "float | None" = None,
        download_dtype=None,
        chunk_width: "int | None" = None,
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
            config_put=self._put_rep,
            fused=fused,
            scoped=scoped,
        )
        self._W = int(chunk_width or DENSE_MAX_K)
        self._res: List[Resource] = []
        self._S = 0  # segments (resources)
        self._Sp = 8
        self._R = 0  # real chunk rows
        self._Rp = 0  # padded rows
        self._idx_dtype = np.int64  # flat slot indices (compacted at rebuild)
        self._base_row = np.zeros(0, np.int64)  # per segment
        self._n_chunks = np.zeros(0, np.int64)  # per segment
        self._row_rids = np.zeros(0, np.int32)  # per row (-1 padding)
        self._row_chunk = np.zeros(0, np.int32)  # per row (-1 padding)
        self._row_seg_h = np.zeros(0, np.int32)  # per row (Sp-1 padding)
        self._rid_to_seg: Dict[int, int] = {}

        # Device tables (donated through each tick executable).
        self._wants = self._has = self._sub = self._act = None
        self._row_seg_d = None

    # -- build / rebuild ----------------------------------------------

    def rebuild(self, resources: Sequence[Resource]) -> None:
        """Full pack: size the chunk map from live counts, install the
        engine's chunk tracking, and upload every table."""
        res = list(resources)
        self._res = res
        self._S = len(res)
        self._Sp = _bucket(self._S + 1, 8)
        W = self._W
        counts = np.array([len(r.store) for r in res], np.int64)
        self._n_chunks = np.maximum(1, -(-counts // W))
        self._base_row = np.zeros(self._S + 1, np.int64)
        np.cumsum(self._n_chunks, out=self._base_row[1:])
        self._R = int(self._base_row[-1])
        # +1 reserves a padding row for no-op scatters.
        self._Rp = _round_rows(self._R + 1)
        if self._meshrows is not None:
            # Equal chunk-row blocks per shard; fresh per-shard
            # rotation cursors (the old ones indexed the old layout).
            self._Rp = self._meshrows.round_rows(self._Rp)
            self._rotation.reset(self._meshrows.n_dev)
        else:
            self._rotation.reset()
        # Flat device indices (slot s of the segment at row b lives at
        # b*W + s): int32 halves the index-upload bytes when it fits.
        self._idx_dtype = compact_index_dtype((self._Rp + 1) * W)
        self._row_rids = np.full(self._Rp, -1, np.int32)
        self._row_chunk = np.full(self._Rp, -1, np.int32)
        # Padding rows resolve to the reserved padding segment Sp-1
        # (capacity 0, all lanes inactive).
        self._row_seg_h = np.full(self._Rp, self._Sp - 1, np.int32)
        self._rid_to_seg = {}
        for i, r in enumerate(res):
            b, n = self._base_row[i], self._n_chunks[i]
            self._row_rids[b : b + n] = r.store._rid
            self._row_chunk[b : b + n] = np.arange(n, dtype=np.int32)
            self._row_seg_h[b : b + n] = i
            self._rid_to_seg[r.store._rid] = i
        # row_seg must stay sorted for the segment ops' sorted fast
        # path: padding segment Sp-1 >= every real segment. (True by
        # construction; cheap to assert while packing is host-side.)
        assert (np.diff(self._row_seg_h) >= 0).all()

        # Install tracking, then pack. Writes landing between the two
        # calls mark slot dirt that survives to the next drain AND are
        # already included in the pack (it reads live state) — a benign
        # double-upload, never a miss.
        self._engine.chunk_config(
            np.asarray([r.store._rid for r in res], np.int32), W
        )
        w, h, s, act, _filled, versions = self._engine.pack_chunks(
            self._row_rids[: self._R], self._row_chunk[: self._R], W
        )
        dtype = self._dtype
        pad = ((0, self._Rp - self._R), (0, 0))
        self._wants = self._put_rows(np.pad(w, pad).astype(dtype))
        self._has = self._put_rows(np.pad(h, pad).astype(dtype))
        self._sub = self._put_rows(np.pad(s, pad).astype(dtype))
        self._act = self._put_rows(np.pad(act, pad).astype(bool))
        self._row_seg_d = self._put_rows(self._row_seg_h)
        self._config.reset(self._Sp)
        self._refresh_config(res, self._config._epoch, self._clock())
        self._just_rebuilt = True
        self._tick_fns.clear()
        self._drop_scope_cache()

    def _needs_rebuild(self, resources: List[Resource]) -> bool:
        if self._wants is None or len(resources) != self._S or any(
            a is not b for a, b in zip(resources, self._res)
        ):
            return True
        # Growth past the allocated chunks: sized from live counts (one
        # C sums call per wide resource — there are few by nature).
        for i, r in enumerate(self._res):
            if len(r.store) > self._n_chunks[i] * self._W:
                return True
        return False

    # -- the tick executable ------------------------------------------

    def _tick_fn_mesh(self, Dw: int, Df: int, Sb: int, lanes: frozenset):
        """The shard_mapped chunked tick: tables and row_seg row-sharded
        over the mesh, per-segment config replicated, staged slot
        scatters pre-partitioned per shard (shard-LOCAL flat indices;
        padded slots carry the out-of-range index Rl*W and drop).
        Per-segment totals combine with the bit-stable psum reduction
        (parallel.sharded.resident_chunk_reduces), so a resource whose
        chunks straddle a shard boundary reduces to byte-identical
        totals vs the single-device solve_chunked."""
        key = (Dw, Df, Sb, lanes)
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        import jax.numpy as jnp
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from doorman_tpu.parallel.compat import shard_map
        from doorman_tpu.parallel.sharded import resident_chunk_reduces
        from doorman_tpu.solver.lanes import solve_lanes

        mr = self._meshrows
        axes = mr.axes
        Rp, W = self._Rp, self._W
        Rl = Rp // mr.n_dev
        dtype = self._dtype
        out_dtype = self._out_dtype
        # The full row->segment map is a compile-time constant of this
        # executable (rebuilds clear _tick_fns): every shard runs the
        # same segment op over the psum-assembled global row totals.
        segsum, segmax = resident_chunk_reduces(
            self._mesh, self._row_seg_h, self._Sp, Rl
        )

        def body(wants, has, sub, act, row_seg, w_idx, w_val, f_idx,
                 f_w, f_h, f_s, f_a, sel_idx, cap, kind, learn, statc):
            w_idx = w_idx[0]
            f_idx = f_idx[0]
            wants = (
                wants.reshape(-1)
                .at[w_idx].set(w_val[0].astype(dtype), mode="drop")
                .at[f_idx].set(f_w[0], mode="drop")
                .reshape(Rl, W)
            )
            has = (
                has.reshape(-1).at[f_idx].set(f_h[0], mode="drop")
                .reshape(Rl, W)
            )
            sub = (
                sub.reshape(-1).at[f_idx].set(f_s[0], mode="drop")
                .reshape(Rl, W)
            )
            act = (
                act.reshape(-1).at[f_idx].set(f_a[0], mode="drop")
                .reshape(Rl, W)
            )
            gets = solve_lanes(
                wants, has, sub, act, cap, kind, learn, statc,
                segsum=segsum, segmax=segmax,
                expand=lambda totals: totals[row_seg][:, None],
                lanes=lanes,
            )
            out = jnp.take(
                gets, sel_idx[0], axis=0, mode="clip",
                indices_are_sorted=True,
            ).astype(out_dtype)
            return wants, gets, sub, act, out[None]

        rowk = P(axes, None)
        row = P(axes)
        dev = P(axes, None)
        rep = P()
        mapped = shard_map(
            body,
            mesh=self._mesh,
            in_specs=(
                rowk, rowk, rowk, rowk,  # tables
                row,  # row_seg (local block)
                dev, dev,  # w_idx, w_val
                dev, dev, dev, dev, dev,  # f_idx, f_w, f_h, f_s, f_a
                dev,  # sel_idx
                rep, rep, rep, rep,  # per-segment config
            ),
            out_specs=(rowk, rowk, rowk, rowk, P(axes, None, None)),
        )

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def tick(*args):
            return mapped(*args)

        self._tick_fns[key] = tick
        return tick

    def _tick_fn(self, Dw: int, Df: int, Sb: int, lanes: frozenset):
        key = (Dw, Df, Sb, lanes)
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        from functools import partial

        from doorman_tpu.solver.dense import (
            ChunkedDenseBatch,
            solve_chunked,
        )

        Rp, W = self._Rp, self._W
        dtype = self._dtype
        out_dtype = self._out_dtype
        row_seg = self._row_seg_d

        # Flat 1D scatters: slot s of the segment based at row b lives
        # at flat index b*W + s. Wants-only slots (`w_*`, the
        # steady-state churn) ship one value each; full slots (`f_*`)
        # ship all four lanes. Reshape in/out of [Rp*W] is free (same
        # buffer); donation keeps the tables in place.
        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def tick(wants, has, sub, act, w_idx, w_val, f_idx, f_w, f_h,
                 f_s, f_a, sel_idx, cap, kind, learn, statc):
            wants = (
                wants.reshape(-1)
                .at[w_idx].set(w_val.astype(dtype))
                .at[f_idx].set(f_w)
                .reshape(Rp, W)
            )
            has = has.reshape(-1).at[f_idx].set(f_h).reshape(Rp, W)
            sub = sub.reshape(-1).at[f_idx].set(f_s).reshape(Rp, W)
            act = act.reshape(-1).at[f_idx].set(f_a).reshape(Rp, W)
            gets = solve_chunked(
                ChunkedDenseBatch(
                    wants=wants, has=has, subclients=sub, active=act,
                    row_seg=row_seg, capacity=cap, algo_kind=kind,
                    learning=learn, static_capacity=statc,
                ),
                lanes=lanes,
            )
            out = gets[sel_idx, :].astype(out_dtype)
            return wants, gets, sub, act, out

        self._tick_fns[key] = tick
        return tick

    def _fused_layout(self, Dw: int, Df: int, Sb: int, use_bf16: bool):
        """[(elements, dtype)] of the wide fused staging buffer, in the
        order the host packs it (_fused_bytes) and the executable
        unpacks it (_unpack_fused): flat slot index blocks, value
        blocks, the delivery row set, and the active flags."""
        idt, dt = self._idx_dtype, self._dtype
        return (
            (Dw, idt),  # w_idx
            (Dw, _BF16 if use_bf16 else dt),  # w_val (bf16 when exact)
            (Df, idt),  # f_idx
            (Df, dt),  # f_w
            (Df, dt),  # f_h
            (Df, dt),  # f_s
            (Sb, np.int32),  # sel
            (Df, np.bool_),  # f_a
        )

    def _tick_fn_fused(self, Dw: int, Df: int, Sb: int, lanes: frozenset,
                       use_bf16: bool):
        """One-launch fused wide tick: the eight staged blocks arrive
        as ONE uint8 buffer, unpacked by byte plane at static offsets
        in-program (_unpack_fused; see ResidentDenseSolver._tick_fn_fused
        for the idiom and the
        byte-identity argument — every scatter/solve op here is the
        round-trip executable's)."""
        key = ("fused", Dw, Df, Sb, lanes, use_bf16, self._idx_dtype)
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        from functools import partial

        from doorman_tpu.solver.dense import (
            ChunkedDenseBatch,
            solve_chunked,
        )

        Rp, W = self._Rp, self._W
        dtype = self._dtype
        out_dtype = self._out_dtype
        row_seg = self._row_seg_d
        layout = self._fused_layout(Dw, Df, Sb, use_bf16)

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def tick(wants, has, sub, act, buf, cap, kind, learn, statc):
            (
                w_idx, w_val, f_idx, f_w, f_h, f_s, sel_idx, f_a
            ) = _unpack_fused(buf, layout)
            wants = (
                wants.reshape(-1)
                .at[w_idx].set(w_val.astype(dtype))
                .at[f_idx].set(f_w)
                .reshape(Rp, W)
            )
            has = has.reshape(-1).at[f_idx].set(f_h).reshape(Rp, W)
            sub = sub.reshape(-1).at[f_idx].set(f_s).reshape(Rp, W)
            act = act.reshape(-1).at[f_idx].set(f_a).reshape(Rp, W)
            gets = solve_chunked(
                ChunkedDenseBatch(
                    wants=wants, has=has, subclients=sub, active=act,
                    row_seg=row_seg, capacity=cap, algo_kind=kind,
                    learning=learn, static_capacity=statc,
                ),
                lanes=lanes,
            )
            out = gets[sel_idx, :].astype(out_dtype)
            return wants, gets, sub, act, out

        self._tick_fns[key] = tick
        return tick

    def _tick_fn_fused_scoped(self, Dw: int, Df: int, Sb: int, Cb: int,
                              Scb: int, lanes: frozenset,
                              use_bf16: bool):
        """Scoped fused wide tick: the group closure in action. The
        scope buffer (cached int32) carries the scoped segments'
        ENTIRE chunk-row span — every straddling chunk of every
        touched segment, the correctness invariant for per-segment
        lanes — as [Cb] row indices, their compact segment map [Cb],
        and the scoped segment ids [Scb] (config gather). The compact
        solve runs solve_chunked's exact two-level reduction over the
        compact rows (same per-row values, same addition order —
        bit-identical totals per scoped segment), fresh grants scatter
        back into the donated resident slab, delivery gathers from the
        slab, and the per-SEGMENT solve-moved mask (segment-any of the
        per-row fixpoint test) packs into the slab tail for the host
        frontier. Padding rows point at the reserved padding row and
        the reserved compact padding segment Scb-1 (seg id Sp-1:
        capacity 0, inactive)."""
        key = (
            "fused_scoped", Dw, Df, Sb, Cb, Scb, lanes, use_bf16,
            self._idx_dtype,
        )
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        import jax.numpy as jnp
        from functools import partial

        from doorman_tpu.solver.dense import (
            ChunkedDenseBatch,
            chunked_reduces,
            solve_chunked,
        )

        Rp, W = self._Rp, self._W
        dtype = self._dtype
        out_dtype = self._out_dtype
        layout = self._fused_layout(Dw, Df, Sb, use_bf16)
        Mv = -(-Scb // W)  # moved-mask rows appended to the slab

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def tick(wants, has, sub, act, buf, scope_buf, cap, kind,
                 learn, statc):
            (
                w_idx, w_val, f_idx, f_w, f_h, f_s, sel_idx, f_a
            ) = _unpack_fused(buf, layout)
            wants = (
                wants.reshape(-1)
                .at[w_idx].set(w_val.astype(dtype))
                .at[f_idx].set(f_w)
                .reshape(Rp, W)
            )
            has = has.reshape(-1).at[f_idx].set(f_h).reshape(Rp, W)
            sub = sub.reshape(-1).at[f_idx].set(f_s).reshape(Rp, W)
            act = act.reshape(-1).at[f_idx].set(f_a).reshape(Rp, W)
            rows = scope_buf[:Cb]
            row_seg_c = scope_buf[Cb : 2 * Cb]
            seg_ids = scope_buf[2 * Cb :]
            h_c = has[rows]
            gets_c = solve_chunked(
                ChunkedDenseBatch(
                    wants=wants[rows], has=h_c, subclients=sub[rows],
                    active=act[rows], row_seg=row_seg_c,
                    capacity=cap[seg_ids], algo_kind=kind[seg_ids],
                    learning=learn[seg_ids],
                    static_capacity=statc[seg_ids],
                ),
                lanes=lanes,
            )
            # Per-segment fixpoint test: any chunk row of the segment
            # whose fresh solve differs from its input has.
            segsum, _ = chunked_reduces(row_seg_c, Scb)
            moved_seg = (
                segsum(
                    (gets_c != h_c).any(axis=1).astype(dtype)[:, None]
                )
                > 0
            )
            has = has.at[rows].set(gets_c)
            out = has[sel_idx, :].astype(out_dtype)
            mvd = jnp.pad(
                moved_seg.astype(out_dtype), (0, Mv * W - Scb)
            ).reshape(Mv, W)
            slab = jnp.concatenate([out, mvd], axis=0)
            return wants, has, sub, act, slab

        self._tick_fns[key] = tick
        return tick

    def _tick_fn_mesh_fused_scoped(self, Dw: int, Df: int, Sb: int,
                                   Cbl: int, Cbg: int, Scb: int,
                                   lanes: frozenset, use_bf16: bool):
        """Mesh variant of the scoped wide tick: per-shard scoped
        extents with the straddling-chunk psum RESTRICTED to scoped
        chunks. Each shard's slice of the scope buffer carries its
        local scoped rows ([Cbl], pad Rl: gather-clip / scatter-drop),
        their global compact positions ([Cbl], pad Cbg: dropped from
        the assemble), and the replicated global compact segment map
        [Cbg] + scoped segment ids [Scb]. The two-level reduction runs
        through parallel.sharded.scoped_chunk_reduces — a [Cbg]-sized
        psum/pmax over disjoint supports instead of the full [Rp]
        collective, bit-identical per scoped segment (same rows, same
        order, identity elsewhere)."""
        key = (
            "fused_mesh_scoped", Dw, Df, Sb, Cbl, Cbg, Scb, lanes,
            use_bf16, self._idx_dtype,
        )
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        import jax.numpy as jnp
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from doorman_tpu.parallel.compat import shard_map
        from doorman_tpu.parallel.sharded import scoped_chunk_reduces
        from doorman_tpu.solver.lanes import solve_lanes

        mr = self._meshrows
        axes = mr.axes
        Rp, W = self._Rp, self._W
        Rl = Rp // mr.n_dev
        dtype = self._dtype
        out_dtype = self._out_dtype
        layout = self._fused_layout(Dw, Df, Sb, use_bf16)

        def body(wants, has, sub, act, buf, scope_buf, cap, kind,
                 learn, statc):
            (
                w_idx, w_val, f_idx, f_w, f_h, f_s, sel_idx, f_a
            ) = _unpack_fused(buf[0], layout)
            wants = (
                wants.reshape(-1)
                .at[w_idx].set(w_val.astype(dtype), mode="drop")
                .at[f_idx].set(f_w, mode="drop")
                .reshape(Rl, W)
            )
            has = (
                has.reshape(-1).at[f_idx].set(f_h, mode="drop")
                .reshape(Rl, W)
            )
            sub = (
                sub.reshape(-1).at[f_idx].set(f_s, mode="drop")
                .reshape(Rl, W)
            )
            act = (
                act.reshape(-1).at[f_idx].set(f_a, mode="drop")
                .reshape(Rl, W)
            )
            sb = scope_buf[0]
            rows_l = sb[:Cbl]
            gpos = sb[Cbl : 2 * Cbl]
            row_seg_cg = sb[2 * Cbl : 2 * Cbl + Cbg]
            seg_ids = sb[2 * Cbl + Cbg :]

            def take_rows(tbl):
                return jnp.take(
                    tbl, rows_l, axis=0, mode="clip",
                    indices_are_sorted=True,
                )

            segsum, segmax = scoped_chunk_reduces(
                self._mesh, gpos, row_seg_cg, Cbg, Scb
            )
            # Local compact row -> compact segment (pad slots clip to
            # the last global position, whose segment is the compact
            # padding segment Scb-1).
            seg_l = jnp.take(row_seg_cg, gpos, mode="clip")
            h_c = take_rows(has)
            gets_c = solve_lanes(
                take_rows(wants), h_c, take_rows(sub), take_rows(act),
                cap[seg_ids], kind[seg_ids], learn[seg_ids],
                statc[seg_ids],
                segsum=segsum, segmax=segmax,
                expand=lambda totals: totals[seg_l][:, None],
                lanes=lanes,
            )
            moved_seg = (
                segsum(
                    (gets_c != h_c).any(axis=1).astype(dtype)[:, None]
                )
                > 0
            )
            has = has.at[rows_l].set(gets_c, mode="drop")
            out = jnp.take(
                has, sel_idx, axis=0, mode="clip",
                indices_are_sorted=True,
            ).astype(out_dtype)
            return wants, has, sub, act, out[None], moved_seg

        rowk = P(axes, None)
        row = P(axes)
        rep = P()
        mapped = shard_map(
            body,
            mesh=self._mesh,
            in_specs=(
                rowk, rowk, rowk, rowk,  # tables
                row,  # fused uint8 buffer [n_dev, B]
                row,  # scope buffer [n_dev, 2*Cbl + Cbg + Scb]
                rep, rep, rep, rep,  # per-segment config
            ),
            out_specs=(
                rowk, rowk, rowk, rowk, P(axes, None, None), rep,
            ),
        )

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def tick(*args):
            return mapped(*args)

        self._tick_fns[key] = tick
        return tick

    def _tick_fn_mesh_fused(self, Dw: int, Df: int, Sb: int,
                            lanes: frozenset, use_bf16: bool):
        """Mesh variant of the wide fused upload: each shard's staged
        blocks arrive as one [1, B] slice of the sharded uint8 buffer
        (shard-LOCAL flat indices, same drop semantics as the
        round-trip mesh executable)."""
        key = (
            "fused_mesh", Dw, Df, Sb, lanes, use_bf16, self._idx_dtype
        )
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        import jax.numpy as jnp
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from doorman_tpu.parallel.compat import shard_map
        from doorman_tpu.parallel.sharded import resident_chunk_reduces
        from doorman_tpu.solver.lanes import solve_lanes

        mr = self._meshrows
        axes = mr.axes
        Rp, W = self._Rp, self._W
        Rl = Rp // mr.n_dev
        dtype = self._dtype
        out_dtype = self._out_dtype
        layout = self._fused_layout(Dw, Df, Sb, use_bf16)
        segsum, segmax = resident_chunk_reduces(
            self._mesh, self._row_seg_h, self._Sp, Rl
        )

        def body(wants, has, sub, act, row_seg, buf, cap, kind, learn,
                 statc):
            (
                w_idx, w_val, f_idx, f_w, f_h, f_s, sel_idx, f_a
            ) = _unpack_fused(buf[0], layout)
            wants = (
                wants.reshape(-1)
                .at[w_idx].set(w_val.astype(dtype), mode="drop")
                .at[f_idx].set(f_w, mode="drop")
                .reshape(Rl, W)
            )
            has = (
                has.reshape(-1).at[f_idx].set(f_h, mode="drop")
                .reshape(Rl, W)
            )
            sub = (
                sub.reshape(-1).at[f_idx].set(f_s, mode="drop")
                .reshape(Rl, W)
            )
            act = (
                act.reshape(-1).at[f_idx].set(f_a, mode="drop")
                .reshape(Rl, W)
            )
            gets = solve_lanes(
                wants, has, sub, act, cap, kind, learn, statc,
                segsum=segsum, segmax=segmax,
                expand=lambda totals: totals[row_seg][:, None],
                lanes=lanes,
            )
            out = jnp.take(
                gets, sel_idx, axis=0, mode="clip",
                indices_are_sorted=True,
            ).astype(out_dtype)
            return wants, gets, sub, act, out[None]

        rowk = P(axes, None)
        row = P(axes)
        rep = P()
        mapped = shard_map(
            body,
            mesh=self._mesh,
            in_specs=(
                rowk, rowk, rowk, rowk,  # tables
                row,  # row_seg (local block)
                row,  # fused uint8 buffer [n_dev, B]
                rep, rep, rep, rep,  # per-segment config
            ),
            out_specs=(rowk, rowk, rowk, rowk, P(axes, None, None)),
        )

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def tick(*args):
            return mapped(*args)

        self._tick_fns[key] = tick
        return tick

    # -- phases -------------------------------------------------------

    def _drain(self, ph: PhaseRecorder):
        """Drain dirty slots of our tracked rids. (drain FIRST, then
        read versions, then pack — see StoreEngine.chunk_versions.)"""
        W = self._W
        slot_parts: List[np.ndarray] = []  # flat device indices
        lvl_parts: List[np.ndarray] = []
        rid_parts: List[np.ndarray] = []  # rid per drained slot
        raw_slot_parts: List[np.ndarray] = []  # engine slot per drained
        for rid in self._engine.dirty_slot_rids():
            seg = self._rid_to_seg.get(int(rid))
            if seg is None:
                continue
            slots, levels = self._engine.drain_slots(int(rid))
            if not len(slots):
                continue
            # Slots past the allocated chunk span (growth raced the
            # rebuild check) force a rebuild next tick via
            # _needs_rebuild; clamp here so this tick stays in-bounds.
            limit = int(self._n_chunks[seg]) * W
            keep = slots < limit
            slots = slots[keep]
            levels = levels[keep]
            slot_parts.append(self._base_row[seg] * W + slots)
            lvl_parts.append(levels)
            rid_parts.append(np.full(len(slots), rid, np.int32))
            raw_slot_parts.append(slots)
        if slot_parts:
            flat_idx = np.concatenate(slot_parts)
            levels = np.concatenate(lvl_parts)
            slot_rids = np.concatenate(rid_parts)
            raw_slots = np.concatenate(raw_slot_parts)
        else:
            flat_idx = np.zeros(0, np.int64)
            levels = np.zeros(0, np.uint8)
            slot_rids = np.zeros(0, np.int32)
            raw_slots = np.zeros(0, np.int64)
        ph.lap("drain")
        return flat_idx, levels, slot_rids, raw_slots

    def _drained_empty(self, drained) -> bool:
        return len(drained[0]) == 0

    def _launch(self, res_list, drained, config_changed, now, ph):
        flat_idx, levels, slot_rids, raw_slots = drained

        # Delivery set (chunk rows). Full-dirty rows (membership /
        # client-reported has) and config-changed segments always
        # deliver same-tick; wants-dirty rows deliver same-tick only
        # while the set stays small (beyond the budget the rotation
        # covers them within a refresh interval — the module docstring
        # explains why that bound is the reference's own staleness).
        W = self._W
        full_mask = levels >= 2
        dirty_rows = flat_idx // W
        # Solve-mode decision: the scope unit is the SEGMENT (the
        # group closure — per-segment lanes couple every chunk of a
        # resource, so one dirty slot scopes the segment's whole
        # straddling-chunk span).
        if len(dirty_rows):
            dirty_segs = self._row_seg_h[np.unique(dirty_rows)]
            dirty_segs = np.unique(dirty_segs[dirty_segs < self._S])
        else:
            dirty_segs = np.zeros(0, np.int64)
        scope, _forced = self._scope_for_tick(
            dirty_segs, config_changed, self._S
        )
        if scope is not None:
            self.last_scope = {
                "rows": int(self._n_chunks[scope].sum())
                if len(scope)
                else 0,
                "resources": int(len(scope)),
            }
        else:
            self.last_scope = {"rows": self._R, "resources": self._S}
        rot = self._rotation_rows(
            self._R,
            self._Rp // self._meshrows.n_dev
            if self._meshrows is not None
            else 0,
        )
        if self._just_rebuilt or config_changed is None:
            self._just_rebuilt = False
            sel = np.arange(max(self._R, 1), dtype=np.int64)
        else:
            parts = [dirty_rows[full_mask], rot]
            budget = max(64, 2 * max(len(rot), 1))
            wants_rows = np.unique(dirty_rows[~full_mask])
            if len(wants_rows) <= budget:
                parts.append(wants_rows)
            for s in config_changed:
                if s < self._S:
                    b, n = self._base_row[s], self._n_chunks[s]
                    parts.append(np.arange(b, b + n, dtype=np.int64))
            sel = np.unique(np.concatenate(parts))
        n_sel = len(sel)
        sel_rids = self._row_rids[sel]
        sel_chunks = self._row_chunk[sel]
        # Versions BEFORE the pack (safe direction; see chunk_versions).
        versions = self._engine.chunk_versions(sel_rids, sel_chunks)

        # Pack the dirty slots' values (one gather call per rid) into
        # UNPADDED arrays; padding is per-path below (single device:
        # one flat block aimed at the padding row; mesh: per-shard
        # blocks with out-of-range drop slots).
        n_w = int((~full_mask).sum())
        n_f = int(full_mask.sum())
        dtype = self._dtype
        w_idx = np.zeros(n_w, np.int64)
        w_val = np.zeros(n_w, dtype)
        f_idx = np.zeros(n_f, np.int64)
        f_w = np.zeros(n_f, dtype)
        f_h = np.zeros(n_f, dtype)
        f_s = np.zeros(n_f, dtype)
        f_a = np.zeros(n_f, bool)
        wpos = fpos = 0
        # One-tick UPLOAD-side inconsistency window: pack_slots reads
        # LIVE engine state, after the drain above. A swap-remove
        # landing between the drain and this pack makes a wants-only
        # (level-1) slot ship the NEW occupant's wants while the old
        # occupant's has/subclients/active lanes are still on device —
        # that resource's shared totals are slightly skewed for every
        # chunk of THIS solve. It self-corrects in one tick: the
        # membership change bumped the chunk version, so the version
        # guard (read before this pack — see chunk_versions) blocks the
        # skewed chunk's write-back, and the re-marked slots re-deliver
        # a consistent solve next tick. This is the upload-side sibling
        # of the module docstring's download staleness bound ("lag but
        # never lead" covers the write-back only); pinned by
        # tests/test_resident_wide.py::
        # test_drain_remove_pack_interleaving_converges.
        for rid in np.unique(slot_rids) if len(slot_rids) else ():
            m = slot_rids == rid
            pw, phas, psub, pact = self._engine.pack_slots(
                int(rid), raw_slots[m]
            )
            fm = full_mask[m]
            fi = flat_idx[m]
            nw_i = int((~fm).sum())
            nf_i = int(fm.sum())
            w_idx[wpos : wpos + nw_i] = fi[~fm]
            w_val[wpos : wpos + nw_i] = pw[~fm]
            wpos += nw_i
            f_idx[fpos : fpos + nf_i] = fi[fm]
            f_w[fpos : fpos + nf_i] = pw[fm]
            f_h[fpos : fpos + nf_i] = phas[fm]
            f_s[fpos : fpos + nf_i] = psub[fm]
            f_a[fpos : fpos + nf_i] = pact[fm].astype(bool)
            fpos += nf_i
        ph.lap("pack")

        # A backlog past the ceiling merges into the resident tables
        # whole (inside the staging lap); the tick then scatters nothing.
        whole = n_w + n_f > WIDE_SCATTER_MAX
        self.last_scope.update(slots=n_w + n_f, whole=int(whole))
        if whole:
            self._stage_whole(w_idx, w_val, f_idx, f_w, f_h, f_s, f_a)
            w_idx, w_val, f_idx, f_w, f_h, f_s, f_a = (
                a[:0] for a in (w_idx, w_val, f_idx, f_w, f_h, f_s, f_a)
            )
        keep = np.zeros(n_sel, np.uint8)
        if n_sel:
            segs = self._row_seg_h[sel]
            keep = self._config.learn_h[segs].astype(np.uint8)
        if self._meshrows is not None:
            return self._stage_mesh(
                w_idx, w_val, f_idx, f_w, f_h, f_s, f_a,
                sel, sel_rids, sel_chunks, versions, keep, now, ph,
                scope, whole,
            )

        Dw = pow2_bucket(len(w_idx), 1024)
        Df = pow2_bucket(len(f_idx), 256)
        Sb = pow2_bucket(n_sel, 32)
        self.last_scope["padded"] = (
            Dw + Df + (self._Rp * W if whole else 0)
        )
        pad_slot = self._R * W  # padding row slot 0
        idt = self._idx_dtype

        def padded(arr, width, fill):
            out = np.full((width,) + arr.shape[1:], fill, arr.dtype)
            out[: len(arr)] = arr
            return out

        sel_pad = np.resize(sel, Sb) if n_sel else np.zeros(Sb, np.int64)
        lanes = self._config.lanes()
        w_val_block = padded(w_val, Dw, 0)
        # Compact upload of the wants-value block (bf16 when exact; see
        # engine.bf16_exact) and int32 flat indices when the table fits.
        if _BF16 is not None and bf16_exact(w_val_block):
            w_val_block = w_val_block.astype(_BF16)
        host_blocks = (
            padded(w_idx, Dw, pad_slot).astype(idt),
            w_val_block,
            padded(f_idx, Df, pad_slot).astype(idt),
            padded(f_w, Df, 0),
            padded(f_h, Df, 0),
            padded(f_s, Df, 0),
            sel_pad.astype(np.int32),
            padded(f_a, Df, False),
        )
        cfg = self._config
        from doorman_tpu.utils.transfer import start_download

        moved_rows = 0
        if self._fused:
            # One-launch fused wide tick: all eight staged blocks in
            # one uint8 buffer, one placement, one launch, one download
            # stream (see ResidentDenseSolver._launch's fused tail).
            use_bf16 = w_val_block.dtype != self._dtype
            buf = _fused_bytes(host_blocks)
            if scope is not None:
                # Scoped staging: the group closure's whole chunk-row
                # span, its compact segment map, and the scoped
                # segment ids — one cached int32 buffer. Scb reserves
                # a compact padding segment above every real one.
                scope_rows = (
                    np.concatenate([
                        np.arange(
                            self._base_row[s],
                            self._base_row[s] + self._n_chunks[s],
                            dtype=np.int64,
                        )
                        for s in scope
                    ])
                    if len(scope)
                    else np.zeros(0, np.int64)
                )
                Cb = min(
                    pow2_bucket(max(len(scope_rows), 1), 8), self._Rp
                )
                Scb = pow2_bucket(len(scope) + 1, 8)
                scope_host = np.full(2 * Cb + Scb, 0, np.int32)
                scope_host[:Cb] = self._R
                scope_host[: len(scope_rows)] = scope_rows
                row_seg_c = np.full(Cb, Scb - 1, np.int32)
                row_seg_c[: len(scope_rows)] = np.repeat(
                    np.arange(len(scope), dtype=np.int32),
                    self._n_chunks[scope],
                )
                scope_host[Cb : 2 * Cb] = row_seg_c
                seg_ids = np.full(Scb, self._Sp - 1, np.int32)
                seg_ids[: len(scope)] = scope
                scope_host[2 * Cb :] = seg_ids
            ph.lap("staging")
            buf_d = self._put(buf)
            if scope is not None:
                tick = self._tick_fn_fused_scoped(
                    Dw, Df, Sb, Cb, Scb, lanes, use_bf16
                )
                scope_d = self._place_scope(scope_host, self._put)
                moved_rows = -(-Scb // W)
                (
                    self._wants, self._has, self._sub, self._act, out
                ) = tick(
                    self._wants, self._has, self._sub, self._act,
                    buf_d, scope_d,
                    cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                )
            else:
                tick = self._tick_fn_fused(Dw, Df, Sb, lanes, use_bf16)
                (
                    self._wants, self._has, self._sub, self._act, out
                ) = tick(
                    self._wants, self._has, self._sub, self._act,
                    buf_d,
                    cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                )
            count_launch()
            out = start_download(out, chunks=1)
            ph.lap("fused")
        else:
            ph.lap("staging")
            put = self._put
            tick = self._tick_fn(Dw, Df, Sb, lanes)
            w_i_d, w_v_d, f_i_d, f_w_d, f_h_d, f_s_d, sel_d, f_a_d = (
                tuple(put(b) for b in host_blocks)
            )
            ph.lap("upload")
            (
                self._wants, self._has, self._sub, self._act, out
            ) = tick(
                self._wants, self._has, self._sub, self._act,
                w_i_d, w_v_d, f_i_d, f_w_d, f_h_d, f_s_d, f_a_d, sel_d,
                cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
            )
            count_launch()
            out = start_download(out)
            ph.lap("solve")
        return TickHandle(
            out=out,
            sel_rows=sel,
            rids=sel_rids,
            versions=versions,
            keep_has=keep,
            n_sel=n_sel,
            dispatched_at=now,
            chunks=sel_chunks,
            scope_ids=scope,
            moved_rows=moved_rows,
            seq=self._seq,
        )

    def _stage_mesh(self, w_idx, w_val, f_idx, f_w, f_h, f_s, f_a,
                    sel, sel_rids, sel_chunks, versions, keep, now, ph,
                    scope=None, whole=False):
        """Mesh tail of the launch: slot scatters and the delivery set
        grouped by owning shard; per-shard blocks land only on their
        own device, the shard_mapped tick solves with the bit-stable
        psum reduction, and the delivery downloads one stream per
        shard (see ResidentDenseSolver._stage_mesh)."""
        from doorman_tpu.solver.resident_mesh import (
            group_by_shard,
            pad_shard_blocks,
            pad_shard_indices,
        )
        from doorman_tpu.utils.transfer import start_sharded_download

        mr = self._meshrows
        n_dev = mr.n_dev
        W = self._W
        Rl = self._Rp // n_dev
        span = Rl * W
        n_sel = len(sel)
        idt = self._idx_dtype

        ow = w_idx // span
        counts_w, (w_idx_l, w_val_l) = group_by_shard(
            ow, n_dev, [w_idx - ow * span, w_val]
        )
        of = f_idx // span
        counts_f, (f_idx_l, f_w_l, f_h_l, f_s_l, f_a_l) = group_by_shard(
            of, n_dev, [f_idx - of * span, f_w, f_h, f_s, f_a]
        )
        # sel is sorted, so owners are nondecreasing and the stable
        # grouping preserves sel's order exactly — the handle's global
        # bookkeeping (rids/chunks/versions/keep) needs no permutation.
        owner_sel = sel // Rl
        counts_sel, (sel_l,) = group_by_shard(
            owner_sel, n_dev, [sel - owner_sel * Rl]
        )

        Dw = pow2_bucket(int(counts_w.max()) if len(w_idx) else 1, 1024)
        Df = pow2_bucket(int(counts_f.max()) if len(f_idx) else 1, 256)
        Sb = pow2_bucket(int(counts_sel.max()) if n_sel else 1, 32)
        self.last_scope["padded"] = n_dev * (Dw + Df) + (
            self._Rp * W if whole else 0
        )
        w_idx_b, w_val_b = pad_shard_blocks(
            counts_w, Dw, [(w_idx_l, span), (w_val_l, 0)]
        )
        w_idx_b = w_idx_b.astype(idt)
        if _BF16 is not None and bf16_exact(w_val_b):
            w_val_b = w_val_b.astype(_BF16)
        f_idx_b, f_w_b, f_h_b, f_s_b, f_a_b = pad_shard_blocks(
            counts_f, Df,
            [
                (f_idx_l, span), (f_w_l, 0), (f_h_l, 0), (f_s_l, 0),
                (f_a_l, False),
            ],
        )
        f_idx_b = f_idx_b.astype(idt)
        sel_b = pad_shard_indices(counts_sel, Sb, sel_l).astype(np.int32)
        lanes = self._config.lanes()
        fused = self._fused
        if fused and scope is not None:
            # Per-shard scoped extents: each shard's slice of the
            # scope buffer carries its local scoped rows + their
            # global compact positions; the replicated compact segment
            # map and scoped segment ids ride in every slice (one
            # placement, no second replicated upload).
            scope_rows = (
                np.concatenate([
                    np.arange(
                        self._base_row[s],
                        self._base_row[s] + self._n_chunks[s],
                        dtype=np.int64,
                    )
                    for s in scope
                ])
                if len(scope)
                else np.zeros(0, np.int64)
            )
            Cbg = min(pow2_bucket(max(len(scope_rows), 1), 8), self._Rp)
            Scb = pow2_bucket(len(scope) + 1, 8)
            row_seg_cg = np.full(Cbg, Scb - 1, np.int32)
            row_seg_cg[: len(scope_rows)] = np.repeat(
                np.arange(len(scope), dtype=np.int32),
                self._n_chunks[scope],
            )
            seg_ids = np.full(Scb, self._Sp - 1, np.int32)
            seg_ids[: len(scope)] = scope
            owner_c = scope_rows // Rl
            counts_c, (rows_loc, gpos_loc) = group_by_shard(
                owner_c, n_dev,
                [
                    scope_rows - owner_c * Rl,
                    np.arange(len(scope_rows), dtype=np.int64),
                ],
            )
            Cbl = min(
                pow2_bucket(
                    max(
                        int(counts_c.max()) if len(scope_rows) else 0,
                        1,
                    ),
                    8,
                ),
                Rl,
            )
            rows_l_b, gpos_b = pad_shard_blocks(
                counts_c, Cbl, [(rows_loc, Rl), (gpos_loc, Cbg)]
            )
            scope_host = np.concatenate(
                [
                    rows_l_b.astype(np.int32),
                    gpos_b.astype(np.int32),
                    np.tile(row_seg_cg, (n_dev, 1)),
                    np.tile(seg_ids, (n_dev, 1)),
                ],
                axis=1,
            )
        if fused:
            # Fused upload (see ResidentDenseSolver._stage_mesh): one
            # [n_dev, B] uint8 buffer, each shard's slice carrying its
            # eight staged blocks back to back in _fused_layout order.
            buf_host = _fused_bytes(
                (w_idx_b, w_val_b, f_idx_b, f_w_b, f_h_b, f_s_b, sel_b,
                 f_a_b)
            )
        ph.lap("staging")

        itemsize = self._dtype.itemsize
        idx_bytes = np.dtype(idt).itemsize
        ph.shard_bytes(
            "upload",
            counts_w * (idx_bytes + itemsize)
            + counts_f * (idx_bytes + 3 * itemsize + 1)
            + counts_sel * 4,
        )
        ph.shard_bytes(
            "download",
            counts_sel * W * np.dtype(self._out_dtype).itemsize,
        )
        put = self._put_rows
        cfg = self._config
        moved_d = None
        if fused:
            use_bf16 = w_val_b.dtype != self._dtype
            buf_d = put(buf_host)
            if scope is not None:
                tick = self._tick_fn_mesh_fused_scoped(
                    Dw, Df, Sb, Cbl, Cbg, Scb, lanes, use_bf16
                )
                scope_d = self._place_scope(scope_host, put)
                (
                    self._wants, self._has, self._sub, self._act,
                    out, moved_d
                ) = tick(
                    self._wants, self._has, self._sub, self._act,
                    buf_d, scope_d,
                    cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                )
            else:
                tick = self._tick_fn_mesh_fused(
                    Dw, Df, Sb, lanes, use_bf16
                )
                (
                    self._wants, self._has, self._sub, self._act, out
                ) = tick(
                    self._wants, self._has, self._sub, self._act,
                    self._row_seg_d, buf_d,
                    cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
                )
            count_launch()
            out = start_sharded_download(out)
            ph.lap("fused")
        else:
            tick = self._tick_fn_mesh(Dw, Df, Sb, lanes)
            staged = (
                put(w_idx_b), put(w_val_b), put(f_idx_b), put(f_w_b),
                put(f_h_b), put(f_s_b), put(f_a_b), put(sel_b),
            )
            ph.lap("upload")
            (
                self._wants, self._has, self._sub, self._act, out
            ) = tick(
                self._wants, self._has, self._sub, self._act,
                self._row_seg_d, *staged,
                cfg.cap_d, cfg.kind_d, cfg.learn_d, cfg.statc_d,
            )
            count_launch()
            out = start_sharded_download(out)
            ph.lap("solve")
        return TickHandle(
            out=out,
            sel_rows=sel,
            rids=sel_rids,
            versions=versions,
            keep_has=keep,
            n_sel=n_sel,
            dispatched_at=now,
            chunks=sel_chunks,
            shard_counts=counts_sel,
            scope_ids=scope,
            moved=moved_d,
            seq=self._seq,
        )

    def _merge_fn(self):
        """The whole-table merge: each staged column replaces the
        resident one where its slot is dirty (flags: bits 0-1 the drain
        level, 1 wants-only or 2 full; bit 2 the active flag), as the
        scatter path's blocks would. Its shape is the table's alone."""
        key = ("whole",)
        fn = self._tick_fns.get(key)
        if fn is not None:
            return fn

        import jax
        import jax.numpy as jnp
        from functools import partial

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def merge(wants, has, sub, act, w, h, s, flags):
            level = flags & 3
            full = level >= 2
            return (
                jnp.where(level >= 1, w, wants),
                jnp.where(full, h, has),
                jnp.where(full, s, sub),
                jnp.where(full, (flags & 4) != 0, act),
            )

        self._tick_fns[key] = merge
        return merge

    def _stage_whole(self, w_idx, w_val, f_idx, f_w, f_h, f_s, f_a):
        """Stage a backlog past WIDE_SCATTER_MAX as the whole table's
        columns (wants, has, subclients and one flags byte a slot: 13 B
        at f32) and merge them into the resident tables on device."""
        shape = (self._Rp, self._W)
        n = self._Rp * self._W
        w = np.zeros(n, self._dtype)
        h = np.zeros(n, self._dtype)
        s = np.zeros(n, self._dtype)
        flags = np.zeros(n, np.uint8)
        w[w_idx] = w_val
        flags[w_idx] = 1
        w[f_idx] = f_w
        h[f_idx] = f_h
        s[f_idx] = f_s
        flags[f_idx] = 2 | (f_a.astype(np.uint8) << 2)
        put = self._put_rows
        self._wants, self._has, self._sub, self._act = self._merge_fn()(
            self._wants, self._has, self._sub, self._act,
            *(put(a.reshape(shape)) for a in (w, h, s, flags)),
        )
        count_launch()

    def _apply_grants(self, handle: TickHandle, gets: np.ndarray) -> int:
        return self._engine.apply_chunks(
            handle.rids,
            handle.chunks,
            gets,
            handle.keep_has,
            handle.versions,
        )
