"""Pallas TPU kernel for the dense bucketed solve.

Same semantics as dense.solve_dense (the shared lane math in
doorman_tpu.solver.lanes — reference algorithm.go:44-313 and
simulation/algo_proportional.py:31-65), but fused into one VMEM-resident
kernel. Under plain XLA the fair-share water-fill re-reads the [R, K]
demand tiles from HBM on every bisection iteration (~50 passes); here a
grid step loads its row tile into VMEM once, runs every algorithm lane
and the full bisection on-chip, and writes the grant tile back — one HBM
read and one write per element regardless of iteration count.

Layout: the [R, K] arrays tile along R (TILE_R rows per grid step, K
lanes wide); per-resource vectors ride along as [R, 1] columns. Bool
masks travel as compute-dtype {0,1} columns because TPU VMEM tiling is
specified for numeric dtypes; they are re-derived with `> 0` in-kernel.
R and K are padded to tile boundaries (padding rows solve as garbage and
are sliced off; padded lanes are inactive by construction).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from doorman_tpu.solver.dense import DenseBatch
from doorman_tpu.solver.lanes import solve_lanes
from doorman_tpu.solver.pallas_common import (
    FUSED_LIVE_TILES,
    LANE,
    col_spec,
    pad_col,
    pad_tile,
    row_spec,
    tile_rows,
)


def _kernel(wants_ref, has_ref, sub_ref, active_ref, cap_ref, kind_ref,
            learn_ref, static_ref, out_ref):
    out_ref[:] = solve_lanes(
        wants_ref[:],
        has_ref[:],
        sub_ref[:],
        active_ref[:] > 0,
        cap_ref[:],
        kind_ref[:],
        learn_ref[:] > 0,
        static_ref[:],
        segsum=lambda v: jnp.sum(v, axis=1, keepdims=True),
        segmax=lambda v: jnp.max(v, axis=1, keepdims=True),
        expand=lambda t: t,
    )


def _fused_kernel(wants_ref, has_ref, sub_ref, active_ref, cap_ref,
                  kind_ref, learn_ref, static_ref, prev_ref, deliv_ref,
                  gets_ref, prev_out_ref, changed_ref):
    """One VMEM pass per row tile: every lane solve + the delivered-
    grant delta against the resident previous-grants tile + the prev
    update. The XLA formulation re-reads `gets` and `prev` from HBM
    for the compare and the scatter-style update; here they never
    leave VMEM — the fused tick's delta tracking costs zero extra HBM
    traffic over the solve itself."""
    gets = solve_lanes(
        wants_ref[:],
        has_ref[:],
        sub_ref[:],
        active_ref[:] > 0,
        cap_ref[:],
        kind_ref[:],
        learn_ref[:] > 0,
        static_ref[:],
        segsum=lambda v: jnp.sum(v, axis=1, keepdims=True),
        segmax=lambda v: jnp.max(v, axis=1, keepdims=True),
        expand=lambda t: t,
    )
    gets_ref[:] = gets
    prev = prev_ref[:]
    out = gets.astype(prev.dtype)
    deliv = deliv_ref[:] > 0  # [T, 1] column: delivered this tick
    # Compare and reduce in f32: widening is exact, so equality is the
    # same as in prev's dtype, and Mosaic cannot relayout a packed
    # (bf16) i1 mask for an any-reduction.
    ne = out.astype(jnp.float32) != prev.astype(jnp.float32)
    diff = jnp.max(
        jnp.where(ne, jnp.float32(1), jnp.float32(0)), axis=1, keepdims=True
    ) > 0
    changed_ref[:] = jnp.where(
        deliv & diff,
        jnp.ones((), wants_ref.dtype),
        jnp.zeros((), wants_ref.dtype),
    )
    # prev tracks what the store of record last SAW: only delivered
    # rows advance, the rest keep their previous delivery vintage.
    prev_out_ref[:] = jnp.where(deliv, out, prev)


@functools.partial(jax.jit, static_argnames=("interpret",))
def solve_dense_pallas(batch: DenseBatch, interpret: bool = False) -> jax.Array:
    """Grants [R, K]; bit-compatible with dense.solve_dense.

    `interpret=True` runs the kernel in the pallas interpreter — the
    CPU-mesh test path; on TPU leave it False.
    """
    R, K = batch.wants.shape
    dtype = batch.wants.dtype
    kpad = (-K) % LANE
    Kp = K + kpad
    tile_r = tile_rows(R, Kp, jnp.dtype(dtype).itemsize)
    rpad = (-R) % tile_r
    Rp = R + rpad

    def tile(x):  # [R, K] compute-dtype, padded
        return pad_tile(x.astype(dtype), rpad, kpad)

    def col(x, cdtype):  # [R] -> [Rp, 1]
        return pad_col(x.astype(cdtype), rpad)

    rows, cols = row_spec(tile_r, Kp), col_spec(tile_r)
    gets = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((Rp, Kp), dtype),
        grid=(Rp // tile_r,),
        in_specs=[
            rows,  # wants
            rows,  # has
            rows,  # subclients
            rows,  # active mask
            cols,  # capacity
            cols,  # algo_kind
            cols,  # learning mask
            cols,  # static_capacity
        ],
        out_specs=rows,
        interpret=interpret,
    )(
        tile(batch.wants),
        tile(batch.has),
        tile(batch.subclients),
        tile(batch.active),
        col(batch.capacity, dtype),
        col(batch.algo_kind, jnp.int32),
        col(batch.learning, dtype),
        col(batch.static_capacity, dtype),
    )
    return gets[:R, :K]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_tick_pallas(
    batch: DenseBatch,
    prev: jax.Array,  # [R, K] previous DELIVERED grants (download dtype)
    delivered: jax.Array,  # [R] {0,1}: rows the tick delivers
    interpret: bool = False,
) -> "tuple[jax.Array, jax.Array, jax.Array]":
    """The fused-tick row-tile kernel: (gets, prev_new, changed).

    One grid step loads a row tile into VMEM and produces the grants,
    the advanced previous-grants tile, and the per-row changed flag in
    the same pass — solve + delta compare + prev update never touch
    HBM between each other. `gets` is bit-compatible with
    `solve_dense_pallas` (the solve is the same `solve_lanes` body);
    `changed[r]` is True exactly when row r is delivered this tick AND
    its grants (in prev's dtype) differ from `prev[r]`; `prev_new`
    advances delivered rows and preserves the rest. `interpret=True`
    is the CPU parity-test path (tests/test_fused_tick.py); on TPU
    leave it False.
    """
    R, K = batch.wants.shape
    dtype = batch.wants.dtype
    kpad = (-K) % LANE
    Kp = K + kpad
    tile_r = tile_rows(
        R, Kp, jnp.dtype(dtype).itemsize, live_tiles=FUSED_LIVE_TILES
    )
    rpad = (-R) % tile_r
    Rp = R + rpad

    def tile(x):  # [R, K] compute-dtype, padded
        return pad_tile(x.astype(dtype), rpad, kpad)

    def col(x, cdtype):  # [R] -> [Rp, 1]
        return pad_col(x.astype(cdtype), rpad)

    rows, cols = row_spec(tile_r, Kp), col_spec(tile_r)
    prev_dtype = prev.dtype
    gets, prev_new, changed = pl.pallas_call(
        _fused_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((Rp, Kp), dtype),
            jax.ShapeDtypeStruct((Rp, Kp), prev_dtype),
            jax.ShapeDtypeStruct((Rp, 1), dtype),
        ],
        grid=(Rp // tile_r,),
        in_specs=[
            rows,  # wants
            rows,  # has
            rows,  # subclients
            rows,  # active mask
            cols,  # capacity
            cols,  # algo_kind
            cols,  # learning mask
            cols,  # static_capacity
            rows,  # previous delivered grants
            cols,  # delivered mask
        ],
        out_specs=[rows, rows, cols],
        interpret=interpret,
    )(
        tile(batch.wants),
        tile(batch.has),
        tile(batch.subclients),
        tile(batch.active),
        col(batch.capacity, dtype),
        col(batch.algo_kind, jnp.int32),
        col(batch.learning, dtype),
        col(batch.static_capacity, dtype),
        pad_tile(prev, rpad, kpad),
        col(delivered, dtype),
    )
    return (
        gets[:R, :K],
        prev_new[:R, :K],
        changed[:R, 0] > 0,
    )
