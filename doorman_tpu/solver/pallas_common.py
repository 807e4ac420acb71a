"""Shared scaffolding for the pallas row-tile kernels (pallas_dense,
pallas_priority): tile sizing under a VMEM budget, padding, and block
specs. One place to tune; both kernels stay in lockstep."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# Conservative budget for the live [T, K] intermediates a lane/water-fill
# body keeps in VMEM (~8 of them out of the ~16MB per core).
_VMEM_BUDGET_BYTES = 4 * 1024 * 1024
_LIVE_TILES = 8
# The fused tick kernel streams two more [T, K] operands (prev in,
# prev out) and two more [T, 1] columns (delivered, changed) through
# double-buffered VMEM than the plain solve; at the plain budget its
# 1024-row tile needs 16.44M of the 16M scoped VMEM on a v5e.
FUSED_LIVE_TILES = 16
_MAX_TILE_R = 1024


def tile_rows(R: int, K: int, itemsize: int,
              live_tiles: int = _LIVE_TILES) -> int:
    """Rows per grid step: as large as the VMEM budget allows (big tiles
    amortize per-op overhead in the iterative water-fill), capped so a
    small table is not padded up to a huge tile. `live_tiles` is the
    number of [T, K] tiles the kernel keeps live at once."""
    per_row = max(K, LANE) * itemsize * live_tiles
    tile = max(8, min(_MAX_TILE_R, _VMEM_BUDGET_BYTES // per_row))
    tile -= tile % 8
    rows_needed = R + (-R) % 8
    return max(8, min(tile, rows_needed))


def pad_tile(x: jax.Array, rpad: int, kpad: int) -> jax.Array:
    """Pad an [R, K] array to tile boundaries (values 0 / False)."""
    if rpad or kpad:
        x = jnp.pad(x, ((0, rpad), (0, kpad)))
    return x


def pad_col(x: jax.Array, rpad: int) -> jax.Array:
    """[R] -> [R + rpad, 1] column."""
    x = x[:, None]
    if rpad:
        x = jnp.pad(x, ((0, rpad), (0, 0)))
    return x


# Block index maps return int32 explicitly: under jax_enable_x64 a bare
# Python 0 becomes an int64 that Mosaic cannot lower ("failed to
# legalize operation 'func.return'").


def row_spec(tile_r: int, Kp: int) -> pl.BlockSpec:
    return pl.BlockSpec(
        (tile_r, Kp), lambda i: (i, jnp.int32(0)), memory_space=pltpu.VMEM
    )


def col_spec(tile_r: int) -> pl.BlockSpec:
    return pl.BlockSpec(
        (tile_r, 1), lambda i: (i, jnp.int32(0)), memory_space=pltpu.VMEM
    )
