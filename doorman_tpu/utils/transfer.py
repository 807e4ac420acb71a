"""Host<->device transfer helpers.

A single `jax.device_get` of a large array serializes one copy stream;
a device link may reach full bandwidth only with several async copies
in flight (not yet measured on the v5e). `chunked_device_get` splits
the copy along the leading axis and overlaps the pieces.
"""

from __future__ import annotations

import numpy as np

from doorman_tpu.utils import dispatch as dispatch_mod


def split_for_download(
    arr, *, chunks: "int | None" = None, min_bytes: int = 1 << 17
) -> list:
    """Split a device array into leading-axis slices for an overlapped
    download whose async copies the CALLER starts (use when the copy
    should begin well before the consuming `device_get`, e.g. at
    dispatch time in a pipelined tick). Always returns a list — length
    1 when splitting cannot help. chunks=None sizes the stream count
    to the array (one per ~256 KB, between 2 and 8).

    Each slice is an XLA slice op producing its own (small) device
    buffer — NOT a view — so the split costs one dispatch and a
    transient allocation per part; the bytes crossing the host link
    are unchanged."""
    nbytes = getattr(arr, "nbytes", 0)
    ndim = getattr(arr, "ndim", 0)
    if chunks is None:
        chunks = int(min(8, max(2, nbytes >> 18)))
    if chunks <= 1:
        # Single-stream download (the fused tick's shape): no slice op
        # at all — the array itself is the one part.
        return [arr]
    if ndim < 1 or nbytes < min_bytes or arr.shape[0] < chunks:
        return [arr]
    bounds = np.linspace(0, arr.shape[0], chunks + 1).astype(int)
    parts = [arr[a:b] for a, b in zip(bounds[:-1], bounds[1:])]
    # Each split slice is its own device op (see docstring): the
    # overlap's dispatch cost, counted so the fused tick's single-
    # stream download shows up as fewer dispatches, not just a claim.
    dispatch_mod.count_dispatch(len(parts))
    return parts


def land_parts(parts: list) -> np.ndarray:
    """Land `split_for_download` parts into one contiguous ndarray
    (preallocated — no per-part concatenate copy). Every part landed
    is one device->host sync in the dispatch accounting
    (utils.dispatch) — the chokepoint the fused-tick `host_syncs`
    number reads."""
    import jax

    dispatch_mod.count_host_sync(len(parts))
    if len(parts) == 1:
        return jax.device_get(parts[0])
    lead = sum(int(p.shape[0]) for p in parts)
    out = np.empty((lead,) + tuple(parts[0].shape[1:]), parts[0].dtype)
    pos = 0
    for p in parts:
        n = int(p.shape[0])
        out[pos : pos + n] = jax.device_get(p)
        pos += n
    return out


def start_download(arr, *, chunks: "int | None" = None,
                   min_bytes: int = 1 << 17) -> list:
    """Split `arr` for an overlapped download AND start the async
    copies; pair with `land_parts` to consume. Failure to start a copy
    is non-fatal (numpy/fake-backend arrays land synchronously)."""
    parts = split_for_download(arr, chunks=chunks, min_bytes=min_bytes)
    try:
        for p in parts:
            p.copy_to_host_async()
    except Exception:
        pass
    return parts


def start_sharded_download(arr) -> list:
    """Per-shard async downloads of a leading-axis device-sharded
    array: one part per shard, ordered by leading-axis offset, so
    `land_parts` reassembles the full [n_dev, ...] block.  Each part is
    a shard's own device buffer — no cross-device reshuffle, and every
    device's host link streams its slice concurrently.  Falls back to
    `start_download` when the array is not sharded (single-device
    resident path)."""
    try:
        shards = list(arr.addressable_shards)
    except Exception:
        return start_download(arr)
    if len(shards) <= 1:
        return start_download(arr)
    parts = [
        s.data
        for s in sorted(shards, key=lambda s: s.index[0].start or 0)
    ]
    try:
        for p in parts:
            p.copy_to_host_async()
    except Exception:
        pass
    return parts


def chunked_device_get(
    arr, *, chunks: int = 8, min_bytes: int = 1 << 20
) -> np.ndarray:
    """device_get with the copy split into `chunks` overlapping pieces.

    Small arrays (< min_bytes) and scalars take the plain path; the
    split is along axis 0. Returns one contiguous ndarray either way.
    """
    if getattr(arr, "nbytes", 0) < min_bytes:
        import jax

        return jax.device_get(arr)
    return land_parts(start_download(arr, chunks=chunks,
                                     min_bytes=min_bytes))
