"""`jax.shard_map` with the replication check off.

Every in-repo user imports shard_map from here. The solvers run
`psum`-combined scans whose carries the static replication checker
cannot type (it reports "Scan carry input and output got mismatched
replication types" and suggests exactly this flag); the numerics are
pinned independently against the single-chip solve in
tests/test_sharded.py. Callers can pass `check_vma=True` to re-enable it.
"""

from __future__ import annotations

import jax


def shard_map(f, *args, **kwargs):
    """`jax.shard_map` with `check_vma=False` unless overridden."""
    kwargs.setdefault("check_vma", False)
    return jax.shard_map(f, *args, **kwargs)
