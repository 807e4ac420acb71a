"""Ahead-of-time compiles of the tick's kernels for a described TPU v5e.

No chip is attached here: the TPU compiler compiles for a topology that
is only described, at the 1M-lease x 10k-resource dense shape
[10240, 128] f32. A kernel that interpret mode accepts can still be
refused by Mosaic (a layout it cannot relayout, more scoped VMEM than a
core has); these tests catch that before a chip run does.

Only one process may load libtpu at a time, so the topology is
described inside a module-scoped fixture of this one file, never at
import: under xdist only the worker given this file loads the library.
"""

import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import tests.conftest  # noqa: F401

from doorman_tpu.solver.dense import DenseBatch, solve_dense
from doorman_tpu.solver.pallas_dense import (
    fused_tick_pallas,
    solve_dense_pallas,
)
from doorman_tpu.solver.pallas_priority import alloc_banded_pallas

R, K = 10240, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def no_compile_cache():
    """A compile for a described chip would be written to the cache
    and could never be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _batch(sh):
    f32 = jnp.float32
    return DenseBatch(
        wants=_shape(sh, (R, K), f32),
        has=_shape(sh, (R, K), f32),
        subclients=_shape(sh, (R, K), f32),
        active=_shape(sh, (R, K), jnp.bool_),
        capacity=_shape(sh, (R,), f32),
        algo_kind=_shape(sh, (R,), jnp.int32),
        learning=_shape(sh, (R,), jnp.bool_),
        static_capacity=_shape(sh, (R,), f32),
    )


def _args(case, sh):
    f32 = jnp.float32
    if case == "solve_dense_pallas":
        return lambda b: solve_dense_pallas(b), (_batch(sh),)
    if case in ("fused_tick_pallas_f32_prev", "fused_tick_pallas_bf16_prev"):
        prev = jnp.bfloat16 if case.endswith("bf16_prev") else f32
        return (
            lambda b, p, d: fused_tick_pallas(b, p, d),
            (_batch(sh), _shape(sh, (R, K), prev), _shape(sh, (R,), f32)),
        )
    if case == "alloc_banded_pallas":
        return (
            lambda w, wt, band, act, cap: alloc_banded_pallas(
                w, wt, band, act, cap, num_bands=4
            ),
            (
                _shape(sh, (R, K), f32),
                _shape(sh, (R, K), f32),
                _shape(sh, (R, K), jnp.int32),
                _shape(sh, (R, K), jnp.bool_),
                _shape(sh, (R,), f32),
            ),
        )
    assert case == "xla_solve_dense"
    return solve_dense, (_batch(sh),)


@pytest.mark.parametrize(
    "case,kernel",
    [
        ("solve_dense_pallas", True),
        ("fused_tick_pallas_f32_prev", True),
        ("fused_tick_pallas_bf16_prev", True),
        ("alloc_banded_pallas", True),
        ("xla_solve_dense", False),
    ],
)
def test_compiles_for_v5e(one_chip, case, kernel):
    fn, args = _args(case, one_chip)
    with no_compile_cache():
        compiled = jax.jit(fn).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
