"""Compile the served scan programs for a described TPU v5e, no chip needed.

Interpret mode never checks Mosaic's tiling rules or VMEM budget; the TPU
compiler does, and it runs here against a described (not attached) chip.
Each case compiles at deployment widths (millions of rows, int8/int16
columns, 1,000+ groups, Q up to 64) and asserts the compiled program holds
the Mosaic kernel. Keep every such compile in this one file: the topology is
described inside a fixture, so only the worker that runs this file loads
the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import executor as exec_lib
from repro.core.types import CmpOp
from repro.kernels import ops as kops
from repro.kernels.agg_scan import (MAX_FUSED_STRATA, agg_scan_fused_pallas,
                                    quantile_scan_pallas)

N_ROWS = 4_000_000
V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_v5e(one_chip):
    """compile(fn, *shapes) -> compiled text, with the persistent cache off
    (an entry compiled for a described chip cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM
        return compiled.as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("q,n_groups,code_dtype,n_strata", [
    (1, 2000, jnp.int8, 4096), (64, 1000, jnp.int16, 384),
    (64, 200, jnp.int16, 15000), (2, 512, jnp.int16, MAX_FUSED_STRATA)])
def test_fused_scan_compiles_for_v5e(one_chip, compile_v5e, q, n_groups,
                                     code_dtype, n_strata):
    s = lambda shape, dt: _shape(one_chip, shape, dt)   # noqa: E731
    n = N_ROWS
    args = (s((n,), jnp.float32), s((n,), jnp.float32), s((n,), jnp.int16),
            s((n_strata,), jnp.float32), s((n,), jnp.bool_),
            (s((n,), code_dtype), s((n,), jnp.int8)), s((n,), jnp.int16),
            s((q,), jnp.float32), s((q, 3), jnp.float32))
    text = compile_v5e(lambda *a: agg_scan_fused_pallas(
        *a, ops_struct=((CmpOp.EQ, CmpOp.LT), (CmpOp.GE,)),
        atom_slots=(0, 1, 0), n_groups=n_groups), *args)
    assert "tpu_custom_call" in text


def test_quantile_scan_compiles_for_v5e(one_chip, compile_v5e):
    s = lambda shape, dt: _shape(one_chip, shape, dt)   # noqa: E731
    n = N_ROWS
    args = (s((n,), jnp.float32), s((n,), jnp.float32), s((n,), jnp.int8),
            s((128,), jnp.float32), s((n,), jnp.bool_), (s((n,), jnp.int8),),
            s((n,), jnp.int16), s((), jnp.float32), s((), jnp.float32),
            s((), jnp.float32), s((1,), jnp.float32))
    text = compile_v5e(lambda *a: quantile_scan_pallas(
        *a, ops_struct=((CmpOp.EQ,),), n_groups=200, n_bins=256), *args)
    assert "tpu_custom_call" in text


def test_quantile_scan_compiles_for_v5e_wide_family(one_chip, compile_v5e):
    """The one-pass QUANTILE kernel over a (day, URL)-like family: 15,000
    strata, int16 ids."""
    s = lambda shape, dt: _shape(one_chip, shape, dt)   # noqa: E731
    n = N_ROWS
    args = (s((n,), jnp.float32), s((n,), jnp.float32), s((n,), jnp.int16),
            s((15000,), jnp.float32), s((n,), jnp.bool_),
            (s((n,), jnp.int8), s((n,), jnp.int16)), s((n,), jnp.int16),
            s((), jnp.float32), s((), jnp.float32), s((), jnp.float32),
            s((2,), jnp.float32))
    text = compile_v5e(lambda *a: quantile_scan_pallas(
        *a, ops_struct=((CmpOp.EQ, CmpOp.EQ),), n_groups=200, n_bins=256),
        *args)
    assert "tpu_custom_call" in text


def test_batched_executor_program_compiles_for_v5e(one_chip, compile_v5e,
                                                   monkeypatch):
    """The engine's vmapped batched program around the fused kernel. The
    executor reads the CPU backend here, so the test turns interpret mode
    off itself."""
    monkeypatch.setattr(kops, "INTERPRET", False)
    s = lambda shape, dt: _shape(one_chip, shape, dt)   # noqa: E731
    n, q = N_ROWS, 16
    cols = {"City": s((1, n), jnp.int16), "OS": s((1, n), jnp.int8),
            "Genre": s((1, n), jnp.int8),
            "SessionTime": s((1, n), jnp.float32)}
    struct = ((("OS", CmpOp.EQ), ("Genre", CmpOp.EQ)),)
    fn = exec_lib.make_batched_query_fn(struct, "SessionTime", "City", 200,
                                        use_pallas=True)
    text = compile_v5e(fn, s((q,), jnp.float32), s((q, 2), jnp.float32),
                       cols, s((1, n), jnp.float32), s((1, n), jnp.int16),
                       s((256,), jnp.float32), s((1, n), jnp.bool_))
    assert "tpu_custom_call" in text


def _served_tail(s, n):
    cols = {"City": s((1, n), jnp.int16), "OS": s((1, n), jnp.int8),
            "Genre": s((1, n), jnp.int8),
            "SessionTime": s((1, n), jnp.float32)}
    return (cols, s((1, n), jnp.float32), s((1, n), jnp.int16),
            s((15104,), jnp.float32), s((1, n), jnp.bool_),
            s((3,), jnp.int32))


@pytest.mark.parametrize("builder", ["fused", "quantile"])
def test_bounded_grid_compiles_for_v5e_under_the_served_vmap(
        one_chip, compile_v5e, monkeypatch, builder):
    """The engine's single-query and QUANTILE programs with their row grid
    bounded by a traced `spans` argument: the scalar-prefetched bound stays
    unbatched under the vmap over shards, and Mosaic compiles the kernel."""
    monkeypatch.setattr(kops, "INTERPRET", False)
    s = lambda shape, dt: _shape(one_chip, shape, dt)   # noqa: E731
    struct = ((("OS", CmpOp.EQ), ("Genre", CmpOp.EQ)),)
    vals = ((s((), jnp.float32), s((), jnp.float32)),)
    if builder == "fused":
        fn = exec_lib.make_query_fn(struct, "SessionTime", "City", 200,
                                    use_pallas=True)
        head = (s((), jnp.float32), vals)
    else:
        fn = exec_lib.make_quantile_fn(struct, "SessionTime", "City", 200,
                                       use_pallas=True)
        head = (s((), jnp.float32), vals) + (s((), jnp.float32),) * 3
    text = compile_v5e(fn, *head, *_served_tail(s, N_ROWS))
    assert "tpu_custom_call" in text
