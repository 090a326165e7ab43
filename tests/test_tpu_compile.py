"""The tick kernels compiled for a described TPU v5e at a real chip's share.

Interpret mode checks the kernels' maths but not what the TPU compiler
accepts: block tiling, VMEM use, and whether a launch fits in HBM.  These
tests compile each kernel of the fused tick for one chip of a described
``v5e:2x2`` topology (no chip attached, nothing runs) at N = 411,078,656,
the parameter count of stablelm-1.6b at 4 layers that ``chip_smoke.py``
trains, which is not a multiple of the 8192-element block.  Each compile
must hold a Pallas kernel (``tpu_custom_call``) and, with params, ring and
optimizer state donated as the engines donate them, fit one chip's HBM.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.adaptive_update.fused import (
    SCALAR_ORDER,
    fused_chain_call,
    fused_combine_call,
    fused_tick_call,
)

N = 411_078_656
K = 4
HBM_BYTES = 15.75e9  # what the v5e compiler lets one program use
N_BUFS = {"sgd": 0, "momentum": 1, "adam": 2}
KINDS = tuple(N_BUFS)
RING_DTYPES = ("float32", "bfloat16")


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off: an entry compiled for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _vec(sharding):
    return _sds((N,), jnp.float32, sharding)


def _ring(dtype, sharding):
    return _sds((K, N), dtype, sharding)


def _kvec(sharding):
    return _sds((K, 1), jnp.float32, sharding)


def _scalars(kind, sharding):
    return {k: _sds((), jnp.float32, sharding) for k in SCALAR_ORDER[kind]}


def _bufs(kind, sharding):
    return tuple(_vec(sharding) for _ in range(N_BUFS[kind]))


def _check_fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )
    assert total <= HBM_BYTES, f"{total / 1e9:.2f} GB of {HBM_BYTES / 1e9:.2f}"
    # the kernels work on the buffers as stored: no padded copy per launch
    assert m.temp_size_in_bytes < 2**20, f"temp {m.temp_size_in_bytes} bytes"


@pytest.mark.parametrize("ring_dtype", RING_DTYPES)
@pytest.mark.parametrize("kind", KINDS)
def test_tick_kernel_fits_one_chip(kind, ring_dtype, one_chip):
    tick = jax.jit(
        functools.partial(fused_tick_call, kind, interpret=False),
        donate_argnums=(0, 2, 4),  # p, bufs, ring
    )
    compiled = tick.lower(
        _vec(one_chip), _vec(one_chip), _bufs(kind, one_chip),
        _scalars(kind, one_chip), _ring(ring_dtype, one_chip),
        _kvec(one_chip), _kvec(one_chip),
    ).compile()
    _check_fits(compiled)


@pytest.mark.parametrize("ring_dtype", RING_DTYPES)
def test_combine_kernel_fits_one_chip(ring_dtype, one_chip):
    combine = jax.jit(
        functools.partial(fused_combine_call, interpret=False), donate_argnums=(1,)
    )
    compiled = combine.lower(
        _vec(one_chip), _ring(ring_dtype, one_chip), _kvec(one_chip), _kvec(one_chip)
    ).compile()
    _check_fits(compiled)


@pytest.mark.parametrize("kind", KINDS)
def test_chain_kernel_fits_one_chip(kind, one_chip):
    chain = jax.jit(
        functools.partial(fused_chain_call, kind, interpret=False),
        donate_argnums=(0, 2),  # p, bufs
    )
    compiled = chain.lower(
        _vec(one_chip), _vec(one_chip), _bufs(kind, one_chip), _scalars(kind, one_chip)
    ).compile()
    _check_fits(compiled)
