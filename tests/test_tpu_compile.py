"""The tick kernels compiled for a described TPU v5e at a real chip's share.

Interpret mode checks the kernels' maths but not what the TPU compiler
accepts: block tiling, VMEM use, and whether a launch fits in HBM.  These
tests compile each kernel of the fused tick for one chip of a described
``v5e:2x2`` topology (no chip attached, nothing runs) at N = 411,078,656,
the parameter count of stablelm-1.6b at 4 layers that ``chip_smoke.py``
trains, which is not a multiple of any block the launches choose.  Each compile
must hold a Pallas kernel (``tpu_custom_call``) and, with params, ring and
optimizer state donated as the engines donate them, fit one chip's HBM,
and update params, ring and state in place: one launch that aliases them, and
no copy of a flat buffer or of the ring.  The launches the benchmark's cells
run are also compiled at stablelm-1.6b's N at 4 layers with its untied head
(where an f32 ring would not fit one chip), and each must stream wide blocks.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.adaptive_update import fused
from repro.kernels.adaptive_update.fused import (
    SCALAR_ORDER,
    fused_chain_call,
    fused_combine_call,
    fused_tick_call,
)

N = 411_078_656
N_CELL = 616_599_552  # the benchmark's stablelm-1.6b-4l
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


def _vec(sharding, n=N):
    return _sds((n,), jnp.float32, sharding)


def _ring(dtype, sharding, n=N):
    return _sds((K, n), dtype, sharding)


def _kvec(sharding):
    return _sds((K, 1), jnp.float32, sharding)


def _scalars(kind, sharding):
    return {k: _sds((), jnp.float32, sharding) for k in SCALAR_ORDER[kind]}


def _bufs(kind, sharding, n=N):
    return tuple(_vec(sharding, n) for _ in range(N_BUFS[kind]))


def _check_fits(compiled, n_aliased, n=N):
    """One Pallas launch that aliases ``n_aliased`` operands to its outputs,
    no copy of an ``n``-element buffer, and the program within HBM."""
    text = compiled.as_text()
    launches = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(launches) == 1, launches
    aliasing = re.search(r"output_to_operand_aliasing=\{(.*?)\}, ", launches[0])
    assert aliasing and len(re.findall(r"\{\d*\}: \(\d+, \{\}\)", aliasing.group(1))) == n_aliased
    copies = re.findall(rf"= \w+\[(?:\d+,)*{n}\]\S* copy\(", text)
    assert not copies, copies
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
    _check_fits(compiled, 2 + N_BUFS[kind])  # p, ring, state


@pytest.mark.parametrize("ring_dtype", RING_DTYPES)
def test_combine_kernel_fits_one_chip(ring_dtype, one_chip):
    combine = jax.jit(
        functools.partial(fused_combine_call, interpret=False), donate_argnums=(1,)
    )
    compiled = combine.lower(
        _vec(one_chip), _ring(ring_dtype, one_chip), _kvec(one_chip), _kvec(one_chip)
    ).compile()
    _check_fits(compiled, 1)  # ring


@pytest.mark.parametrize("kind", KINDS)
def test_chain_kernel_fits_one_chip(kind, one_chip):
    chain = jax.jit(
        functools.partial(fused_chain_call, kind, interpret=False),
        donate_argnums=(0, 2),  # p, bufs
    )
    compiled = chain.lower(
        _vec(one_chip), _vec(one_chip), _bufs(kind, one_chip), _scalars(kind, one_chip)
    ).compile()
    _check_fits(compiled, 1 + N_BUFS[kind])  # p, state


@pytest.mark.parametrize("launch", ["tick", "chain"])
def test_cell_launch_fits_one_chip_in_wide_blocks(launch, one_chip, monkeypatch):
    """The momentum launches the cells run, at the cells' N: the tick with a
    K = 4 bf16 ring and the sync chain.  Each must fit, update in place, and
    stream blocks of at least 32,768 elements (at 8,192 a fixed cost per grid
    step took about half the launch on a v5e)."""
    blocks = []

    def spy(n, operands):
        blocks.append(real(n, operands))
        return blocks[-1]

    real = fused.block_elems
    monkeypatch.setattr(fused, "block_elems", spy)
    vec, bufs = _vec(one_chip, N_CELL), _bufs("momentum", one_chip, N_CELL)
    scalars = _scalars("momentum", one_chip)
    if launch == "tick":
        fused_tick_call.clear_cache()
        compiled = jax.jit(
            functools.partial(fused_tick_call, "momentum", interpret=False),
            donate_argnums=(0, 2, 4),
        ).lower(
            vec, vec, bufs, scalars, _ring(jnp.bfloat16, one_chip, N_CELL),
            _kvec(one_chip), _kvec(one_chip),
        ).compile()
    else:
        fused_chain_call.clear_cache()
        compiled = jax.jit(
            functools.partial(fused_chain_call, "momentum", interpret=False),
            donate_argnums=(0, 2),
        ).lower(vec, vec, bufs, scalars).compile()
    _check_fits(compiled, 3 if launch == "tick" else 2, n=N_CELL)
    assert len(blocks) == 1 and blocks[0] >= 32_768, blocks


# -- names the benchmark's trace reduction reads ----------------------------

BODY_SCOPES = (
    "jvp(param_view)", "transpose(jvp(param_view))", "jvp(forward)", "transpose(jvp(forward))",
)
STEP_SCOPES = {"async": BODY_SCOPES + ("staleness", "update"), "sync": BODY_SCOPES + ("update",)}


@pytest.mark.parametrize("mode", sorted(STEP_SCOPES))
def test_step_scopes_survive_compilation(mode, one_chip):
    """The step body's named scopes reach the compiled program's ``op_name``
    metadata, which the device trace carries.  At tiny widths; the step
    takes the CPU branch of ``use_pallas``, which holds the same scopes."""
    import re

    from repro.configs import get_config, reduced
    from repro.data import make_batch_for
    from repro.launch.train import mindthestep_pipeline
    from repro.training.steps import init_train_state, make_step

    cfg = reduced(get_config("stablelm-1.6b"), d_model=64)
    W = 4 if mode == "async" else 1
    pipeline, adapt = mindthestep_pipeline(0.01, W, K, momentum=0.9, staleness=mode == "async")
    step = make_step(cfg, pipeline, mode=mode, num_workers=W, fuse=True)
    state = jax.eval_shape(
        lambda key: init_train_state(
            key, cfg, pipeline, async_ring=K if mode == "async" else 0, adapt=adapt,
            fuse=True, ring_dtype=jnp.bfloat16,
        ),
        jax.random.PRNGKey(0),
    )
    batch = jax.eval_shape(lambda: make_batch_for(cfg, batch=2, seq=16, seed=0))
    shapes = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), (state, batch))
    text = jax.jit(step, donate_argnums=(0,)).lower(*shapes).compile().as_text()
    components = {
        c for path in re.findall(r'op_name="([^"]*)"', text)
        for p in path.split(";") for c in p.split("/")
    }
    assert set(STEP_SCOPES[mode]) <= components, set(STEP_SCOPES[mode]) - components


def test_update_launch_keeps_its_name(one_chip):
    """A ``fused_tick_call`` nested in a jitted step, as the step calls it,
    compiles to a Pallas launch whose instruction name the benchmark's trace
    reduction finds (``bench/trace.py::UPDATE_KERNEL``)."""
    import re

    from bench.trace import UPDATE_KERNEL

    n = 3 * 8192

    def step(p, g, bufs, scalars, ring, push, w_slot):
        with jax.named_scope("update"):
            return fused_tick_call("momentum", p * 1.0, g, bufs, scalars, ring, push, w_slot)

    vec = _sds((n,), jnp.float32, one_chip)
    kvec = _sds((K, 1), jnp.float32, one_chip)
    text = jax.jit(step).lower(
        vec, vec, (vec,), _scalars("momentum", one_chip),
        _sds((K, n), jnp.bfloat16, one_chip), kvec, kvec,
    ).compile().as_text()
    launches = re.findall(r"^\s*(?:ROOT )?(%\S+) = .*tpu_custom_call", text, re.M)
    assert launches
    assert all(UPDATE_KERNEL.search(name) for name in launches), launches
