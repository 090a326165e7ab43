"""Fused MindTheStep parameter-server update — Pallas TPU kernel.

The paper's server hot spot (§IV: the apply step is "exactly d floating point
multiplications and additions") is elementwise over every parameter:

    v <- mu * v - alpha(tau) * g        (momentum buffer, optional)
    x <- x + v

Unfused, that is 3 full HBM passes (read v, read g + write v, read/write x).
This kernel fuses scale + momentum + apply into ONE pass over a padded
``(R, 128)`` view: each ``(BLOCK_ROWS, 128)`` VMEM tile is read once and
written once.  The fused-chain launches of :mod:`.fused` work on the flat
buffers as stored and size their own, much wider blocks.

``alpha`` arrives as a (1, 1) scalar tile (SMEM-friendly) so the same
compiled kernel serves every staleness value — the alpha(tau) gather happens
outside, in :mod:`repro.optim.mindthestep`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["fused_update_call", "BLOCK_ROWS", "LANES"]

LANES = 128  # TPU lane width
BLOCK_ROWS = 64  # sublane tile: (64, 128) f32 = 32 KiB per operand in VMEM


def _update_kernel(alpha_ref, mu_ref, p_ref, g_ref, v_ref, p_out_ref, v_out_ref):
    """One (BLOCK_ROWS, LANES) tile: v' = mu v - alpha g; p' = p + v'."""
    alpha = alpha_ref[0, 0]
    mu = mu_ref[0, 0]
    g = g_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    v_new = mu * v - alpha * g
    v_out_ref[...] = v_new.astype(v_out_ref.dtype)
    p_out_ref[...] = (p_ref[...].astype(jnp.float32) + v_new).astype(p_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_update_call(
    p2d: jnp.ndarray,  # (R, 128) padded parameter tile view
    g2d: jnp.ndarray,
    v2d: jnp.ndarray,
    alpha: jnp.ndarray,  # scalar
    mu: jnp.ndarray,  # scalar
    *,
    interpret: bool = True,
):
    R = p2d.shape[0]
    assert p2d.shape[1] == LANES and R % BLOCK_ROWS == 0
    grid = (R // BLOCK_ROWS,)
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    tile = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _update_kernel,
        grid=grid,
        in_specs=[scalar_spec, scalar_spec, tile, tile, tile],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct(p2d.shape, p2d.dtype),
            jax.ShapeDtypeStruct(v2d.shape, v2d.dtype),
        ],
        interpret=interpret,
    )(alpha.reshape(1, 1).astype(jnp.float32), mu.reshape(1, 1).astype(jnp.float32), p2d, g2d, v2d)
