"""Fused-chain Pallas TPU kernels: one flat-buffer pass per optimizer family.

The fusion compiler (:mod:`repro.optim.fuse`) lowers a whole ``chain()``
pipeline to ONE kernel launch per step.  Three kernels cover the supported
bodies — ``sgd`` (scale + apply), ``momentum`` (scale + trace + apply) and
``adam`` (preconditioner + scale + apply) — and the staleness / drop / clip
links enter as SCALAR factors (``f_stale``/``f_keep``/``f_clip``), so the
"± clip" variants reuse the same kernels: the norm reduction happens outside
(it is a second data pass by nature) and only its scalar result is fused in.

Every ``_TILE``-element block of ``p``/``g``/state is read once and written
once — the whole server update is a single HBM pass no matter how many links
the chain has, vs one read+write pass PER LINK for the link-by-link
``tree.map`` execution.  Scalars ride as (1, 1) SMEM-friendly tiles exactly
like the original ``adaptive_update`` kernel, so one compiled kernel serves
every staleness value / clip factor / bias-correction step.

The kernels work on the flat buffers as they are stored: 1-D ``(N,)``
params / gradient / state and the ``(K, N)`` ring, blocked ``_TILE``
elements at a time over a ``cdiv(N, _TILE)`` grid, so a ragged tail is the
partial last block (masked by Pallas) and nothing is padded, reshaped or
sliced around the launch.  Params, ring and optimizer state are aliased
input -> output (``input_output_aliases``): under a donating jit the kernel
updates them in place, and the step holds one copy of each.

Scalar factors are applied sequentially in link order (never pre-multiplied):
float multiplication is not associative, and bit-equality with the unfused
pipeline is the contract (`f = 1.0` for an absent link is bitwise exact).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.adaptive_update.kernel import BLOCK_ROWS, LANES
from repro.kernels.adaptive_update.ref import fused_chain_ref, fused_tick_ref

__all__ = [
    "fused_chain_call",
    "fused_chain_flat",
    "fused_tick_call",
    "fused_tick_flat",
    "fused_combine_call",
    "fused_combine_flat",
    "SCALAR_ORDER",
]

_TILE = BLOCK_ROWS * LANES

# Scalar bundle keys per family, in kernel-operand order.
SCALAR_ORDER = {
    "sgd": ("f_stale", "f_keep", "f_clip", "m_scale"),
    "momentum": ("f_stale", "f_keep", "f_clip", "m_scale", "mu"),
    "adam": (
        "f_stale",
        "f_keep",
        "f_clip",
        "m_scale",
        "b1",
        "omb1",
        "b2",
        "omb2",
        "eps",
        "c1",
        "c2",
    ),
}


def _prefix(u, fs_ref, fk_ref, fc_ref):
    """staleness -> drop -> clip scalar factors, in link order."""
    u = fs_ref[0, 0] * u
    u = u * fk_ref[0, 0]
    return u * fc_ref[0, 0]


def _sgd_kernel(fs_ref, fk_ref, fc_ref, ms_ref, p_ref, g_ref, p_out_ref):
    u = _prefix(g_ref[...].astype(jnp.float32), fs_ref, fk_ref, fc_ref)
    u = ms_ref[0, 0] * u
    p_out_ref[...] = (p_ref[...].astype(jnp.float32) + u).astype(p_out_ref.dtype)


def _momentum_kernel(
    fs_ref, fk_ref, fc_ref, ms_ref, mu_ref, p_ref, g_ref, v_ref, p_out_ref, v_out_ref
):
    u = _prefix(g_ref[...].astype(jnp.float32), fs_ref, fk_ref, fc_ref)
    u = ms_ref[0, 0] * u
    v_new = mu_ref[0, 0] * v_ref[...].astype(jnp.float32) + u
    v_out_ref[...] = v_new.astype(v_out_ref.dtype)
    p_out_ref[...] = (p_ref[...].astype(jnp.float32) + v_new).astype(p_out_ref.dtype)


def _adam_kernel(
    fs_ref,
    fk_ref,
    fc_ref,
    ms_ref,
    b1_ref,
    omb1_ref,
    b2_ref,
    omb2_ref,
    eps_ref,
    c1_ref,
    c2_ref,
    p_ref,
    g_ref,
    m_ref,
    v_ref,
    p_out_ref,
    m_out_ref,
    v_out_ref,
):
    u = _prefix(g_ref[...].astype(jnp.float32), fs_ref, fk_ref, fc_ref)
    m_new = b1_ref[0, 0] * m_ref[...].astype(jnp.float32) + omb1_ref[0, 0] * u
    v_new = b2_ref[0, 0] * v_ref[...].astype(jnp.float32) + omb2_ref[0, 0] * jnp.square(u)
    out = (m_new * c1_ref[0, 0]) / (jnp.sqrt(v_new * c2_ref[0, 0]) + eps_ref[0, 0])
    u2 = ms_ref[0, 0] * out
    m_out_ref[...] = m_new.astype(m_out_ref.dtype)
    v_out_ref[...] = v_new.astype(v_out_ref.dtype)
    p_out_ref[...] = (p_ref[...].astype(jnp.float32) + u2).astype(p_out_ref.dtype)


_KERNELS = {
    # kind -> (kernel body, number of flat state buffers)
    "sgd": (_sgd_kernel, 0),
    "momentum": (_momentum_kernel, 1),
    "adam": (_adam_kernel, 2),
}


_SCALAR = pl.BlockSpec((1, 1), lambda i: (0, 0))
_VEC = pl.BlockSpec((_TILE,), lambda i: (i,))


def _grid(n: int) -> tuple[int]:
    return (pl.cdiv(n, _TILE),)


def _ring_block(K: int) -> pl.BlockSpec:
    return pl.BlockSpec((K, _TILE), lambda i: (0, i))


def _scalar_tiles(kind: str, scalars) -> list:
    return [jnp.asarray(scalars[k], jnp.float32).reshape(1, 1) for k in SCALAR_ORDER[kind]]


def _shape_of(x) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def fused_chain_call(kind: str, p, g, bufs, scalars, *, interpret: bool = False):
    """One Pallas launch for a fused chain step on flat ``(N,)`` buffers.

    ``bufs`` is the family's flat state tuple (see ``_KERNELS``), ``scalars``
    the f32 scalar bundle keyed per ``SCALAR_ORDER[kind]``.  Returns
    ``(p_new, new_bufs)``; ``p`` and ``bufs`` are aliased to them.
    """
    kernel, n_bufs = _KERNELS[kind]
    bufs = tuple(bufs)
    assert len(bufs) == n_bufs, f"{kind} expects {n_bufs} state buffers, got {len(bufs)}"
    svals = _scalar_tiles(kind, scalars)
    S = len(svals)
    out = pl.pallas_call(
        kernel,
        grid=_grid(p.shape[0]),
        in_specs=[_SCALAR] * S + [_VEC] * (2 + n_bufs),
        out_specs=[_VEC] * (1 + n_bufs),
        out_shape=[_shape_of(p)] + [_shape_of(b) for b in bufs],
        # p -> p_new, bufs[j] -> new_bufs[j]  (operand g at S + 1 is read-only)
        input_output_aliases={S: 0, **{S + 2 + j: 1 + j for j in range(n_bufs)}},
        interpret=interpret,
    )(*svals, p, g, *bufs)
    return out[0], tuple(out[1:])


def fused_chain_flat(
    kind: str,
    p: jnp.ndarray,
    g: jnp.ndarray,
    bufs,
    scalars,
    *,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """Production dispatch for one fused chain step on flat 1-D buffers.

    ``use_pallas=None`` auto-selects the Pallas kernel on TPU (interpret OFF —
    one real HBM pass) and the XLA reference elsewhere; both lower to the same
    one-pass data movement and identical f32 numerics
    (:func:`~repro.kernels.adaptive_update.ref.fused_chain_ref` is the oracle).
    ``bufs``/return mirror :func:`fused_chain_call` except that the ref path
    keeps adam's state as the ``{"m", "v"}`` dict it receives.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        if kind == "adam":
            p_new, (m_new, v_new) = fused_chain_call(
                kind, p, g, (bufs["m"], bufs["v"]), scalars, interpret=interpret
            )
            return p_new, {"m": m_new, "v": v_new}
        kernel_bufs = () if kind == "sgd" else (bufs,)
        p_new, new_bufs = fused_chain_call(kind, p, g, kernel_bufs, scalars, interpret=interpret)
        return p_new, (bufs if kind == "sgd" else new_bufs[0])
    return fused_chain_ref(kind, p, g, bufs, scalars)


# ---------------------------------------------------------------------------
# One-launch async tick: ring push + weighted combine fused into the chain
# ---------------------------------------------------------------------------
#
# The tick kernels take the whole flat-resident delayed ring as its (K, N)
# operand, blocked over the SAME grid as p/g/state: each grid step owns a
# (K, _TILE) ring block, pushes the fresh gradient into
# slot t%K via a one-hot select, contracts the K slots against the slot-folded
# combine weights, and feeds the result straight into the chain body — params,
# ring slot and optimizer state are all written in the same pass, so the whole
# server tick is ONE launch (the clip variant keeps its separate combine
# launch: the norm is a reduction between combine and apply by nature).
#
# Slot folding: the per-worker weights w[w] land on ring slots as
# ``w_slot[k] = sum_{w: slot(tau_w)=k} w[w] * live[w]`` — workers sharing a
# slot fold BEFORE the multiply, whereas the unfused weighted sum adds after.
# Same value to f32 round-off, not bitwise; the production CPU/GPU path
# therefore runs ``fused_tick_ref`` (exact composition of the unfused ops)
# and the Pallas tick is tolerance-tested under the ``pallas`` mark.


def _tick_combine(push_ref, wsl_ref, g_ref, r_ref, r_out_ref):
    """Push the fresh gradient into the ring block and combine the K slots."""
    r = r_ref[...]  # (K, _TILE)
    g = g_ref[...].astype(r.dtype)  # (_TILE,): the push stores the ring-dtype cast
    r_new = jnp.where(push_ref[...] > 0, g[None, :], r)  # push: (K, 1) one-hot
    r_out_ref[...] = r_new
    return jnp.sum(wsl_ref[...] * r_new.astype(jnp.float32), axis=0)  # (K, 1) weights


def _sgd_tick_kernel(
    fs_ref, fk_ref, fc_ref, ms_ref, push_ref, wsl_ref, p_ref, g_ref, r_ref,
    p_out_ref, r_out_ref,
):
    u = _tick_combine(push_ref, wsl_ref, g_ref, r_ref, r_out_ref)
    u = _prefix(u, fs_ref, fk_ref, fc_ref)
    u = ms_ref[0, 0] * u
    p_out_ref[...] = (p_ref[...].astype(jnp.float32) + u).astype(p_out_ref.dtype)


def _momentum_tick_kernel(
    fs_ref, fk_ref, fc_ref, ms_ref, mu_ref, push_ref, wsl_ref, p_ref, g_ref,
    r_ref, v_ref, p_out_ref, r_out_ref, v_out_ref,
):
    u = _tick_combine(push_ref, wsl_ref, g_ref, r_ref, r_out_ref)
    u = _prefix(u, fs_ref, fk_ref, fc_ref)
    u = ms_ref[0, 0] * u
    v_new = mu_ref[0, 0] * v_ref[...].astype(jnp.float32) + u
    v_out_ref[...] = v_new.astype(v_out_ref.dtype)
    p_out_ref[...] = (p_ref[...].astype(jnp.float32) + v_new).astype(p_out_ref.dtype)


def _adam_tick_kernel(
    fs_ref, fk_ref, fc_ref, ms_ref, b1_ref, omb1_ref, b2_ref, omb2_ref,
    eps_ref, c1_ref, c2_ref, push_ref, wsl_ref, p_ref, g_ref, r_ref, m_ref,
    v_ref, p_out_ref, r_out_ref, m_out_ref, v_out_ref,
):
    u = _tick_combine(push_ref, wsl_ref, g_ref, r_ref, r_out_ref)
    u = _prefix(u, fs_ref, fk_ref, fc_ref)
    m_new = b1_ref[0, 0] * m_ref[...].astype(jnp.float32) + omb1_ref[0, 0] * u
    v_new = b2_ref[0, 0] * v_ref[...].astype(jnp.float32) + omb2_ref[0, 0] * jnp.square(u)
    out = (m_new * c1_ref[0, 0]) / (jnp.sqrt(v_new * c2_ref[0, 0]) + eps_ref[0, 0])
    u2 = ms_ref[0, 0] * out
    m_out_ref[...] = m_new.astype(m_out_ref.dtype)
    v_out_ref[...] = v_new.astype(v_out_ref.dtype)
    p_out_ref[...] = (p_ref[...].astype(jnp.float32) + u2).astype(p_out_ref.dtype)


_TICK_KERNELS = {
    "sgd": (_sgd_tick_kernel, 0),
    "momentum": (_momentum_tick_kernel, 1),
    "adam": (_adam_tick_kernel, 2),
}


def _slot_weights(K: int, step, taus, weights):
    """Trace the push one-hot, the slot-folded combine weights and the drop
    mask for one tick — (K, 1) operand shapes, matching the scalar tiles."""
    slot = jnp.mod(step, K)
    src_step = step - taus
    src_slot = jnp.mod(src_step, K)
    live = ((src_step >= 0) & (taus < K)).astype(jnp.float32)
    w = jnp.asarray(weights, jnp.float32) * live
    push = jax.nn.one_hot(slot, K, dtype=jnp.float32).reshape(K, 1)
    w_slot = jnp.zeros((K,), jnp.float32).at[src_slot].add(w).reshape(K, 1)
    return push, w_slot, live


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def fused_tick_call(kind: str, p, g, bufs, scalars, ring, push, w_slot, *, interpret: bool = False):
    """One Pallas launch for a whole async tick on flat ``(N,)`` buffers.

    ``ring`` is the flat ``(K, N)`` delayed ring; ``push`` / ``w_slot`` the
    ``(K, 1)`` one-hot push selector and slot-folded combine weights from
    :func:`_slot_weights`.  Returns ``(p_new, new_bufs, new_ring)``; ``p``,
    ``bufs`` and ``ring`` are aliased to them.
    """
    kernel, n_bufs = _TICK_KERNELS[kind]
    bufs = tuple(bufs)
    assert len(bufs) == n_bufs, f"{kind} expects {n_bufs} state buffers, got {len(bufs)}"
    K = ring.shape[0]
    svals = _scalar_tiles(kind, scalars)
    S = len(svals)
    kvec = pl.BlockSpec((K, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        kernel,
        grid=_grid(p.shape[0]),
        in_specs=[_SCALAR] * S + [kvec, kvec, _VEC, _VEC, _ring_block(K)] + [_VEC] * n_bufs,
        out_specs=[_VEC, _ring_block(K)] + [_VEC] * n_bufs,
        out_shape=[_shape_of(p), _shape_of(ring)] + [_shape_of(b) for b in bufs],
        # p -> p_new, ring -> new_ring, bufs[j] -> new_bufs[j]
        input_output_aliases={
            S + 2: 0, S + 4: 1, **{S + 5 + j: 2 + j for j in range(n_bufs)}
        },
        interpret=interpret,
    )(*svals, push, w_slot, p, g, ring, *bufs)
    return out[0], tuple(out[2:]), out[1]


def _combine_kernel(push_ref, wsl_ref, g_ref, r_ref, g_out_ref, r_out_ref):
    g_out_ref[...] = _tick_combine(push_ref, wsl_ref, g_ref, r_ref, r_out_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_combine_call(g, ring, push, w_slot, *, interpret: bool = False):
    """One Pallas launch for push + weighted combine only: ``(g_eff, new_ring)``.

    The two-launch tick of the clip variant (norm reduction between combine
    and apply).  ``ring`` is aliased to ``new_ring``.
    """
    K = ring.shape[0]
    kvec = pl.BlockSpec((K, 1), lambda i: (0, 0))
    g_eff, new_ring = pl.pallas_call(
        _combine_kernel,
        grid=_grid(g.shape[0]),
        in_specs=[kvec, kvec, _VEC, _ring_block(K)],
        out_specs=[_VEC, _ring_block(K)],
        out_shape=[jax.ShapeDtypeStruct(g.shape, jnp.float32), _shape_of(ring)],
        input_output_aliases={3: 1},
        interpret=interpret,
    )(push, w_slot, g, ring)
    return g_eff, new_ring


def fused_combine_flat(g, ring, step, taus, weights, *, use_pallas=None, interpret=False):
    """Production dispatch for the push + combine half-tick on a flat ring.

    Returns ``(g_eff, live, new_ring)``.  The non-Pallas path runs the exact
    unfused ring ops (``delayed_combine`` on the bare-array ring), keeping the
    CPU/GPU bit-parity contract.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        push, w_slot, live = _slot_weights(ring.shape[0], step, taus, weights)
        g_eff, new_ring = fused_combine_call(g, ring, push, w_slot, interpret=interpret)
        return g_eff, live, new_ring
    from repro.async_engine.delayed import DelayedGradients, delayed_combine

    g_eff, live, new_state = delayed_combine(
        DelayedGradients(ring=ring, step=step), g, taus, weights
    )
    return g_eff, live, new_state.ring


def fused_tick_flat(
    kind: str,
    p: jnp.ndarray,
    g: jnp.ndarray,
    bufs,
    scalars,
    ring: jnp.ndarray,
    step,
    taus,
    weights,
    *,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """Production dispatch for one whole async tick on flat 1-D buffers.

    ``use_pallas=None`` auto-selects the one-launch Pallas tick on TPU and
    the exact-composition oracle (:func:`~repro.kernels.adaptive_update.ref
    .fused_tick_ref` — unfused ring ops + chain ref, bit-identical f32)
    elsewhere.  ``bufs``/returns mirror :func:`fused_chain_flat`, plus the
    new ring and the per-worker ``live`` mask:
    ``(p_new, new_bufs, new_ring, live)``.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        push, w_slot, live = _slot_weights(ring.shape[0], step, taus, weights)
        if kind == "adam":
            p_new, (m_new, v_new), new_ring = fused_tick_call(
                kind, p, g, (bufs["m"], bufs["v"]), scalars, ring, push, w_slot,
                interpret=interpret,
            )
            return p_new, {"m": m_new, "v": v_new}, new_ring, live
        kernel_bufs = () if kind == "sgd" else (bufs,)
        p_new, new_bufs, new_ring = fused_tick_call(
            kind, p, g, kernel_bufs, scalars, ring, push, w_slot, interpret=interpret
        )
        return p_new, (bufs if kind == "sgd" else new_bufs[0]), new_ring, live
    return fused_tick_ref(kind, p, g, bufs, scalars, ring, step, taus, weights)
