"""Pure-jnp oracles for the fused adaptive update and the fused-chain family.

``fused_chain_ref`` doubles as the production CPU/GPU lowering of the fusion
compiler (:mod:`repro.optim.fuse`): its op ORDER replicates the link-by-link
pipeline exactly (scalar factors applied sequentially in link order, f32
accumulation, one final cast), so the fused path is bit-identical to the
unfused chain in f32 — the correctness contract the parity suite enforces.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "adaptive_update_ref",
    "adaptive_update_tree_ref",
    "fused_chain_ref",
    "fused_tick_ref",
]


def adaptive_update_ref(p, g, v, alpha, mu):
    """v' = mu v - alpha g;  p' = p + v'  (elementwise, f32 accumulate)."""
    v_new = mu * v.astype(jnp.float32) - alpha * g.astype(jnp.float32)
    p_new = p.astype(jnp.float32) + v_new
    return p_new.astype(p.dtype), v_new.astype(v.dtype)


def fused_chain_ref(kind: str, p, g, bufs, s):
    """One-pass reference for a whole fused chain step on flat f32 buffers.

    ``s`` is the scalar bundle of :mod:`repro.optim.fuse` (every entry a
    traced f32 scalar): the prefix factors ``f_stale`` / ``f_keep`` /
    ``f_clip`` (1.0 when the link is absent — multiplication by 1.0 is
    bitwise exact) followed by the optimizer-family constants.  Each factor
    is applied SEQUENTIALLY, never pre-combined, because float multiplication
    is not associative and the contract is bit-equality with the link-by-link
    pipeline.  ``bufs`` is the family's flat state: ``()`` for sgd, the
    velocity buffer for momentum, ``{"m", "v"}`` for adam (the step counter
    stays outside — the bias corrections arrive pre-computed as ``c1``/``c2``).
    """
    u = g.astype(jnp.float32)
    u = s["f_stale"] * u  # scale_by_staleness: factor * l
    u = u * s["f_keep"]  # drop_stale: l * keep
    u = u * s["f_clip"]  # clip_by_global_norm: l * factor
    if kind == "sgd":
        u = s["m_scale"] * u  # scale(-lr): m * l
        return (p.astype(jnp.float32) + u).astype(p.dtype), bufs
    if kind == "momentum":
        u = s["m_scale"] * u
        v = s["mu"] * bufs + u  # trace(mu): mu * v + u
        return (p.astype(jnp.float32) + v).astype(p.dtype), v
    if kind == "adam":
        m = s["b1"] * bufs["m"] + s["omb1"] * u
        v = s["b2"] * bufs["v"] + s["omb2"] * jnp.square(u)
        out = (m * s["c1"]) / (jnp.sqrt(v * s["c2"]) + s["eps"])
        u2 = s["m_scale"] * out
        return (p.astype(jnp.float32) + u2).astype(p.dtype), {"m": m, "v": v}
    raise ValueError(f"unknown fused-chain kind {kind!r}")


def fused_tick_ref(kind: str, p, g, bufs, s, ring, step, taus, weights):
    """One whole async server tick on flat buffers: the tick-kernel oracle.

    Composes the proven ring ops (:func:`repro.async_engine.delayed
    .delayed_combine` on the bare ``(K, N)`` ring — a single-leaf pytree) with
    :func:`fused_chain_ref`, so the tick is bit-identical to the unfused
    push + gather + weighted sum + link-by-link pipeline.  This IS the production
    CPU/GPU lowering of ``flat_tick_step``; the Pallas tick kernel folds the
    per-worker weights onto ring slots instead (different float association
    when workers share a slot) and is tolerance-tested against this.

    Returns ``(p_new, new_bufs, new_ring, live)``.
    """
    from repro.async_engine.delayed import DelayedGradients, delayed_combine

    g_eff, live, new_state = delayed_combine(
        DelayedGradients(ring=ring, step=step), g, taus, weights
    )
    p_new, new_bufs = fused_chain_ref(kind, p, g_eff, bufs, s)
    return p_new, new_bufs, new_state.ring, live


def adaptive_update_tree_ref(params, grads, vel, alpha, mu):
    flat = jax.tree.map(
        lambda p, g, v: adaptive_update_ref(p, g, v, alpha, mu), params, grads, vel,
        is_leaf=lambda x: isinstance(x, jnp.ndarray),
    )
    new_p = jax.tree.map(lambda t: t[0], flat, is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda t: t[1], flat, is_leaf=lambda x: isinstance(x, tuple))
    return new_p, new_v
