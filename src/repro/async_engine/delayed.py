"""SPMD async-as-delay: the paper's technique on the production mesh.

True lock-free asynchrony does not exist inside one XLA program (lock-step
collectives).  What the paper's math actually depends on is only the
*staleness distribution* of applied gradients (Lemma 1 onward) — so on the
mesh we realize asynchrony as **delayed gradient application**: a ring buffer
holds the last ``K`` gradient pytrees (sharded like the parameters; f32 for
all-f32 trees, bf16-compressed otherwise — see :func:`ring_dtype_for`);
each step pushes the fresh gradient and applies one delayed by ``tau``
sampled from the fitted CMP/Poisson staleness model.  The update is then

    x <- x - alpha(tau) * g_{t - tau}

with ``alpha(tau)`` from :mod:`repro.core.step_size` — eq. (4) with the
MindTheStep adaptive step.  This preserves every equation of the paper while
riding the pjit/shard_map distribution (see DESIGN.md §3 hardware-adaptation).

All state lives in one pytree so it pjit-shards with the optimizer state.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "DelayedGradients",
    "WorkerRing",
    "init_delayed",
    "init_flat_delayed",
    "init_worker_ring",
    "init_flat_worker_ring",
    "flat_size",
    "ring_dtype_for",
    "sample_tau",
    "delayed_apply",
    "delayed_apply_batch",
    "delayed_combine",
    "worker_ring_combine",
    "staleness_cdf",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DelayedGradients:
    """Ring buffer of in-flight gradients.

    ring: pytree of (K, ...) arrays — slot ``t % K`` holds gradient of step t.
    step: int32 scalar — number of gradients pushed so far.
    """

    ring: Any
    step: jnp.ndarray


def ring_dtype_for(params: Any, dtype=None):
    """Resolve the ring storage dtype: an explicit ``dtype`` wins; otherwise
    all-f32 trees get f32 rings (slot pushes and pops are then pure copies —
    the bf16 compression forced a software cast per element in the CPU combine
    hot loop) and mixed/low-precision trees keep the bf16 compression."""
    if dtype is not None:
        return dtype
    leaves = jax.tree.leaves(params)
    if leaves and all(l.dtype == jnp.float32 for l in leaves):
        return jnp.float32
    return jnp.bfloat16


def init_delayed(params: Any, K: int, dtype=None) -> DelayedGradients:
    dtype = ring_dtype_for(params, dtype)
    ring = jax.tree.map(lambda p: jnp.zeros((K,) + p.shape, dtype), params)
    return DelayedGradients(ring=ring, step=jnp.zeros((), jnp.int32))


def flat_size(params: Any) -> int:
    """Total element count of a pytree — the ``N`` of its packed flat buffer."""
    return sum(int(np.prod(p.shape)) if p.shape else 1 for p in jax.tree.leaves(params))


def init_flat_delayed(params: Any, K: int, dtype=None) -> DelayedGradients:
    """Flat-RESIDENT ring: ONE ``(K, N)`` buffer for the whole gradient pytree.

    The fused execution path (``make_step(..., fuse=True)``) keeps gradients
    packed: the per-step push/pop/combine runs over this single buffer — one
    dynamic-slice and one contraction per step instead of one per leaf — and
    the pack happens exactly once per step (the fresh gradient), never when
    refreshing ring slots.  Every ring op (``delayed_combine`` etc.) is pytree-
    polymorphic, so the flat ring is just the single-leaf special case of the
    same code path — which is what makes the fused/unfused bit-parity hold:
    identical pushes, gathers and contractions, merely de-fragmented.
    """
    dtype = ring_dtype_for(params, dtype)
    ring = jnp.zeros((K, flat_size(params)), dtype)
    return DelayedGradients(ring=ring, step=jnp.zeros((), jnp.int32))


def staleness_cdf(pmf: np.ndarray) -> jnp.ndarray:
    """Precompute the inverse-CDF sampling table for in-jit tau draws."""
    p = np.asarray(pmf, dtype=np.float64)
    p = p / p.sum()
    return jnp.asarray(np.cumsum(p), jnp.float32)


def sample_tau(key: jax.Array, cdf: jnp.ndarray) -> jnp.ndarray:
    """Draw tau ~ the fitted staleness model via inverse CDF (int32 scalar)."""
    u = jax.random.uniform(key, ())
    return jnp.searchsorted(cdf, u).astype(jnp.int32)


def delayed_apply(
    state: DelayedGradients,
    new_grad: Any,
    tau: jnp.ndarray,
) -> tuple[Any, jnp.ndarray, DelayedGradients]:
    """Push ``new_grad``; pop the gradient from ``tau`` steps ago.

    Returns ``(delayed_grad, live, new_state)`` where ``live`` is 0.0 while
    the requested slot predates the run (warmup) or exceeds the ring capacity
    — the caller multiplies the step size by ``live`` (the paper's drop rule
    for tau > tau_drop maps to tau >= K here).
    """
    K = jax.tree.leaves(state.ring)[0].shape[0]
    t = state.step
    ring = _push(state, new_grad)
    src_step = t - tau
    src_slot = jnp.mod(src_step, K)
    live = ((src_step >= 0) & (tau < K)).astype(jnp.float32)
    delayed = jax.tree.map(
        lambda r: jax.lax.dynamic_index_in_dim(r, src_slot, axis=0, keepdims=False), ring
    )
    return delayed, live, DelayedGradients(ring=ring, step=t + 1)


def _push(state: DelayedGradients, new_grad: Any) -> Any:
    K = jax.tree.leaves(state.ring)[0].shape[0]
    slot = jnp.mod(state.step, K)
    return jax.tree.map(
        lambda r, g: jax.lax.dynamic_update_index_in_dim(
            r, g.astype(r.dtype), slot, axis=0
        ),
        state.ring,
        new_grad,
    )


def delayed_apply_batch(
    state: DelayedGradients,
    new_grad: Any,
    taus: jnp.ndarray,  # (W,) int32
) -> tuple[Any, jnp.ndarray, DelayedGradients]:
    """Push ``new_grad``; pop the ``W`` gradients from ``taus`` steps ago.

    The vectorized counterpart of :func:`delayed_apply`: one server tick of an
    ``W``-worker simulation, where worker ``w`` delivers the gradient computed
    ``taus[w]`` steps ago.  Returns ``(delayed, live, new_state)`` with every
    leaf of ``delayed`` carrying a leading ``(W,)`` axis (a gather over ring
    slots) and ``live`` the (W,) per-worker drop mask of the scalar version.
    """
    K = jax.tree.leaves(state.ring)[0].shape[0]
    t = state.step
    ring = _push(state, new_grad)
    src_step = t - taus
    src_slot = jnp.mod(src_step, K)
    live = ((src_step >= 0) & (taus < K)).astype(jnp.float32)
    delayed = jax.tree.map(lambda r: jnp.take(r, src_slot, axis=0), ring)
    return delayed, live, DelayedGradients(ring=ring, step=t + 1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class WorkerRing:
    """Per-worker delayed-gradient rings for the sharded async engine.

    ring: pytree of (W, K, ...) arrays — worker ``w``'s slot ``t % K`` holds
          the gradient of step ``t``.  The leading worker axis is sharded over
          the ``workers`` mesh axis (see :func:`repro.sharding.specs
          .worker_specs`); under ``shard_map`` each device owns a (W_local, K,
          ...) block and the weighted combine is psum-merged across shards.
    step: int32 scalar, replicated — one push per server tick.
    """

    ring: Any
    step: jnp.ndarray


def init_worker_ring(params: Any, K: int, W: int, dtype=None) -> WorkerRing:
    dtype = ring_dtype_for(params, dtype)
    ring = jax.tree.map(lambda p: jnp.zeros((W, K) + p.shape, dtype), params)
    return WorkerRing(ring=ring, step=jnp.zeros((), jnp.int32))


def init_flat_worker_ring(params: Any, K: int, W: int, dtype=None) -> WorkerRing:
    """Per-worker rings as ONE ``(W, K, N)`` buffer (see :func:`init_flat_delayed`).

    The leading worker axis shards over the ``workers`` mesh axis exactly like
    the pytree form (``worker_specs`` keys on axis 0 regardless of leaf
    count); ``worker_ring_combine`` treats the bare array as a single-leaf
    pytree, so the sharded fused step reuses the proven combine unchanged.
    """
    dtype = ring_dtype_for(params, dtype)
    ring = jnp.zeros((W, K, flat_size(params)), dtype)
    return WorkerRing(ring=ring, step=jnp.zeros((), jnp.int32))


def worker_ring_combine(
    ring: Any,  # pytree of LOCAL (Wl, K, ...) blocks
    step: jnp.ndarray,
    new_grad: Any,
    taus: jnp.ndarray,  # (Wl,) int32
    weights: jnp.ndarray,  # (Wl,)
    *,
    axis_name: str | None = None,
) -> tuple[Any, jnp.ndarray, Any]:
    """One server tick over a local block of worker rings (shard_map body).

    Pushes ``new_grad`` into every local worker's ring, pops worker ``w``'s
    gradient from ``taus[w]`` steps ago, and returns the weighted partial sum

        g_partial = sum_w weights[w] * live[w] * g_{t - taus[w]}

    psum-reduced over ``axis_name`` when given (the cross-shard merge of the
    sharded engine), so every shard leaves with the same global ``g_eff``.
    Each worker ring receives identical pushes — under async-as-delay every
    worker observes the same gradient stream, so the W_local-fold storage is
    redundant TODAY; it is kept because (a) the worker axis is what lets the
    rings diverge later (per-worker gradient noise, partial-failure replay)
    without touching this contraction, and (b) it buys a shard-local gather
    with no cross-worker communication until the single psum.  On a 1-device
    mesh this reproduces :func:`delayed_combine` bit-exactly (same popped
    values, same :func:`_weighted_sum`).
    """
    K = jax.tree.leaves(ring)[0].shape[1]
    Wl = taus.shape[0]
    slot = jnp.mod(step, K)
    ring = jax.tree.map(
        lambda r, g: jax.lax.dynamic_update_index_in_dim(
            r, jnp.broadcast_to(g.astype(r.dtype), (Wl,) + g.shape), slot, axis=1
        ),
        ring,
        new_grad,
    )
    src_step = step - taus
    src_slot = jnp.mod(src_step, K)
    live = ((src_step >= 0) & (taus < K)).astype(jnp.float32)
    w = (jnp.asarray(weights, jnp.float32) * live).astype(jnp.float32)

    def combine_leaf(r):
        # per-worker pop: row i = r[i, src_slot[i]], one dynamic slice per
        # local worker, fused into the weighted sum (no gathered copy is held)
        zeros = (0,) * (r.ndim - 2)
        rows = [
            jax.lax.dynamic_slice(r, (i, src_slot[i]) + zeros, (1, 1) + r.shape[2:])[0, 0]
            for i in range(Wl)
        ]
        partial = _weighted_sum(w, rows)
        return jax.lax.psum(partial, axis_name) if axis_name is not None else partial

    combined = jax.tree.map(combine_leaf, ring)
    return combined, live, ring


def _weighted_sum(w, rows):
    """``sum_i w[i] * rows[i]`` in f32, accumulated in worker order.  XLA
    fuses it into one pass over the rows, where a contraction would first
    hold an f32 copy of every row (and on a TPU, at default precision, round
    both operands to bf16)."""
    acc = w[0] * rows[0].astype(jnp.float32)
    for i in range(1, len(rows)):
        acc = acc + w[i] * rows[i].astype(jnp.float32)
    return acc


def delayed_combine(
    state: DelayedGradients,
    new_grad: Any,
    taus: jnp.ndarray,  # (W,)
    weights: jnp.ndarray,  # (W,) — e.g. alpha(tau_w) / (alpha_c * W)
) -> tuple[Any, jnp.ndarray, DelayedGradients]:
    """Push + batched pop + weighted combine in one pass.

    Returns the single f32 gradient pytree

        g = sum_w weights[w] * live[w] * g_{t - taus[w]}

    so the caller never materializes the ``(W, ...)`` gather — the weighted
    sum happens leaf-wise (:func:`_weighted_sum`) over the gathered rows.  ``live``
    zeroes warmup / beyond-ring workers (the paper's drop rule).
    """
    delayed, live, new_state = delayed_apply_batch(state, new_grad, taus)
    w = (jnp.asarray(weights, jnp.float32) * live).astype(jnp.float32)
    combined = jax.tree.map(lambda d: _weighted_sum(w, d), delayed)
    return combined, live, new_state
