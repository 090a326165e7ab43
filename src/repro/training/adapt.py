"""Jit-resident adaptation state for online MindTheStep (paper §IV).

The paper's online adaptation is a feedback loop: observe tau -> refit the
CMP/Poisson staleness model -> rebuild ``alpha(tau)`` -> keep training.  For
that loop to survive ``jax.jit`` the adaptation artifacts must be step
*inputs*, not closure constants — otherwise ``refresh()`` rebuilds a table the
compiled step never sees (the closure-baking bug this module removes).

:class:`AdaptState` is a pytree threaded through ``TrainState``:

* ``alpha_table`` — f32 ``alpha(tau)`` lookup, gathered in-jit per worker;
* ``tau_cdf``     — inverse-CDF table of the fitted staleness model, sampled
  in-jit (a *vector* of ``W`` taus per step, one per simulated worker);
* ``hist``        — int32 staleness histogram, scatter-added in-jit.

The host syncs only at ``refresh_every`` boundaries: :func:`host_refresh`
pulls the histogram (the ONLY device->host transfer of the adaptation loop),
feeds it to the :class:`~repro.core.estimator.OnlineStalenessEstimator`,
refits, and returns a new ``AdaptState`` with identical shapes — so the next
call of the already-compiled step applies the fresh tables without retracing.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.async_engine.delayed import staleness_cdf

__all__ = [
    "AdaptState",
    "WorkerAdaptState",
    "init_adapt",
    "make_adapt",
    "make_worker_adapt",
    "worker_sampler_tables",
    "default_adapt_setup",
    "sample_taus",
    "sample_worker_taus",
    "alpha_lookup",
    "record_taus",
    "record_worker_taus",
    "merge_worker_hist",
    "host_refresh",
    "worker_host_refresh",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AdaptState:
    """Adaptation tables + telemetry, resident in the jitted step.

    All three arrays keep fixed shapes across refreshes (``alpha_table`` and
    ``hist`` share support ``[0, tau_max]``) — a refresh is a pure data swap.
    """

    alpha_table: jnp.ndarray  # (tau_max + 1,) f32 — alpha(tau)
    tau_cdf: jnp.ndarray  # (S,) f32 — inverse-CDF sampling table
    hist: jnp.ndarray  # (tau_max + 1,) i32 — observed-tau histogram

    @property
    def tau_max(self) -> int:
        return self.alpha_table.shape[0] - 1


def init_adapt(alpha_table, tau_cdf) -> AdaptState:
    """Build an AdaptState from raw tables (histogram starts empty)."""
    at = jnp.asarray(alpha_table, jnp.float32)
    return AdaptState(
        alpha_table=at,
        tau_cdf=jnp.asarray(tau_cdf, jnp.float32),
        hist=jnp.zeros(at.shape, jnp.int32),
    )


def make_adapt(schedule, model, *, cdf_support: int, tau_max: int | None = None) -> AdaptState:
    """AdaptState from a :class:`StepSizeSchedule` + fitted staleness model.

    ``cdf_support`` bounds the sampled taus to ``[0, cdf_support)`` — set it to
    the delayed-ring depth so sampled delays are (mostly) servable.
    """
    table = np.asarray(schedule.table, np.float64)
    if tau_max is not None:
        assert len(table) >= tau_max + 1, "schedule table shorter than tau_max"
        table = table[: tau_max + 1]
    return init_adapt(table, staleness_cdf(model.pmf_table(cdf_support - 1)))


def default_adapt_setup(alpha_c: float, workers: int, ring: int, *, tau_max: int | None = None):
    """The production async recipe, shared by the launcher and the dry-run
    specs so they always lower/train the same step: Poisson(workers) staleness
    model, eq.-17 schedule with K = alpha_c (implicit-momentum magnitude in
    step-size units) normalized per eq. 26 against the ring-truncated pmf the
    sampler actually draws from, and an AdaptState whose CDF covers the ring.

    Returns ``(schedule, model, adapt)``.
    """
    from repro.core.staleness import Poisson
    from repro.core.step_size import make_schedule

    tau_max = ring * 4 if tau_max is None else tau_max
    model = Poisson(float(workers))
    # The raw eq.-17 core is ~1e-8 at tau ~ lambda; without the normalization
    # the initial phase would train at effectively zero step size.
    pmf = model.pmf_table(ring - 1)
    sched = make_schedule(
        "poisson_momentum", alpha_c, model, K=alpha_c,
        tau_max=tau_max, normalize_pmf=pmf / np.sum(pmf),
    )
    return sched, model, make_adapt(sched, model, cdf_support=ring, tau_max=tau_max)


# ---------------------------------------------------------------------------
# Sharded-engine state: per-worker samplers + histograms over a workers axis
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class WorkerAdaptState:
    """Adaptation state with a leading worker axis (sharded async engine).

    The *policy* (``alpha_table``) stays global/replicated — the paper's
    ``alpha(tau)`` is a property of the server, not of any worker.  The
    *environment* is per-worker and heterogeneous: worker ``w`` draws its
    staleness either from its own inverse-CDF row ``tau_cdf[w]`` (geometric /
    Poisson / CMP fits) or by replaying its own recorded trace row
    ``tau_trace[w]`` (event-simulator or production traces), selected by
    ``use_trace[w]``.  ``hist`` keeps one histogram row per worker,
    scatter-added in-jit and psum-merged only at ``host_refresh`` boundaries.

    All worker-axis leaves shard over the ``workers`` mesh axis; shapes are
    refresh-invariant exactly like :class:`AdaptState`.
    """

    alpha_table: jnp.ndarray  # (tau_max + 1,) f32, replicated
    tau_cdf: jnp.ndarray  # (W, S) f32 — per-worker inverse-CDF rows
    tau_trace: jnp.ndarray  # (W, T) i32 — per-worker replay traces
    use_trace: jnp.ndarray  # (W,) i32 — 1 where the worker replays its trace
    hist: jnp.ndarray  # (W, tau_max + 1) i32 — per-worker histograms

    @property
    def tau_max(self) -> int:
        return self.alpha_table.shape[0] - 1

    @property
    def num_workers(self) -> int:
        return self.tau_cdf.shape[0]


def worker_sampler_tables(
    samplers: list, *, support: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stack heterogeneous per-worker tau samplers into fixed-shape tables.

    ``samplers[w]`` is either a :class:`~repro.core.staleness.StalenessModel`
    (sampled via its ring-truncated inverse CDF over ``[0, support)``) or a
    1-D integer array (a staleness *trace*, e.g. from
    :func:`repro.async_engine.events.simulate_staleness_trace`, replayed
    cyclically).  Returns ``(tau_cdf (W, S), tau_trace (W, T), use_trace (W,))``
    with traces tiled to the longest trace length (min 1).
    """
    from repro.core.staleness import StalenessModel

    T = 1
    for s in samplers:
        if not isinstance(s, StalenessModel):
            T = max(T, len(np.asarray(s)))
    cdfs, traces, flags = [], [], []
    for s in samplers:
        if isinstance(s, StalenessModel):
            cdfs.append(np.asarray(staleness_cdf(s.pmf_table(support - 1)), np.float32))
            traces.append(np.zeros(T, np.int32))
            flags.append(0)
        else:
            tr = np.asarray(s, np.int64).ravel()
            assert tr.size > 0, "empty staleness trace"
            reps = -(-T // tr.size)  # ceil division
            traces.append(np.tile(tr, reps)[:T].astype(np.int32))
            cdfs.append(np.ones(support, np.float32))  # degenerate (unused): tau = 0
            flags.append(1)
    return np.stack(cdfs), np.stack(traces), np.asarray(flags, np.int32)


def make_worker_adapt(alpha_table, samplers: list, *, cdf_support: int) -> WorkerAdaptState:
    """Build a :class:`WorkerAdaptState` from a table + per-worker samplers."""
    at = jnp.asarray(alpha_table, jnp.float32)
    cdf, trace, flags = worker_sampler_tables(samplers, support=cdf_support)
    W = len(samplers)
    return WorkerAdaptState(
        alpha_table=at,
        tau_cdf=jnp.asarray(cdf),
        tau_trace=jnp.asarray(trace),
        use_trace=jnp.asarray(flags),
        hist=jnp.zeros((W,) + at.shape, jnp.int32),
    )


# ---------------------------------------------------------------------------
# In-jit primitives
# ---------------------------------------------------------------------------

def sample_taus(key: jax.Array, cdf: jnp.ndarray, num: int) -> jnp.ndarray:
    """Draw ``num`` iid taus ~ fitted model via inverse CDF — (num,) int32.

    One draw per simulated worker: the vectorized counterpart of
    :func:`repro.async_engine.delayed.sample_tau`.
    """
    u = jax.random.uniform(key, (num,))
    return jnp.searchsorted(cdf, u).astype(jnp.int32)


def alpha_lookup(adapt: AdaptState, taus: jnp.ndarray) -> jnp.ndarray:
    """Gather ``alpha(tau)`` for a vector of (possibly traced) taus."""
    idx = jnp.clip(taus, 0, adapt.tau_max)
    return adapt.alpha_table[idx]


def record_taus(adapt: AdaptState, taus: jnp.ndarray) -> AdaptState:
    """Scatter-add observed taus into the in-jit histogram.

    Clips to the histogram support — the same clip the host-side estimator's
    ``observe()`` applies, so the two bookkeepers agree bin-for-bin.
    """
    idx = jnp.clip(taus, 0, adapt.tau_max)
    return AdaptState(
        alpha_table=adapt.alpha_table,
        tau_cdf=adapt.tau_cdf,
        hist=adapt.hist.at[idx].add(1),
    )


def sample_worker_taus(
    u: jnp.ndarray,  # (Wl,) uniforms, one per local worker
    tau_cdf: jnp.ndarray,  # (Wl, S)
    tau_trace: jnp.ndarray,  # (Wl, T)
    use_trace: jnp.ndarray,  # (Wl,)
    step: jnp.ndarray,
) -> jnp.ndarray:
    """Per-worker heterogeneous tau draw (shard_map body; (Wl,) int32).

    CDF workers invert their own row at ``u[w]``; trace workers replay
    ``tau_trace[w, step mod T]``.  With identical CDF rows this bit-matches
    :func:`sample_taus` on the same uniforms (same searchsorted, vmapped).
    """
    t_cdf = jax.vmap(jnp.searchsorted)(tau_cdf, u).astype(jnp.int32)
    T = tau_trace.shape[1]
    t_trace = jax.lax.dynamic_index_in_dim(
        tau_trace, jnp.mod(step, T), axis=1, keepdims=False
    ).astype(jnp.int32)
    return jnp.where(use_trace > 0, t_trace, t_cdf)


def record_worker_taus(hist: jnp.ndarray, taus: jnp.ndarray) -> jnp.ndarray:
    """Scatter-add each local worker's tau into its own histogram row."""
    Wl, bins = hist.shape
    idx = jnp.clip(taus, 0, bins - 1)
    return hist.at[jnp.arange(Wl), idx].add(1)


# ---------------------------------------------------------------------------
# Host-side refresh boundary
# ---------------------------------------------------------------------------

def host_refresh(
    adapt: AdaptState,
    mts: Any,
    *,
    strategy: str = "poisson_momentum",
    family: str = "poisson",
    K: float | None = None,
    normalize: bool = True,
    refresh_cdf: bool = False,
    logger: Any = print,
) -> AdaptState:
    """Drain the in-jit histogram, refit, and return same-shape fresh tables.

    ``K`` (eq. 16/17's implicit-momentum magnitude, in step-size units)
    defaults to ``mts.alpha_c``: that keeps ``c(tau)`` in ``[0, 1]`` so the
    rebuilt table has support on the observed taus.  ``K >> alpha_c`` zeroes
    every bin past the first few and the eq.-26 normalization fails — pass it
    explicitly only if that aggressive-drop policy is what you want.

    ``mts`` is a :class:`~repro.optim.mindthestep.MindTheStep` constructed
    with an estimator.  This is the only point where adaptation state crosses
    the device->host boundary; everything it returns re-enters the compiled
    step as ordinary inputs (no retrace — shapes are invariant).

    Only the *policy* (``alpha_table``) is rebuilt from the refit by default.
    The *sampler* (``tau_cdf``) models the simulated environment — worker/
    scheduler delay, which does not change because our estimate of it did —
    so it stays fixed.  Swapping it from the refit model would close a
    self-referential loop: taus sampled from a ring-truncated CDF bias the
    fit low, the biased fit produces an even lower CDF, and lambda drifts
    monotonically away from the true worker count.  ``refresh_cdf=True``
    opts into the swap for experiments that want the sampler to track the
    fit anyway.
    """
    assert mts.estimator is not None, "host_refresh needs a MindTheStep with an estimator"
    counts = np.asarray(jax.device_get(adapt.hist))
    new_cdf = adapt.tau_cdf
    if refresh_cdf:
        # fit() is a pure read (idempotent): build the sampler swap before
        # refresh() applies the once-per-boundary forgetting.  observe first
        # so the swap sees this boundary's histogram.
        mts.estimator.observe_counts(counts)
        counts = None  # consumed
        model = mts.estimator.fit(family)
        new_cdf = staleness_cdf(model.pmf_table(adapt.tau_cdf.shape[0] - 1))
    table = _refit_alpha_table(
        counts, mts, strategy=strategy, family=family, K=K,
        normalize=normalize, logger=logger, n_bins=adapt.alpha_table.shape[0],
    )
    return AdaptState(
        alpha_table=table,
        tau_cdf=new_cdf,
        hist=jnp.zeros_like(adapt.hist),
    )


def _refit_alpha_table(
    counts: np.ndarray | None,
    mts: Any,
    *,
    strategy: str,
    family: str,
    K: float | None,
    normalize: bool,
    logger: Any,
    n_bins: int,
) -> jnp.ndarray:
    """Shared refresh-boundary core: observe drained ``counts`` (unless the
    caller already fed them), refit/rebuild the schedule, return the new f32
    table truncated to ``n_bins``."""
    from repro.core.step_size import STRATEGIES

    assert mts.estimator is not None, "host_refresh needs a MindTheStep with an estimator"
    # Fail fast on misconfiguration: the fallback below must only absorb the
    # data-dependent eq.-26 normalization failure, never a typo'd strategy or
    # family that would otherwise log "kept previous schedule" forever.
    assert strategy in STRATEGIES, f"unknown strategy {strategy!r}; choose from {STRATEGIES}"
    assert family in ("poisson", "cmp", "geometric", "uniform"), f"unknown family {family!r}"
    if K is None:
        K = mts.alpha_c
    if counts is not None:
        mts.estimator.observe_counts(counts)
    try:
        mts.refresh(strategy, family=family, K=K, normalize=normalize)
    except ValueError as e:
        # The refit schedule can put zero step size on ALL observed taus
        # (aggressive K/alpha zeroing + the clip/drop protocol), making the
        # eq.-26 normalization impossible.  A refresh boundary must never
        # kill a long run: keep the current schedule and say so — via the
        # loop logger, not warnings.warn, whose dedup would silence every
        # occurrence after the first.
        if logger is not None:
            logger(
                f"host_refresh: kept previous schedule "
                f"(n_seen={mts.estimator.n_seen}): {e}"
            )
    table = np.asarray(mts.schedule.table, np.float64)
    assert len(table) >= n_bins, (
        f"refreshed schedule support {len(table) - 1} < adapt tau_max {n_bins - 1}; "
        "construct the estimator with tau_max >= adapt.tau_max"
    )
    return jnp.asarray(table[:n_bins], jnp.float32)


def merge_worker_hist(adapt: WorkerAdaptState, mesh=None, axis_name: str = "workers"):
    """Global staleness histogram: psum-merge the per-worker rows.

    With a ``workers`` mesh this runs as a tiny compiled collective — each
    shard sums its local (W_local, bins) block, then one ``lax.psum`` merges
    across shards and leaves the (bins,) result replicated (what the
    ``host_refresh`` boundary pulls).  Without a mesh it is a plain sum.
    """
    if mesh is None or "workers" not in getattr(mesh, "axis_names", ()):
        return jnp.sum(adapt.hist, axis=0)
    from jax.sharding import PartitionSpec as P

    merged = jax.shard_map(
        lambda h: jax.lax.psum(jnp.sum(h, axis=0), axis_name),
        mesh=mesh,
        in_specs=P(axis_name, None),
        out_specs=P(None),
        check_vma=False,
    )(adapt.hist)
    return merged


def worker_host_refresh(
    adapt: WorkerAdaptState,
    mts: Any,
    *,
    mesh=None,
    strategy: str = "poisson_momentum",
    family: str = "poisson",
    K: float | None = None,
    normalize: bool = True,
    logger: Any = print,
) -> WorkerAdaptState:
    """Refresh boundary of the sharded engine.

    psum-merges the per-worker histograms into the global staleness histogram,
    drains it into the estimator, refits the policy table, and returns a
    same-shape :class:`WorkerAdaptState`.  The per-worker samplers (CDF rows,
    traces) model the ENVIRONMENT and stay fixed, mirroring
    :func:`host_refresh`'s fixed-sampler default.  The new table and the
    reset histogram keep the placement of the leaves they replace, so the
    compiled sharded step sees the same input types and does not retrace.
    """
    counts = np.asarray(jax.device_get(merge_worker_hist(adapt, mesh)))
    table = _refit_alpha_table(
        counts, mts, strategy=strategy, family=family, K=K,
        normalize=normalize, logger=logger, n_bins=adapt.alpha_table.shape[0],
    )
    return WorkerAdaptState(
        alpha_table=jax.device_put(table, adapt.alpha_table.sharding),
        tau_cdf=adapt.tau_cdf,
        tau_trace=adapt.tau_trace,
        use_trace=adapt.use_trace,
        hist=jax.device_put(jnp.zeros(adapt.hist.shape, adapt.hist.dtype), adapt.hist.sharding),
    )
