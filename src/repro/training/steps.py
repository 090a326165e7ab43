"""Train / serve step factories — the jit boundaries of the framework.

One builder, :func:`make_step`, produces the training step for every engine
from a single gradient-transform pipeline (:mod:`repro.optim.transform`):

* ``mode="sync"``          — synchronous data-parallel step (the SyncPSGD
  baseline of paper §III; on the mesh, the batch axis IS the worker axis and
  Theorem 1's effective batch is explicit).
* ``mode="async"``         — MindTheStep-AsyncPSGD on the mesh: per step a
  *vector* of ``W`` worker staleness values is sampled in-jit from the CDF
  table in ``state.adapt``, the matching ``W`` delayed gradients are popped
  from the ring and applied as an ``alpha(tau)``-weighted average (paper
  eq. 4 + Algorithm 1, async-as-delay adaptation, m-worker simulation).
  All adaptation artifacts — alpha table, tau CDF, staleness histogram — ride
  in :class:`~repro.training.adapt.AdaptState` as step INPUTS, so a host-side
  ``refresh()`` swaps them without retracing the compiled step.
* ``mode="sharded_async"`` — the same W-worker simulation under ``shard_map``
  over a ``workers`` mesh axis: per-worker rings, heterogeneous tau samplers,
  per-worker histograms, one ``lax.psum`` merge.

The async modes derive the per-worker weighting from the pipeline itself: a
``scale_by_staleness`` link is absorbed into the delayed-ring combine weights
(``alpha(tau_w) / (alpha_c W)``, gathered from the jit-resident table) and a
``drop_stale`` link into the per-worker drop mask; the pipeline then runs on
the combined ``g_eff`` with ``ctx.staleness_applied = True``.  The legacy
factories (``make_train_step`` / ``make_async_train_step`` /
``make_sharded_async_train_step``) are kept as one-line shims and accept both
pipelines and legacy :class:`~repro.optim.base.Optimizer` shims —
trajectories are bit-identical either way.

``fuse=True`` switches every mode to the FUSED execution model
(:mod:`repro.optim.fuse`): the whole pipeline lowers to one Pallas
flat-buffer kernel per step, the delayed rings live flat-resident (one
``(K, N)`` / ``(W, K, N)`` buffer instead of one ring per leaf), all-f32
params go flat-NATIVE (the param buffer is the packed ``(N,)`` view;
gradients come out of autodiff already packed, so the per-step pack →
combine → unpack round-trip disappears) and the whole async tick is one
``flat_tick_step`` launch.  The trajectory stays bit-identical (f32) to the
link-by-link execution.  Unfuseable chains fall back with a single warning.

``make_serve_step`` — one decode step against a KV cache (inference shapes
``decode_32k`` / ``long_500k``).

Each factory returns a pure function suitable for ``jax.jit`` with explicit
in/out shardings supplied by the launcher.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.async_engine.delayed import (
    DelayedGradients,
    WorkerRing,
    delayed_combine,
    init_delayed,
    init_flat_delayed,
    init_flat_worker_ring,
    init_worker_ring,
    worker_ring_combine,
)
from repro.models import model as M
from repro.optim import transform as T
from repro.optim.base import Optimizer
from repro.training.adapt import (
    AdaptState,
    WorkerAdaptState,
    alpha_lookup,
    record_taus,
    record_worker_taus,
    sample_taus,
    sample_worker_taus,
)

__all__ = [
    "TrainState",
    "init_params",
    "param_view",
    "init_train_state",
    "init_sharded_async_state",
    "make_step",
    "make_train_step",
    "make_async_train_step",
    "make_sharded_async_train_step",
    "make_serve_step",
]

MODES = ("sync", "async", "sharded_async")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray
    rng: jax.Array
    delayed: DelayedGradients | None = None
    adapt: AdaptState | None = None


def init_params(key: jax.Array, cfg) -> Any:
    """The params :func:`init_train_state` would initialize from ``key``.

    THE single source of the key-split discipline (params from the first
    sub-key, rng from the second): callers that need the params up front
    (e.g. to report the model size before building the state) use this and
    pass the result back via ``params=`` — bit-identical to letting
    ``init_train_state`` init them itself.
    """
    kp, _ = jax.random.split(key)
    return M.init_model(kp, cfg)


def param_view(params, cfg) -> Any:
    """Pytree view of params that may be flat-native (one packed ``(N,)``).

    The model-boundary unpack of fused flat-native training: eval hooks,
    launchers and tests use this to look at params leaf-wise regardless of
    the execution layout.  Accepts a :class:`TrainState` or params directly;
    pytree params pass through untouched.
    """
    params = getattr(params, "params", params)
    if isinstance(params, jax.Array) and params.ndim == 1:
        template = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
        return T.flat_view(params, template)
    return params


def init_train_state(
    key: jax.Array,
    cfg,
    opt,
    *,
    async_ring: int = 0,
    adapt: AdaptState | None = None,
    params: Any | None = None,
    fuse: bool = False,
    ring_dtype: Any = None,
) -> TrainState:
    """``opt`` is either a legacy :class:`Optimizer` or a pipeline
    (:class:`~repro.optim.transform.GradientTransform`) — both expose
    ``init(params) -> opt_state``.

    ``fuse=True`` initializes the FUSED execution layout for a fuseable
    pipeline (pair it with ``make_step(..., fuse=True)``): flat-resident
    optimizer state and a flat ``(K, N)`` delayed ring.  All-f32 params
    additionally go flat-NATIVE — ``TrainState.params`` becomes the packed
    ``(N,)`` buffer itself (view it leaf-wise with :func:`param_view`), so
    the per-step pack → combine → unpack round-trip disappears.  An
    unfuseable pipeline falls back to the standard layout silently —
    ``make_step`` owns the (single) fallback warning.

    ``ring_dtype`` overrides the delayed-ring storage dtype (default: the
    params dtype for all-f32 trees, bf16 otherwise — see
    :func:`repro.async_engine.delayed.ring_dtype_for`).
    """
    _, kr = jax.random.split(key)
    if params is None:
        params = init_params(key, cfg)
    if cfg.param_dtype != "float32":
        # low-precision parameter storage (halves weight HBM traffic; the
        # optimizer update still accumulates in f32 before the cast back)
        from repro.models.layers import dtype_of

        pd = dtype_of(cfg.param_dtype)
        params = jax.tree.map(
            lambda p: p.astype(pd) if p.dtype == jnp.float32 else p, params
        )
    fused = _fused_form(opt) if fuse else None
    if fused is not None and all(
        l.dtype == jnp.float32 for l in jax.tree.leaves(params)
    ):
        # flat-NATIVE: the param buffer IS the packed view; the fused state
        # keeps no second copy ("p": None) so donation never aliases
        params = T.pack_flat(params)
    init_ring = init_flat_delayed if fused is not None else init_delayed
    return TrainState(
        params=params,
        opt_state=(fused or opt).init(params),
        step=jnp.zeros((), jnp.int32),
        rng=kr,
        delayed=init_ring(params, async_ring, dtype=ring_dtype) if async_ring else None,
        adapt=adapt,
    )


def _fused_form(pipeline):
    """The one-kernel lowering of ``pipeline`` (None when not fuseable).

    Accepts anything ``make_step`` accepts: a chain, or a legacy shim whose
    ``.pipeline`` carries the chain.
    """
    from repro.optim.fuse import fuse_pipeline

    transform = (
        pipeline
        if isinstance(pipeline, T.GradientTransform)
        else getattr(pipeline, "pipeline", None)
    )
    return fuse_pipeline(transform) if transform is not None else None


def _constrain_grads(grads, cfg):
    """FSDP-style: pin each weight gradient to its parameter's sharding so
    XLA reduce-scatters partial grads instead of all-reducing them replicated
    (cfg.shard_grads; no-op without an active mesh)."""
    if not cfg.shard_grads:
        return grads
    from repro.sharding.ctx import current_rules
    from repro.sharding.specs import tree_shardings

    rules = current_rules()
    if rules is None:
        return grads
    shardings = tree_shardings(grads, rules.mesh)
    return jax.tree.map(jax.lax.with_sharding_constraint, grads, shardings)


def _resolve_pipeline(pipeline):
    """Normalize either API to ``(apply_fn, transform)``.

    ``apply_fn(grads, opt_state, params, ctx) -> (new_params, new_opt_state)``.
    Legacy :class:`Optimizer` / :class:`MindTheStep` shims apply internally
    (their shimmed pipelines make this bit-identical to the chain path);
    bare :class:`GradientTransform` pipelines run through
    :func:`repro.optim.transform.run_pipeline`.  ``transform`` is the
    introspectable pipeline (the shim's inner chain for legacy optimizers) —
    links are searched RECURSIVELY, so nested chains resolve the same way
    everywhere (same traversal as ``T.staleness_link``, which the
    ``train_loop`` refresh path uses).
    """
    if isinstance(pipeline, T.GradientTransform):
        def apply_fn(grads, opt_state, params, ctx):
            return T.run_pipeline(pipeline, grads, opt_state, params, ctx)

        return apply_fn, pipeline

    assert isinstance(pipeline, Optimizer) or hasattr(pipeline, "update"), (
        f"make_step needs a GradientTransform or Optimizer, got {type(pipeline)!r}"
    )

    def apply_fn(grads, opt_state, params, ctx):
        return pipeline.update(grads, opt_state, params)

    return apply_fn, getattr(pipeline, "pipeline", None)


def _resolve_alpha_c(alpha_c, transform) -> float:
    if alpha_c is not None:
        return float(alpha_c)
    link = T.staleness_link(transform) if transform is not None else None
    # reprolint: disable=RL001 — step-build time; alpha_c is a python float field
    return float(link.alpha_c) if link is not None else 1.0


def _drop_mask(transform, taus):
    """Per-worker keep mask from any ``drop_stale`` link (absorbed here)."""
    link = T.drop_link(transform) if transform is not None else None
    if link is None:
        return None
    return (taus <= link.tau_drop).astype(jnp.float32)


def _check_absorbable_order(transform, mode):
    """Mode-equivalence guard for the async engines.

    Absorbing ``scale_by_staleness``/``drop_stale`` into the combine weights
    moves them to the FRONT of the update — equivalent to the sync chain only
    when nothing precedes them but other absorbed links (the factors would
    otherwise have to commute through a stateful or norm-dependent stage,
    e.g. clip or the adam preconditioner).  Reject misordered chains instead
    of silently running a different update per mode.
    """
    if transform is None:
        return
    kinds = [link.kind for link in T.iter_links(transform)]
    non_absorbed = [i for i, k in enumerate(kinds) if k not in ("staleness", "drop", "identity")]
    misordered = non_absorbed and any(
        k in ("staleness", "drop") for k in kinds[non_absorbed[0]:]
    )
    assert not misordered, (
        f"mode={mode!r} absorbs scale_by_staleness/drop_stale into the "
        f"delayed-ring combine weights (the front of the update), but this "
        f"pipeline places one after a {kinds[non_absorbed[0]]!r} link "
        f"(chain order: {kinds}) — put the staleness/drop links first"
    )


def make_step(
    cfg,
    pipeline,
    *,
    mode: str = "sync",
    alpha_c: float | None = None,
    num_workers: int = 1,
    mesh=None,
    axis_name: str = "workers",
    fuse: bool = False,
) -> Callable:
    """One step builder for every engine: ``(TrainState, batch) -> (TrainState, metrics)``.

    ``pipeline`` is a :class:`~repro.optim.transform.GradientTransform`
    (usually from ``chain(...)``) or a legacy :class:`Optimizer` shim.
    ``alpha_c`` defaults to the pipeline's ``scale_by_staleness`` link (1.0
    if absent); ``num_workers`` is the simulated worker count of
    ``mode="async"`` (the sharded mode takes W from ``state.adapt``);
    ``mesh``/``axis_name`` wire the ``workers`` mesh axis of
    ``mode="sharded_async"``.

    ``fuse=True`` lowers the whole pipeline to the fused execution model
    (:mod:`repro.optim.fuse`): the delayed rings stay flat-resident (build
    the state with ``init_train_state(..., fuse=True)`` /
    ``init_sharded_async_state(..., fuse=True)``), all-f32 params go
    flat-NATIVE (packed ``(N,)`` buffer; gradients are born flat through the
    loss-boundary view), and the async tick runs as ONE
    :func:`~repro.optim.fuse.flat_tick_step` launch — ring push, weighted
    combine, scalars, body and apply in a single pass (two launches with
    clip, and in sharded mode where the combine runs under shard_map).  The
    step stays bit-identical (f32) to the link-by-link execution.  A chain
    the compiler cannot classify (e.g. a custom link) falls back to
    link-by-link execution with a single warning.
    """
    assert mode in MODES, f"mode must be one of {MODES}, got {mode!r}"
    apply_fn, transform = _resolve_pipeline(pipeline)
    fused_flat = False
    plan = None
    if fuse:
        fused = _fused_form(pipeline)
        if fused is None:
            warnings.warn(
                "make_step(fuse=True): pipeline is not fuseable (unrecognized "
                "link or ordering) — falling back to link-by-link execution",
                stacklevel=2,
            )
        else:
            apply_fn, transform = _resolve_pipeline(fused)
            fused_flat = True
            plan = fused.plan
    alpha_c = _resolve_alpha_c(alpha_c, transform)
    if mode != "sync":
        _check_absorbable_order(transform, mode)

    def loss_and_grads(params, batch):
        if isinstance(params, jax.Array) and params.ndim == 1:
            # flat-NATIVE params: the model sees the leaf-wise view only
            # inside the loss; the VJP of the view (slice+reshape) is the
            # pack, so the gradient comes out of autodiff already packed —
            # no per-step pack_flat, no per-step param unpack.  (Leaf-wise
            # grad sharding constraints don't apply to the packed buffer.)
            template = jax.eval_shape(
                lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
            )

            def lf_flat(pf):
                return M.loss_fn(T.flat_view(pf, template), batch, cfg)

            (loss, metrics), g_flat = jax.value_and_grad(lf_flat, has_aux=True)(params)
            return loss, metrics, g_flat

        def lf(p):
            return M.loss_fn(p, batch, cfg)

        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        return loss, metrics, _constrain_grads(grads, cfg)

    def _flat_grads(grads):
        """One grad pack max: born-flat gradients pass through untouched."""
        if isinstance(grads, jax.Array) and grads.ndim == 1:
            return grads
        return T.pack_flat(grads)

    def _check_ring_layout(ring):
        is_flat = isinstance(ring, jax.Array)
        assert is_flat == fused_flat, (
            f"delayed ring layout ({'flat' if is_flat else 'pytree'}) does not "
            f"match make_step(fuse={fuse}) — initialize the state with the "
            f"same fuse= flag (init_train_state / init_sharded_async_state)"
        )

    if mode == "sync":

        def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
            loss, metrics, grads = loss_and_grads(state.params, batch)
            ctx = T.StepContext(adapt=state.adapt, rng=state.rng)
            new_params, new_opt = apply_fn(grads, state.opt_state, state.params, ctx)
            new_state = TrainState(
                params=new_params, opt_state=new_opt, step=state.step + 1,
                rng=state.rng, delayed=state.delayed, adapt=state.adapt,
            )
            return new_state, {"loss": loss, **metrics}

        return train_step

    if mode == "async":
        W = int(num_workers)
        assert W >= 1

        def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
            assert state.adapt is not None, (
                "async step needs TrainState.adapt (see init_adapt)"
            )
            assert state.delayed is not None, (
                "async step needs a delayed ring (async_ring > 0)"
            )
            _check_ring_layout(state.delayed.ring)
            loss, metrics, grads = loss_and_grads(state.params, batch)
            rng, sub = jax.random.split(state.rng)
            taus = sample_taus(sub, state.adapt.tau_cdf, W)
            alpha = alpha_lookup(state.adapt, taus)
            weights = alpha / jnp.float32(alpha_c * W)
            keep = _drop_mask(transform, taus)
            if keep is not None:
                weights = weights * keep
            adapt = record_taus(state.adapt, taus)
            ctx = T.StepContext(
                taus=taus, adapt=adapt, rng=rng, staleness_applied=True
            )
            if fused_flat:
                # ONE-LAUNCH TICK: ring push + alpha-weighted combine +
                # scalars + body + apply, all flat-resident (flat_tick_step;
                # 1 launch on TPU, 2 with clip).  Gradients are born flat
                # under flat-native params; non-f32 storage packs once here.
                from repro.optim.fuse import flat_tick_step

                opt = state.opt_state
                assert isinstance(opt, dict) and set(opt) == {"p", "bufs"}, (
                    "fused async step got a non-fused opt state — initialize "
                    "it with init_train_state(..., fuse=True)"
                )
                flat_params = isinstance(state.params, jax.Array)
                if opt["p"] is not None:
                    p_flat = opt["p"]
                else:
                    p_flat = state.params if flat_params else T.pack_flat(state.params)
                p_new, bufs, new_ring, live = flat_tick_step(
                    plan, state.delayed, _flat_grads(grads), taus, weights,
                    opt["bufs"], p_flat, ctx,
                )
                new_opt = {"p": p_new if opt["p"] is not None else None, "bufs": bufs}
                new_params = p_new if flat_params else T.unpack_flat(p_new, state.params)
            else:
                g_eff, live, new_ring = delayed_combine(
                    state.delayed, grads, taus, weights
                )
                new_params, new_opt = apply_fn(g_eff, state.opt_state, state.params, ctx)
            new_state = TrainState(
                params=new_params, opt_state=new_opt, step=state.step + 1,
                rng=rng, delayed=new_ring, adapt=adapt,
            )
            return new_state, {
                "loss": loss,
                "tau_mean": jnp.mean(taus.astype(jnp.float32)),
                "alpha_mean": jnp.mean(alpha),
                "live_frac": jnp.mean(live),
                **metrics,
            }

        return train_step

    # mode == "sharded_async"
    assert mesh is not None, "sharded_async mode needs the workers mesh"
    from jax.sharding import PartitionSpec as P

    def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        adapt = state.adapt
        ring = state.delayed
        assert isinstance(adapt, WorkerAdaptState), (
            "sharded async step needs a WorkerAdaptState (see make_worker_adapt)"
        )
        assert isinstance(ring, WorkerRing), (
            "sharded async step needs per-worker rings (see init_sharded_async_state)"
        )
        _check_ring_layout(ring.ring)
        W = adapt.num_workers

        loss, metrics, grads = loss_and_grads(state.params, batch)
        if fused_flat:
            # flat-resident: the (W, K, N) ring, the per-worker combine and
            # the fused apply all run over one packed buffer per shard (the
            # pack is a no-op for born-flat flat-native gradients)
            grads = _flat_grads(grads)
        rng, sub = jax.random.split(state.rng)
        u = jax.random.uniform(sub, (W,))

        ring_specs = jax.tree.map(lambda _: P(axis_name), ring.ring)
        grad_specs = jax.tree.map(lambda _: P(), grads)

        def tick(ring_leaves, step, grads, u, cdf, trace, flags, hist, alpha_table):
            taus = sample_worker_taus(u, cdf, trace, flags, step)
            alpha = alpha_table[jnp.clip(taus, 0, alpha_table.shape[0] - 1)]
            weights = alpha / jnp.float32(alpha_c * W)
            keep = _drop_mask(transform, taus)
            if keep is not None:
                weights = weights * keep
            g_eff, live, new_ring = worker_ring_combine(
                ring_leaves, step, grads, taus, weights, axis_name=axis_name
            )
            new_hist = record_worker_taus(hist, taus)
            stats = jax.lax.psum(
                jnp.stack(
                    [jnp.sum(taus.astype(jnp.float32)), jnp.sum(alpha), jnp.sum(live)]
                ),
                axis_name,
            )
            return g_eff, new_ring, new_hist, stats

        g_eff, new_ring, new_hist, stats = jax.shard_map(
            tick,
            mesh=mesh,
            in_specs=(
                ring_specs, P(), grad_specs, P(axis_name),
                P(axis_name, None), P(axis_name, None), P(axis_name),
                P(axis_name, None), P(),
            ),
            out_specs=(grad_specs, ring_specs, P(axis_name, None), P()),
            check_vma=False,
        )(
            ring.ring, ring.step, grads, u, adapt.tau_cdf,
            adapt.tau_trace, adapt.use_trace, adapt.hist, adapt.alpha_table,
        )

        new_adapt = WorkerAdaptState(
            alpha_table=adapt.alpha_table,
            tau_cdf=adapt.tau_cdf,
            tau_trace=adapt.tau_trace,
            use_trace=adapt.use_trace,
            hist=new_hist,
        )
        if fused_flat:
            # XLA cannot partition a Pallas kernel: every device runs the
            # fused apply on its own replica of params, g_eff and opt state.
            # The fused body reads no ctx data once staleness is applied.
            new_params, new_opt = jax.shard_map(
                lambda g, opt, p: apply_fn(
                    g, opt, p,
                    T.StepContext(axis_name=axis_name, staleness_applied=True),
                ),
                mesh=mesh,
                in_specs=(P(), P(), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )(g_eff, state.opt_state, state.params)
        else:
            ctx = T.StepContext(
                adapt=new_adapt, rng=rng, axis_name=axis_name, staleness_applied=True
            )
            new_params, new_opt = apply_fn(g_eff, state.opt_state, state.params, ctx)
        new_state = TrainState(
            params=new_params, opt_state=new_opt, step=state.step + 1,
            rng=rng, delayed=WorkerRing(ring=new_ring, step=ring.step + 1),
            adapt=new_adapt,
        )
        return new_state, {
            "loss": loss,
            "tau_mean": stats[0] / W,
            "alpha_mean": stats[1] / W,
            "live_frac": stats[2] / W,
            **metrics,
        }

    return train_step


# ---------------------------------------------------------------------------
# Legacy factory shims (one PR of call sites each; prefer make_step)
# ---------------------------------------------------------------------------

def make_train_step(cfg, opt) -> Callable:
    """Synchronous step: loss -> grad -> pipeline. Batch is globally sharded
    over (pod, data); XLA inserts the gradient all-reduce."""
    return make_step(cfg, opt, mode="sync")


def make_async_train_step(cfg, opt, *, alpha_c: float, num_workers: int = 1) -> Callable:
    """MindTheStep-AsyncPSGD step (async-as-delay on the mesh); see
    :func:`make_step` ``mode="async"``."""
    return make_step(cfg, opt, mode="async", alpha_c=alpha_c, num_workers=num_workers)


def make_sharded_async_train_step(
    cfg, opt, *, alpha_c: float, mesh, axis_name: str = "workers"
) -> Callable:
    """MindTheStep-AsyncPSGD sharded over a ``workers`` mesh axis; see
    :func:`make_step` ``mode="sharded_async"``."""
    return make_step(
        cfg, opt, mode="sharded_async", alpha_c=alpha_c, mesh=mesh, axis_name=axis_name
    )


def init_sharded_async_state(
    key: jax.Array,
    cfg,
    opt,
    *,
    ring: int,
    adapt: WorkerAdaptState,
    mesh,
    params: Any | None = None,
    fuse: bool = False,
    ring_dtype: Any = None,
) -> TrainState:
    """TrainState for the sharded engine: per-worker rings + WorkerAdaptState.

    The worker count is taken from ``adapt``; ring leaves are (W, K, ...).
    Every leaf is placed on ``mesh``, the workers mesh the step runs on:
    the worker-axis leaves (rings, tau samplers, histograms) split over
    ``workers`` by :func:`repro.sharding.specs.worker_shardings`, everything
    else (params, optimizer state, step counters, rng, alpha table)
    replicated.  That placement is what the step returns, so the state is a
    fixed point of the compiled tick and the second tick hits the jit cache.
    ``fuse=True`` builds the fused layout (flat opt state + one (W, K, N)
    ring buffer) for a fuseable pipeline; pair it with
    ``make_step(..., fuse=True)``.
    """
    state = init_train_state(
        key, cfg, opt, async_ring=0, adapt=adapt, params=params, fuse=fuse
    )
    init_wring = (
        init_flat_worker_ring if fuse and _fused_form(opt) is not None else init_worker_ring
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding.specs import worker_shardings

    replicated = NamedSharding(mesh, P())

    # The rings are made on the mesh, each device holding only its workers'
    # rows: made whole on one device first, the (W, K, N) ring of a real
    # model does not fit there.
    shapes = jax.eval_shape(lambda: state.params)

    def make_ring():
        return init_wring(shapes, ring, adapt.num_workers, dtype=ring_dtype)

    ring_shardings = WorkerRing(
        ring=worker_shardings(jax.eval_shape(make_ring).ring, mesh), step=replicated
    )
    wring = jax.jit(make_ring, out_shardings=ring_shardings)()
    state = dataclasses.replace(state, delayed=wring)

    shardings = jax.tree.map(lambda _: replicated, state)
    shardings.delayed = ring_shardings
    for f in ("tau_cdf", "tau_trace", "use_trace", "hist"):
        setattr(shardings.adapt, f, worker_shardings(getattr(adapt, f), mesh))
    return jax.device_put(state, shardings)


def make_serve_step(cfg) -> Callable:
    """One batched greedy decode step: (params, cache, token, pos) ->
    (next_token, logits, cache)."""

    def serve_step(params, cache, token: jnp.ndarray, pos):
        logits, new_cache = M.decode_step(params, cache, token, pos, cfg)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return {"next_token": next_token, "logits": logits, "cache": new_cache}

    return serve_step
