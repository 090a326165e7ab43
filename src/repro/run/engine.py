"""Engine protocol: one uniform execution surface per engine mode.

An :class:`Engine` owns the jit boundary of a run and nothing else:

* ``build()``             — initial :class:`~repro.training.steps.TrainState`
  (params init from ``spec.seed``, optimizer state from the pipeline, delayed
  rings / adaptation state for the async modes, fused layouts under
  ``spec.fuse``);
* ``tick(state, batch)``  — one compiled training step ``-> (state, metrics)``;
* ``refresh(state)``      — the host-side online-adaptation boundary (drain
  the in-jit histogram, refit, swap same-shape tables; no retrace);
* ``finish(state)`` / ``abort()`` / ``liveness()`` — the mandatory lifecycle
  tail (drain-and-teardown, failure-path teardown, live-machinery health);
  no-ops for purely-compiled engines, real for the live parameter server.

The three concrete engines wrap the existing factories —
:func:`~repro.training.steps.make_step`,
:func:`~repro.training.steps.init_train_state`, and
:func:`~repro.training.steps.init_sharded_async_state` — so a pipeline means
the *same update* whichever engine executes it (the PR-3 invariant), and the
orchestrator (:mod:`repro.run.orchestrator`) never branches on mode.

Every spec-built engine counts jit (re)traces (``engine.retraces``): any
retrace beyond the first compile is an online-adaptation regression (tables
must stay step inputs), surfaced by :class:`~repro.run.hooks.BenchHook` as a
gated bench row.

The engine writes host spans into the profiler's trace
(``jax.profiler.TraceAnnotation``; next to free with no profiler running):
``engine.tick`` around the dispatch of each tick, ``engine.trace`` around
each (re)trace of the step, and ``engine.refresh`` around each refresh, whose
phases are spanned in :mod:`repro.training.adapt`.

:class:`PrebuiltEngine` adapts a hand-built ``(step_fn, state)`` pair to the
same protocol — it is how the deprecated ``train_loop`` shim rides the
orchestrator without behavior change.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Protocol, runtime_checkable

import jax
from jax.profiler import TraceAnnotation

from repro.run.spec import RunSpec

__all__ = [
    "Engine",
    "SyncEngine",
    "AsyncEngine",
    "ShardedAsyncEngine",
    "PrebuiltEngine",
    "make_engine",
]


@runtime_checkable
class Engine(Protocol):
    """The execution surface of one run.

    The FULL lifecycle is part of the protocol — the orchestrator calls
    every one of these without ``hasattr`` probing::

        build (or build_template + checkpoint restore)   # once
        tick*                                            # the training loop
        refresh*                                         # at refresh_every
        finish | abort                                   # exactly one, at exit

    ``finish(state)`` is the success path: engines running live machinery
    (worker threads/processes, trace captures) drain outstanding work and
    return the fully-applied state — hooks' ``on_end`` observes its result.
    ``abort()`` is the failure path (any exception escaping the loop): tear
    down WITHOUT draining, leaving crash evidence (e.g. a ``.part`` trace)
    salvageable.  ``liveness()`` reports live-machinery health (per-worker
    last-seen / dead sets for the parameter server; ``{}`` where nothing
    lives).  Purely-compiled engines inherit no-op defaults for all three
    from ``_EngineBase`` — the contract is uniform, not optional.
    """

    pipeline: Any

    def build(self) -> Any: ...

    def build_template(self) -> Any: ...

    def tick(self, state: Any, batch: Any) -> tuple[Any, dict]: ...

    def refresh(self, state: Any) -> Any: ...

    def require_refreshable(self, state: Any) -> None: ...

    def finish(self, state: Any) -> Any: ...

    def abort(self) -> None: ...

    def liveness(self) -> dict: ...


def _buffer_ptr(x) -> int | None:
    """Device address of an array's first shard (identifies shared buffers)."""
    if not isinstance(x, jax.Array) or x.size == 0:
        return None
    return x.addressable_shards[0].data.unsafe_buffer_pointer()


def _refresher_of(pipeline):
    """The refresh-capable handle of ``pipeline``: a scale_by_staleness link
    (possibly inside a chain) or a legacy MindTheStep-style wrapper.  Shares
    :func:`repro.run.ckpt.refresh_link_of`'s resolution, so the checkpointed
    host state and the object a refresh mutates are always the same."""
    from repro.run.ckpt import refresh_link_of

    link = refresh_link_of(pipeline)
    assert link is not None, (
        "refresh requested but the pipeline has no scale_by_staleness link "
        "(or estimator-carrying wrapper)"
    )
    return link


class _EngineBase:
    """Shared plumbing: trace counting, jit, donation, the refresh boundary."""

    # Spec-built engines donate the state into the fused tick (flat-resident
    # buffers update in place); PrebuiltEngine keeps the caller's contract.
    _donate_state = True

    def __init__(self, spec: RunSpec):
        self.spec = spec
        self.pipeline = spec.pipeline
        self.mesh = spec.mesh
        self._traces: list[int] = []
        self._tick: Callable | None = None
        self._params_released = False

    @property
    def retraces(self) -> int | None:
        """Times jax (re)traced the step (1 after a healthy run); None when
        the step arrived pre-compiled (PrebuiltEngine) and cannot be counted."""
        return len(self._traces)

    def _jit(self, base: Callable) -> Callable:
        def counting(state, batch):
            self._traces.append(1)  # runs only when jax (re)traces
            with TraceAnnotation("engine.trace"):
                return base(state, batch)

        if self._donate_state and self.spec.fuse:
            # Fused layouts rewrite params / rings / flat optimizer state
            # wholesale each tick: donating the state lets XLA alias those
            # buffers tick-over-tick instead of copying the (K, N) /
            # (W, K, N) ring every step.  ``_own`` below hands the loop an
            # owned state, so donation never deletes spec-held arrays.
            return jax.jit(counting, donate_argnums=(0,))
        return jax.jit(counting)

    def _own(self, state):
        """Copy the leaves of the built state that share a device buffer
        with the spec (``spec.params``, ``spec.adapt``) when ticks will
        donate the state: a donated buffer is deleted after the call, and
        the spec's arrays must survive this run — and the next run built
        from the same spec.  Leaves the build made fresh (the packed params,
        the zero ring, the optimizer state) are donated as they are, so a
        full-width state is held once, not twice."""
        if not (self._donate_state and self.spec.fuse):
            return state
        import jax.numpy as jnp

        held = {_buffer_ptr(x) for x in jax.tree.leaves((self.spec.params, self.spec.adapt))}
        held.discard(None)
        return jax.tree.map(lambda x: jnp.copy(x) if _buffer_ptr(x) in held else x, state)

    def _build(self, key):
        """Initial state from a PRNG key (the key stays an *argument* so
        :meth:`build_template` can trace this abstractly)."""
        raise NotImplementedError

    def build(self):
        """The initial state.  A donated fused state owns all its buffers
        (:meth:`_own`), so the engine then lets go of ``spec.params``: held
        for the whole run they would keep a second full-width copy of the
        weights on the device.  The caller's spec keeps them; a further
        build of this engine raises (make a new engine from the spec)."""
        if self._params_released:
            raise RuntimeError(
                "this engine already built its state from spec.params and released "
                "them; build a new engine from the spec (make_engine(spec))"
            )
        state = self._own(self._build(jax.random.PRNGKey(self.spec.seed)))
        if self._donate_state and self.spec.fuse and self.spec.params is not None:
            self.spec = dataclasses.replace(self.spec, params=None)
            self._params_released = True
        return state

    def build_template(self):
        """Shape/dtype-only build for checkpoint restore: the resume path
        needs a structural template, not initialized arrays, so this traces
        :meth:`_build` with ``jax.eval_shape`` — no model init FLOPs, no
        ring allocation.  Engines whose build cannot trace abstractly (e.g.
        a sharded ``device_put`` that rejects tracers, or a prebuilt state)
        fall back to the concrete build."""
        try:
            return jax.eval_shape(self._build, jax.random.PRNGKey(self.spec.seed))
        except Exception:
            return self.build()

    def tick(self, state, batch):
        if self._tick is None:
            self._tick = self._jit(self._make_step())
        with TraceAnnotation("engine.tick"):
            return self._tick(state, batch)

    def require_refreshable(self, state) -> None:
        """Fail fast (the orchestrator calls this before the first tick):
        refresh() needs a refresh-capable pipeline and an AdaptState."""
        _refresher_of(self.pipeline)
        assert getattr(state, "adapt", None) is not None, (
            "refresh requested but the state carries no AdaptState — "
            "build it with init_adapt/make_adapt and pass it via RunSpec.adapt"
        )

    def refresh(self, state):
        from repro.training.adapt import (
            WorkerAdaptState,
            host_refresh,
            worker_host_refresh,
        )

        with TraceAnnotation("engine.refresh"):
            self.require_refreshable(state)
            adapt = state.adapt
            refresher = _refresher_of(self.pipeline)
            kwargs = dict(self.spec.refresh_kwargs or {})
            if isinstance(adapt, WorkerAdaptState):
                new_adapt = worker_host_refresh(adapt, refresher, mesh=self.mesh, **kwargs)
            else:
                new_adapt = host_refresh(adapt, refresher, **kwargs)
            return dataclasses.replace(state, adapt=new_adapt)

    # -- lifecycle defaults (Engine protocol): compiled engines hold no live
    # machinery, so success-path finish is identity, failure-path abort and
    # the liveness report are no-ops.  Engines that DO run live machinery
    # (DistributedAsyncEngine) override all three.

    def finish(self, state):
        return state

    def abort(self) -> None:
        pass

    def liveness(self) -> dict:
        return {}

    def _make_step(self) -> Callable:
        raise NotImplementedError


class SyncEngine(_EngineBase):
    """Synchronous data-parallel engine (paper §III SyncPSGD baseline)."""

    def _build(self, key):
        from repro.training.steps import init_train_state

        spec = self.spec
        return init_train_state(
            key,
            spec.cfg,
            spec.pipeline,
            adapt=spec.adapt,
            params=spec.params,
            fuse=spec.fuse,
        )

    def _make_step(self):
        from repro.training.steps import make_step

        spec = self.spec
        return make_step(spec.cfg, spec.pipeline, mode="sync", alpha_c=spec.alpha_c, fuse=spec.fuse)


class AsyncEngine(_EngineBase):
    """MindTheStep-AsyncPSGD engine: W-worker async-as-delay simulation."""

    def __init__(self, spec: RunSpec):
        super().__init__(spec)
        assert spec.ring > 0, "async mode needs RunSpec.ring (delayed-ring depth)"
        assert spec.adapt is not None, "async mode needs RunSpec.adapt (see make_adapt)"

    def _build(self, key):
        from repro.training.steps import init_train_state

        spec = self.spec
        return init_train_state(
            key,
            spec.cfg,
            spec.pipeline,
            async_ring=spec.ring,
            adapt=spec.adapt,
            params=spec.params,
            fuse=spec.fuse,
            ring_dtype=spec.ring_dtype,
        )

    def _make_step(self):
        from repro.training.steps import make_step

        spec = self.spec
        return make_step(
            spec.cfg,
            spec.pipeline,
            mode="async",
            alpha_c=spec.alpha_c,
            num_workers=spec.num_workers,
            fuse=spec.fuse,
        )


class ShardedAsyncEngine(_EngineBase):
    """The W-worker simulation under ``shard_map`` over the ``workers`` axis."""

    def __init__(self, spec: RunSpec):
        super().__init__(spec)
        assert spec.ring > 0, "sharded_async mode needs RunSpec.ring"
        assert spec.adapt is not None, (
            "sharded_async mode needs RunSpec.adapt (a WorkerAdaptState; "
            "see make_worker_adapt)"
        )
        if self.mesh is None:
            from repro.launch.mesh import make_workers_mesh

            self.mesh = make_workers_mesh()

    def _build(self, key):
        from repro.training.steps import init_sharded_async_state

        spec = self.spec
        return init_sharded_async_state(
            key,
            spec.cfg,
            spec.pipeline,
            ring=spec.ring,
            adapt=spec.adapt,
            params=spec.params,
            mesh=self.mesh,
            fuse=spec.fuse,
            ring_dtype=spec.ring_dtype,
        )

    def _make_step(self):
        from repro.training.steps import make_step

        spec = self.spec
        return make_step(
            spec.cfg,
            spec.pipeline,
            mode="sharded_async",
            alpha_c=spec.alpha_c,
            mesh=self.mesh,
            axis_name=spec.axis_name,
            fuse=spec.fuse,
        )


class PrebuiltEngine(_EngineBase):
    """Adapter for a hand-built ``(step_fn, state)`` pair (train_loop shim).

    ``step_fn`` is jitted here unless it already is (``.lower`` duck check —
    the historical ``train_loop`` contract); a pre-compiled step cannot be
    trace-counted, so ``retraces`` is None in that case.  No state donation:
    the caller owns the state and may reuse it after the run.
    """

    _donate_state = False

    def __init__(
        self,
        step_fn: Callable,
        state: Any,
        *,
        pipeline=None,
        mesh=None,
        spec: RunSpec | None = None,
    ):
        super().__init__(spec if spec is not None else RunSpec())
        self.pipeline = pipeline
        self.mesh = mesh
        self._state = state
        if hasattr(step_fn, "lower"):
            self._tick = step_fn
            self._precompiled = True
        else:
            self._tick = self._jit(step_fn)
            self._precompiled = False

    @property
    def retraces(self) -> int | None:
        return None if self._precompiled else len(self._traces)

    def build(self):
        return self._state


_ENGINES = {
    "sync": SyncEngine,
    "async": AsyncEngine,
    "sharded_async": ShardedAsyncEngine,
}


def make_engine(spec: RunSpec) -> Engine:
    """The engine for ``spec.mode`` (sync | async | sharded_async |
    distributed).  The live parameter-server engine imports lazily — thread
    and transport machinery stays out of the simulated-mode import path."""
    if spec.mode == "distributed":
        from repro.distributed.engine import DistributedAsyncEngine

        return DistributedAsyncEngine(spec)
    return _ENGINES[spec.mode](spec)
