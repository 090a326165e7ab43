"""The One Run API: ``run(spec, hooks=...)``.

Every execution surface in the repo — the production launcher, the scenario
matrix, the examples, and the deprecated ``train_loop`` shim — drives
training through this one orchestrator.  The loop itself is deliberately
tiny and mode-blind:

    state = engine.build()                    # or restore via resume_from
    for step in 1..num_steps:
        state, metrics = engine.tick(state, batch)
        if refresh boundary: state = engine.refresh(state)   # then on_refresh
        hooks.on_tick
    state = engine.finish(state)              # success path: engines drain
    hooks.on_end                              # (failure path: engine.abort())

The loop writes host spans into the profiler's trace: ``run.input`` around
each ``next(batches)`` and ``run.hooks`` around each pass over the hooks.

Engine modes, fusion, sharding, and the online-adaptation boundary live in
:mod:`repro.run.engine`; logging/bench/eval/checkpointing live in
:mod:`repro.run.hooks`.  Resume is first-class: ``resume_from=directory``
restores the latest full-fidelity checkpoint (device state + host estimator
sidecar, :mod:`repro.run.ckpt`) into the engine-built template and continues
bit-identically to the uninterrupted run.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

from jax.profiler import TraceAnnotation

from repro.run.engine import Engine, make_engine
from repro.run.hooks import Hook
from repro.run.spec import RunSpec

__all__ = ["RunContext", "RunResult", "run"]


@dataclasses.dataclass
class RunContext:
    """Live run state handed to every hook callback.

    ``step`` counts *completed* ticks (1-based; equals ``start_step`` until
    the first tick of this process).  ``metrics`` is the latest tick's metric
    dict (device arrays — hooks convert to host floats only when they consume
    them).  ``history`` and ``records`` are shared scratch: LogHook/EvalHook
    append history rows; BenchHook files its rows under ``records[name]``.
    """

    spec: RunSpec
    engine: Engine
    state: Any
    step: int = 0
    start_step: int = 0
    metrics: dict | None = None
    history: list = dataclasses.field(default_factory=list)
    records: dict = dataclasses.field(default_factory=dict)

    @property
    def is_last(self) -> bool:
        return self.step == self.spec.num_steps


@dataclasses.dataclass
class RunResult:
    """What a run hands back: final state, history rows, bench records."""

    state: Any
    history: list
    records: dict
    step: int
    start_step: int = 0


def run(
    spec: RunSpec,
    hooks: Sequence[Hook] = (),
    *,
    resume_from: str | None = None,
    resume_step: int | None = None,
    engine: Engine | None = None,
) -> RunResult:
    """Execute ``spec`` under the hook lifecycle; returns a :class:`RunResult`.

    ``resume_from`` names a :class:`~repro.run.hooks.CheckpointHook` directory:
    the latest checkpoint (or ``resume_step``) is restored into the
    engine-built template — same spec, same fuse layout — and the loop
    continues from there, bit-identical (f32) to the uninterrupted run.
    ``engine`` overrides the spec-built engine (the ``train_loop`` shim passes
    a :class:`~repro.run.engine.PrebuiltEngine` here).
    """
    if engine is None:
        engine = make_engine(spec)
    start_step = 0
    if resume_from is not None:
        from repro.run.ckpt import restore_checkpoint

        # Restore needs only a shape/dtype template, not initialized arrays:
        # build_template traces the build abstractly (no model-init FLOPs, no
        # ring allocation) where the engine supports it.
        template = engine.build_template()
        state, start_step = restore_checkpoint(
            resume_from, template, engine.pipeline, step=resume_step
        )
        assert start_step <= spec.num_steps, (
            f"checkpoint step {start_step} is beyond num_steps={spec.num_steps}"
        )
    else:
        state = engine.build()
    if spec.refresh_every:
        # Fail fast, before any (possibly TPU-scale) step runs: the refresh
        # boundary needs a refresh-capable pipeline and an AdaptState.
        engine.require_refreshable(state)
    ctx = RunContext(spec=spec, engine=engine, state=state, step=start_step, start_step=start_step)
    batches = spec.batch_stream(start_step)
    for hook in hooks:
        hook.on_start(ctx)
    try:
        for i in range(start_step, spec.num_steps):
            with TraceAnnotation("run.input"):
                batch = next(batches)
            state, metrics = engine.tick(state, batch)
            ctx.state, ctx.metrics, ctx.step = state, metrics, i + 1
            if spec.refresh_every and (i + 1) % spec.refresh_every == 0:
                state = engine.refresh(state)
                ctx.state = state
                with TraceAnnotation("run.hooks"):
                    for hook in hooks:
                        hook.on_refresh(ctx)
            with TraceAnnotation("run.hooks"):
                for hook in hooks:
                    hook.on_tick(ctx)
    except BaseException:
        # The lifecycle's failure path: engines running live machinery
        # (worker threads/processes) tear it down without draining; a live
        # trace capture stays salvageable.  Part of the Engine protocol —
        # a no-op for purely-compiled engines.
        engine.abort()
        raise
    # The lifecycle's success path: live engines drain outstanding work
    # here, so on_end hooks (e.g. a final checkpoint) observe the
    # fully-applied state.  Identity for purely-compiled engines.
    ctx.state = state = engine.finish(ctx.state)
    for hook in hooks:
        hook.on_end(ctx)
    return RunResult(
        state=ctx.state,
        history=ctx.history,
        records=ctx.records,
        step=ctx.step,
        start_step=start_step,
    )
