"""Set-up and the measured window, through the program's own entry.

Set-up builds one engine and its state from the cell's ``RunSpec``, and
drives them with ``repro.run.run`` through the ticks ``correct`` follows:
the first refresh period (its ring wraps, and at its end the host refits
alpha(tau)) and a few ticks under the refit table.  These also warm up every
shape the window uses: the tick and the host refresh.  The window then hands that same state and engine to further
``run`` calls of one refresh period each until ``--seconds`` have passed.

:class:`TimedEngine` is the thin wrapper ``run`` drives.  It times each
refresh on the host clock, marks tick dispatches, refreshes and waits for
the profiler (``jax.profiler.TraceAnnotation``), and keeps one tick in flight:
after dispatching tick i it waits for tick i-1 and notes when that tick
finished.  Before a refresh it notes when the tick before it finished (the
refresh reads the device's histogram, so it waits for that tick anyway).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.run import Hook


class TimedEngine:
    """The program's engine, with host spans and completion times.

    ``run`` calls it as it calls any engine; ``build`` hands over the state
    that set-up (or the previous call) left with :meth:`hand_back`."""

    def __init__(self, engine, state, *, annotate: bool = False):
        self.engine = engine
        self.pipeline = engine.pipeline
        self._state = state
        self._annotate = annotate
        self._pending = None  # the loss of the tick in flight
        self.done: list[float] = []  # host time each tick was seen finished
        self.refresh_s: list[float] = []
        self.losses: list = []

    def _span(self, name):
        if self._annotate:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def _retire(self):
        if self._pending is not None:
            with self._span("wait"):
                jax.block_until_ready(self._pending)
            self.done.append(time.perf_counter())
            self._pending = None

    # -- the Engine protocol, as repro.run.run calls it ---------------------
    def build(self):
        state, self._state = self._state, None
        return state

    def require_refreshable(self, state):
        self.engine.require_refreshable(state)

    def tick(self, state, batch):
        with self._span("tick"):
            state, metrics = self.engine.tick(state, batch)
        self._retire()
        self._pending = metrics["loss"]
        self.losses.append(metrics["loss"])
        return state, metrics

    def refresh(self, state):
        self._retire()
        t0 = time.perf_counter()
        with self._span("refresh"):
            state = self.engine.refresh(state)
        self.refresh_s.append(time.perf_counter() - t0)
        return state

    def finish(self, state):
        return self.engine.finish(state)

    def abort(self):
        self.engine.abort()

    def liveness(self):
        return self.engine.liveness()

    # -- between run calls ----------------------------------------------------
    def drain(self, state) -> float:
        """Wait for the tick in flight and the state; the time it finished."""
        self._retire()
        jax.block_until_ready(state)
        return self.done[-1] if self.done else time.perf_counter()

    def hand_back(self, state):
        self._state = state


@dataclasses.dataclass
class Program:
    """The program readings of the followed ticks (host arrays)."""

    losses: np.ndarray
    grad_norms: np.ndarray
    change_norms: np.ndarray


def _change_norms(shapes, flat, key):
    from bench.data import _make_weights, leaf_norms, unflatten

    p0 = _make_weights(shapes, key)
    return leaf_norms(shapes, jax.tree.map(jnp.subtract, unflatten(shapes, flat), p0))


class Reader(Hook):
    """Hook that reads the ticks ``correct`` follows (``cell.followed_ticks``),
    over as many ``run`` calls as set-up makes.

    The first gradient is read from the state after tick 1, as the optimizer
    holds it: the ring's first row (async), or the velocity over ``-lr``
    (sync, whose velocity starts at zero).  The change is the params after
    the last followed tick less the seed's weights, read before the next
    tick overwrites them.
    """

    def __init__(self, cell, seed):
        from bench.data import _STREAM_WEIGHTS, leaf_norms, seed_key, unflatten

        self.cell = cell
        self.key = seed_key(seed, _STREAM_WEIGHTS)
        shapes = cell.shapes
        self.norms = jax.jit(lambda flat: leaf_norms(shapes, unflatten(shapes, flat)))
        self.change = jax.jit(functools.partial(_change_norms, shapes))
        self.grad_norms = self.change_norms = None
        self.losses = []
        self.ticks = 0

    def on_tick(self, ctx):
        self.ticks += 1
        if self.ticks > self.cell.followed_ticks:
            return
        self.losses.append(ctx.metrics["loss"])
        if self.ticks == 1:
            state = ctx.state
            if self.cell.traffic["engine"] == "sync":
                v = state.opt_state["bufs"]
                self.grad_norms = self.norms(v) / float(self.cell.traffic["lr"])
            else:
                self.grad_norms = self.norms(state.delayed.ring[0])
        if self.ticks == self.cell.followed_ticks:
            self.change_norms = self.change(ctx.state.params, self.key)

    def result(self) -> Program:
        return Program(
            losses=np.asarray(jnp.stack(self.losses), np.float64),
            grad_norms=np.asarray(self.grad_norms, np.float64),
            change_norms=np.asarray(self.change_norms, np.float64),
        )


def chunk_spec(spec, pool, start: int, ticks: int):
    """``spec`` for one orchestrator call of ``ticks`` ticks, cycling the pool
    from tick ``start``."""
    n = len(pool)
    return dataclasses.replace(
        spec, num_steps=ticks, batch_fn=lambda i: pool[(start + i) % n], params=None,
    )


def followed_batches(cell, pool) -> list:
    """The batches of the followed ticks, as ``chunk_spec`` hands them out."""
    return [pool[i % len(pool)] for i in range(cell.followed_ticks)]


def setup(cell, seed: int, *, annotate: bool = False):
    """Build the program's engine and state for ``cell`` and drive them
    through the followed ticks: the first refresh period, its refit, and
    the ticks after it.

    Returns ``(timed, spec, pool, reader, times)``: the wrapped engine
    holding the state, the spec, the batch pool, the program readings of
    the followed ticks, and the set-up's phases in seconds.
    """
    from bench.cells import model_config, run_spec
    from bench.data import make_pool, make_weights

    from repro.run import make_engine, run

    times = {}
    t0 = time.perf_counter()
    cfg = model_config(cell.config)
    weights = make_weights(cell.shapes, seed)
    spec = run_spec(cell, cfg, weights, seed=seed)
    engine = make_engine(spec)
    state = engine.build()
    spec = dataclasses.replace(spec, params=None)
    del weights
    pool = make_pool(cell.config, cell.traffic, seed)
    jax.block_until_ready((state, pool))
    times["build"] = time.perf_counter() - t0

    reader = Reader(cell, seed)
    timed = TimedEngine(engine, state, annotate=annotate)
    t0 = time.perf_counter()
    # the first tick traces and compiles; a run of one tick shows its cost
    first = _FirstTick()
    result = run(chunk_spec(spec, pool, 0, cell.chunk), hooks=[reader, first], engine=timed)
    rest = cell.followed_ticks - cell.chunk
    timed.hand_back(result.state)
    result = run(chunk_spec(spec, pool, cell.chunk, rest), hooks=[reader], engine=timed)
    timed.drain(result.state)
    times["first_tick"] = first.seconds
    times["warm_up"] = time.perf_counter() - t0 - first.seconds
    timed.hand_back(result.state)
    return timed, spec, pool, reader, times


class _FirstTick(Hook):
    """Times the first tick, which traces and compiles."""

    def on_start(self, ctx):
        self._t = time.perf_counter()

    def on_tick(self, ctx):
        if ctx.step == 1:
            jax.block_until_ready(ctx.metrics["loss"])
            self.seconds = time.perf_counter() - self._t


def window(timed, spec, pool, cell, seconds: float, *, max_chunks=None):
    """Run whole refresh periods after the followed ticks until ``seconds``
    have passed (or ``max_chunks`` periods).  Returns ``(state, stats)``: the window's tick
    count, its seconds, the intervals between tick completions, the
    retraces counted inside it and the losses of its ticks."""
    from repro.run import run

    engine = timed.engine
    traces_before = engine.retraces
    n_done, n_ref, n_loss = len(timed.done), len(timed.refresh_s), len(timed.losses)
    t0 = timed.done[-1]
    start_tick = tick = cell.followed_ticks
    chunks = 0
    while True:
        state = run(chunk_spec(spec, pool, tick, cell.chunk), engine=timed).state
        tick += cell.chunk
        chunks += 1
        if chunks == max_chunks or (max_chunks is None and time.perf_counter() - t0 >= seconds):
            break
        timed.hand_back(state)
    t1 = timed.drain(state)
    return state, {
        "ticks": tick - start_tick,
        "seconds": t1 - t0,
        "intervals_s": np.diff(np.asarray([t0] + timed.done[n_done:])),
        "refresh_s": timed.refresh_s[n_ref:],
        "retraces": engine.retraces - traces_before,
        "losses": timed.losses[n_loss:],
    }
