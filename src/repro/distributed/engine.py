"""DistributedAsyncEngine: live AsyncPSGD behind the Engine protocol.

The orchestrator sees a normal engine — the full typed lifecycle ``build ->
tick* -> refresh* -> finish | abort`` of :class:`repro.run.engine.Engine` —
but a tick does no compute itself: it submits the batch to a :class:`~repro
.distributed.server.ParameterServer` owning the state, and
``spec.num_workers`` live workers (launched BY the transport: threads for
``inproc``, spawned processes for ``socket`` — see ``make_transport``) pull
snapshots, compute gradients, and push them back with real, measured
staleness.

The tick keeps up to ``num_workers - 1`` gradients in flight: tick ``t``
submits batch ``t`` and waits until at least ``t - (W-1)`` updates have been
applied.  That is the natural pipelining of a W-worker parameter server —
every snapshot a worker computes on can be up to W-1 updates stale — while
still guaranteeing each tick observes at least one fresh applied update (so
hook metrics are always real).  The pacing is deadlock-free even under
worker crashes: with ``spec.worker_timeout`` set, the server's liveness
sweep reclaims a dead worker's in-flight batch for a live worker, so the
awaited version always arrives (or the tick raises a diagnostic timeout
naming the dead workers).  ``spec.faults`` threads a :class:`~repro
.distributed.faults.FaultPlan` through the server AND every worker;
``spec.retry`` tunes the workers' rpc-timeout/backoff policy.

The cluster starts lazily on the FIRST tick, using that tick's incoming
state as the server's initial state — which is exactly how ``resume_from``
restoration flows in: the orchestrator restores the checkpoint into the
engine-built template, and the server picks up from the restored version
(the trace capture reopens in resume mode, extending the prior records
instead of clobbering them).  ``finish`` drains every outstanding gradient,
stops the workers, and finalizes the trace; ``abort`` (the orchestrator's
failure path) stops without draining and leaves a salvageable ``.part``
trace behind.  ``liveness`` surfaces the server's per-worker health
(last-seen stamps, declared-dead set, reclaimed batches).
"""

from __future__ import annotations

from typing import Any

from repro.run.engine import _EngineBase
from repro.run.spec import RunSpec

__all__ = ["DistributedAsyncEngine"]


class DistributedAsyncEngine(_EngineBase):
    """Live parameter-server engine; see module docstring."""

    _donate_state = False  # the server owns state evolution; never alias it
    tick_timeout_s = 120.0

    def __init__(self, spec: RunSpec):
        super().__init__(spec)
        assert spec.num_workers >= 1, "distributed mode needs num_workers >= 1"
        self._server = None
        self._transport = None
        self._workers: list = []
        self._trace_writer = None
        self._submitted = 0
        self._base_version = 0

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

    # -- cluster lifecycle ---------------------------------------------------

    def _start(self, state) -> None:
        from repro.distributed.server import ParameterServer
        from repro.distributed.transport import make_transport

        spec = self.spec
        # reprolint: disable=RL001 — one sync per run at engine start, not per tick
        self._base_version = int(state.step)
        if spec.trace_path:
            from repro.async_engine.events import TraceWriter

            self._trace_writer = TraceWriter(
                spec.trace_path, resume=self._base_version > 0
            )
        transport = make_transport(spec.transport, **(spec.transport_opts or {}))
        server = ParameterServer(
            state,
            self.pipeline,
            transport,
            fuse=spec.fuse,
            trace=self._trace_writer,
            on_trace=self._traces.append,
            faults=spec.faults,
            worker_timeout=spec.worker_timeout,
            num_workers=spec.num_workers,
        )
        server.start()
        # registered before any worker starts, so abort() tears the server
        # down when a worker cannot start
        self._server, self._transport, self._workers = server, transport, []
        self._submitted = 0
        for w in range(spec.num_workers):
            self._workers.append(
                transport.start_worker(w, spec.cfg, faults=spec.faults, retry=spec.retry)
            )

    def _stop_cluster(self, *, finalize: bool) -> None:
        self._server.request_stop()
        for w in self._workers:
            w.join(timeout=30)
        self._server.shutdown()
        self._transport.close()
        if self._trace_writer is not None:
            if finalize:
                self._trace_writer.finalize()
            else:
                self._trace_writer.abort()
        self._server = None
        self._transport = None
        self._workers = []
        self._trace_writer = None

    # -- Engine protocol -----------------------------------------------------

    def tick(self, state, batch) -> tuple[Any, dict]:
        if self._server is None:
            self._start(state)
        self._server.submit_batch(batch)
        self._submitted += 1
        lag = self.spec.num_workers - 1  # gradients allowed in flight
        target = self._base_version + max(1, self._submitted - lag)
        self._server.await_applied(target, timeout=self.tick_timeout_s)
        return self._server.snapshot()

    def refresh(self, state):
        if self._server is None:
            return super().refresh(state)
        return self._server.call(super().refresh)

    def finish(self, state):
        """Drain every outstanding gradient, stop workers, finalize trace."""
        if self._server is None:
            return state
        self._server.await_applied(
            self._base_version + self._submitted, timeout=self.tick_timeout_s
        )
        state, _ = self._server.snapshot()
        self._stop_cluster(finalize=True)
        return state

    def abort(self) -> None:
        """Failure-path teardown: no drain, trace left as a ``.part``."""
        if self._server is None:
            return
        self._stop_cluster(finalize=False)

    def liveness(self) -> dict:
        """The server's per-worker health snapshot ({} before first tick)."""
        if self._server is None:
            return {}
        return self._server.liveness()
