"""Live parameter-server subsystem: transport, staleness stamping, engine.

Covers the ISSUE-8 tentpole end to end:

* trace I/O — versioned header, append-safe writes, partial-trace detection,
  resume-extend semantics (a crashed capture is salvageable, never silently
  truncated);
* the in-proc transport — FIFO ordering and bounded-queue backpressure;
* staleness stamping — a scripted pull/push interleaving yields exactly the
  update-count deltas, and a W=1 live run matches a hand-rolled serial
  oracle update-for-update (tau == 0 throughout);
* DistributedAsyncEngine through ``run(spec, hooks=...)`` — live W>=4 runs
  with Log/Bench/Checkpoint hooks, refresh boundaries, checkpoint/resume
  continuing the server state AND extending the trace, failure-path abort;
* live-trace -> trace-replay round trip — the captured distribution replays
  through the sharded simulator's per-worker trace samplers and converges.

The ISSUE-9 fault-tolerance layer rides on top (``chaos``-marked classes):

* FaultPlan parsing/scoping, the make_transport registry + context-manager
  close semantics, worker retry-with-backoff and EOFError-means-exit;
* scripted liveness — a silent worker's in-flight batch is reclaimed (no
  deadlock), its late push resurrects it; server death leaves a salvageable
  ``.part``; v1 traces still load next to v2 wall-clock records;
* the fault matrix — every FAULT_KIND injected into a live W=4 run through
  ``run(spec, hooks=...)``, which must converge and finalize a v2 trace.

Everything here runs under the ``distributed`` marker (own CI leg with a
timeout guard); the socket test spawns real worker processes on localhost.
"""

import dataclasses
import glob
import socket
import struct
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.async_engine.events import TraceError, TraceWriter, load_trace
from repro.configs import get_config, reduced
from repro.core.staleness import Poisson, fit_all_models
from repro.core.step_size import make_schedule
from repro.data import make_batch_for
from repro.distributed import (
    FaultPlan,
    FaultSpec,
    InProcTransport,
    ParameterServer,
    RetryPolicy,
    SocketWorkerEndpoint,
    make_grad_fn,
    make_transport,
    parse_faults,
    transport_kinds,
    worker_loop,
)
from repro.optim import transform as T
from repro.run import BenchHook, CheckpointHook, Hook, LogHook, RunSpec, run
from repro.training import init_train_state, make_adapt, make_worker_adapt
from repro.training.adapt import record_taus

pytestmark = pytest.mark.distributed

TAU_MAX = 31
RING = 8
LR = 0.05


@pytest.fixture(scope="module")
def tiny_cfg():
    return reduced(get_config("stablelm-1.6b"), d_model=32)


def _sched():
    return make_schedule("poisson_momentum", LR, Poisson(3.0), K=1.0, tau_max=TAU_MAX)


def _pipeline(workers=4):
    link = T.scale_by_staleness(_sched(), LR, m=workers, tau_max=TAU_MAX)
    return T.chain(link, T.scale(-LR))


def _adapt():
    return make_adapt(_sched(), Poisson(3.0), cdf_support=RING, tau_max=TAU_MAX)


def _spec(cfg, *, workers=4, num_steps=8, trace_path=None, **kw):
    return RunSpec(
        cfg=cfg,
        pipeline=_pipeline(workers),
        mode="distributed",
        num_steps=num_steps,
        batch_fn=lambda t: make_batch_for(cfg, batch=2, seq=8, seed=100 + t),
        num_workers=workers,
        adapt=_adapt(),
        trace_path=trace_path,
        seed=0,
        **kw,
    )


# ---------------------------------------------------------------------------
# Trace I/O (events.py format)
# ---------------------------------------------------------------------------


class TestTraceIO:
    def test_roundtrip(self, tmp_path):
        path = str(tmp_path / "t.bin")
        w = TraceWriter(path)
        for i in range(5):
            w.append(i, worker=i % 2)
        assert w.finalize() == path
        assert not glob.glob(path + ".part")
        taus, workers = load_trace(path, return_workers=True)
        np.testing.assert_array_equal(taus, np.arange(5))
        np.testing.assert_array_equal(workers, np.arange(5) % 2)

    def test_unfinalized_refused_then_salvaged(self, tmp_path):
        path = str(tmp_path / "t.bin")
        w = TraceWriter(path)
        for i in range(3):
            w.append(i)
        w.abort()  # crash stand-in: .part left behind, no finalized file
        with pytest.raises(TraceError, match="never finalized"):
            load_trace(path)
        np.testing.assert_array_equal(load_trace(path, allow_partial=True), np.arange(3))

    def test_torn_tail_detected_and_truncated(self, tmp_path):
        path = str(tmp_path / "t.bin")
        w = TraceWriter(path)
        w.append(7)
        w.finalize()
        with open(path, "ab") as f:
            f.write(b"\x01\x02\x03")  # torn final record
        with pytest.raises(TraceError, match="torn"):
            load_trace(path)
        np.testing.assert_array_equal(load_trace(path, allow_partial=True), [7])

    def test_bad_magic_and_version(self, tmp_path):
        bad = str(tmp_path / "bad.bin")
        with open(bad, "wb") as f:
            f.write(b"NOTATRCE" + struct.pack("<II", 1, 8))
        with pytest.raises(TraceError, match="magic"):
            load_trace(bad)
        futur = str(tmp_path / "future.bin")
        with open(futur, "wb") as f:
            f.write(b"REPROTRC" + struct.pack("<II", 99, 8))
        with pytest.raises(TraceError, match="version 99"):
            load_trace(futur)

    def test_missing_raises(self, tmp_path):
        with pytest.raises(TraceError, match="no trace file"):
            load_trace(str(tmp_path / "absent.bin"))

    def test_resume_extends_finalized(self, tmp_path):
        path = str(tmp_path / "t.bin")
        w = TraceWriter(path)
        for i in range(3):
            w.append(i, worker=0)
        w.finalize()
        w2 = TraceWriter(path, resume=True)
        assert w2.count == 3
        w2.append(9, worker=1)
        w2.finalize()
        taus, workers = load_trace(path, return_workers=True)
        np.testing.assert_array_equal(taus, [0, 1, 2, 9])
        np.testing.assert_array_equal(workers, [0, 0, 0, 1])

    def test_v1_records_still_load(self, tmp_path):
        """Pre-ISSUE-9 captures (no wall-clock stamps) load unchanged: times
        come back as None, and resume-extending one upgrades it to v2."""
        path = str(tmp_path / "v1.bin")
        with open(path, "wb") as f:
            f.write(b"REPROTRC" + struct.pack("<II", 1, 8))
            for tau, w in [(0, 0), (2, 1), (1, 0)]:
                f.write(struct.pack("<ii", tau, w))
        taus, who, t_pull, t_push = load_trace(path, return_workers=True, return_times=True)
        np.testing.assert_array_equal(taus, [0, 2, 1])
        np.testing.assert_array_equal(who, [0, 1, 0])
        assert t_pull is None and t_push is None
        # resume-extend: v1 priors are re-stamped at 0.0, new records carry time
        w2 = TraceWriter(path, resume=True)
        assert w2.count == 3
        w2.append(4, 1, t_pull=10.0, t_push=11.5)
        w2.finalize()
        taus, who, t_pull, t_push = load_trace(path, return_workers=True, return_times=True)
        np.testing.assert_array_equal(taus, [0, 2, 1, 4])
        np.testing.assert_array_equal(t_pull, [0.0, 0.0, 0.0, 10.0])
        np.testing.assert_array_equal(t_push, [0.0, 0.0, 0.0, 11.5])

    def test_v2_roundtrip_with_times(self, tmp_path):
        path = str(tmp_path / "v2.bin")
        w = TraceWriter(path)
        w.append(1, 0, t_pull=100.0, t_push=100.25)
        w.append(0, 1, t_pull=100.1, t_push=100.5)
        w.finalize()
        taus, _who, t_pull, t_push = load_trace(path, return_workers=True, return_times=True)
        np.testing.assert_array_equal(taus, [1, 0])
        np.testing.assert_allclose(t_push - t_pull, [0.25, 0.4])

    def test_resume_salvages_partial(self, tmp_path):
        path = str(tmp_path / "t.bin")
        w = TraceWriter(path)
        w.append(5)
        w.abort()
        w2 = TraceWriter(path, resume=True)
        assert w2.count == 1
        w2.append(6)
        w2.finalize()
        np.testing.assert_array_equal(load_trace(path), [5, 6])


# ---------------------------------------------------------------------------
# In-proc transport: ordering + backpressure
# ---------------------------------------------------------------------------


class TestInProcTransport:
    def test_fifo_ordering(self):
        tr = InProcTransport()
        for i in range(50):
            tr.send(("m", i))
        seen = [tr.recv(timeout=1.0)[0][1] for _ in range(50)]
        assert seen == list(range(50))

    def test_rpc_replies_route_to_the_right_endpoint(self):
        tr = InProcTransport()
        stop = threading.Event()

        def echo_server():
            while not stop.is_set():
                item = tr.recv(timeout=0.05)
                if item is None:
                    continue
                msg, reply = item
                reply(("echo", msg[1]))

        t = threading.Thread(target=echo_server, daemon=True)
        t.start()
        endpoints = [tr.worker_endpoint() for _ in range(3)]
        try:
            for round_ in range(5):
                for i, ep in enumerate(endpoints):
                    assert ep.rpc(("ping", (i, round_))) == ("echo", (i, round_))
        finally:
            stop.set()
            t.join(timeout=5)

    def test_backpressure_blocks_at_capacity(self):
        tr = InProcTransport(capacity=2)
        tr.send(("a",))
        tr.send(("b",))
        done = threading.Event()

        def overflow():
            tr.send(("c",))  # must block until the server consumes one
            done.set()

        t = threading.Thread(target=overflow, daemon=True)
        t.start()
        time.sleep(0.2)
        assert not done.is_set(), "third send should block at capacity=2"
        assert tr.recv(timeout=1.0)[0] == ("a",)
        assert done.wait(timeout=5), "send should complete once a slot frees"
        t.join(timeout=5)


# ---------------------------------------------------------------------------
# Staleness stamping
# ---------------------------------------------------------------------------


def _server_for(cfg, pipeline, adapt, trace=None):
    state = init_train_state(jax.random.PRNGKey(0), cfg, pipeline, adapt=adapt)
    tr = InProcTransport()
    server = ParameterServer(state, pipeline, tr, trace=trace)
    server.start()
    return state, tr, server


class TestStalenessStamping:
    def test_scripted_interleaving(self, tiny_cfg, tmp_path):
        """tau == server updates applied between this pull and this push."""
        from repro.async_engine.delayed import flat_size

        path = str(tmp_path / "scripted.bin")
        trace = TraceWriter(path)
        pipeline = _pipeline()
        state, tr, server = _server_for(tiny_cfg, pipeline, _adapt(), trace=trace)
        n = flat_size(state.params)
        g = np.zeros(n, np.float32)
        batch = make_batch_for(tiny_cfg, batch=1, seq=8, seed=0)
        try:
            e0, e1 = tr.worker_endpoint(), tr.worker_endpoint()
            server.submit_batch(batch)
            server.submit_batch(batch)
            w0 = e0.rpc(("pull", 0))
            w1 = e1.rpc(("pull", 1))
            assert w0[0] == "work" and w0[1] == 0  # both read version 0
            assert w1[0] == "work" and w1[1] == 0
            # w0 commits first: no updates since its pull -> tau 0
            assert e0.rpc(("push", 0, w0[1], w0[2], g, 1.0)) == ("ack", 0)
            # w1's snapshot is now one update behind -> tau 1
            assert e1.rpc(("push", 1, w1[1], w1[2], g, 1.0)) == ("ack", 1)
            # a fresh pull after both commits reads version 2, commits at tau 0
            server.submit_batch(batch)
            w0b = e0.rpc(("pull", 0))
            assert w0b[1] == 2
            assert e0.rpc(("push", 0, w0b[1], w0b[2], g, 1.0)) == ("ack", 0)
            server.await_applied(3, timeout=10)
        finally:
            server.request_stop()
            server.shutdown()
            tr.close()
        trace.finalize()
        taus, workers = load_trace(path, return_workers=True)
        np.testing.assert_array_equal(taus, [0, 1, 0])
        np.testing.assert_array_equal(workers, [0, 1, 0])

    def test_w1_matches_serial_oracle(self, tiny_cfg, tmp_path):
        """One live worker == serial SGD: tau identically 0 and the final
        params match a hand-rolled pull/grad/apply loop exactly."""
        path = str(tmp_path / "w1.bin")
        steps = 5
        spec = _spec(tiny_cfg, workers=1, num_steps=steps, trace_path=path)
        res = run(spec)
        np.testing.assert_array_equal(load_trace(path), np.zeros(steps, np.int64))

        # serial oracle: same grad fn, same pipeline semantics, no concurrency
        pipeline = _pipeline(1)
        state = init_train_state(jax.random.PRNGKey(0), tiny_cfg, pipeline, adapt=_adapt())
        grad_fn = make_grad_fn(tiny_cfg)
        tau = jnp.zeros((), jnp.int32)

        @jax.jit
        def apply(state, g_flat):
            adapt = record_taus(state.adapt, tau)
            ctx = T.StepContext(tau=tau, adapt=adapt, staleness_applied=False)
            grads = T.unpack_flat(g_flat, state.params)
            new_params, new_opt = T.run_pipeline(
                pipeline, grads, state.opt_state, state.params, ctx
            )
            return dataclasses.replace(
                state, params=new_params, opt_state=new_opt, step=state.step + 1,
                adapt=adapt,
            )

        for t in range(steps):
            p_flat = np.asarray(T.pack_flat(state.params), np.float32)
            _, g_flat = grad_fn(p_flat, spec.batch_fn(t))
            state = apply(state, jnp.asarray(g_flat))

        for a, b in zip(jax.tree.leaves(res.state.params), jax.tree.leaves(state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# DistributedAsyncEngine through run(...)
# ---------------------------------------------------------------------------


class TestDistributedEngine:
    def test_live_run_with_hooks_and_trace(self, tiny_cfg, tmp_path):
        path = str(tmp_path / "live.bin")
        steps, workers = 10, 4
        bench = BenchHook("live", {"workers": workers})
        spec = _spec(tiny_cfg, workers=workers, num_steps=steps, trace_path=path)
        res = run(spec, hooks=[LogHook(log_every=5, logger=lambda s: None), bench])
        assert res.step == steps
        assert int(np.asarray(res.state.step)) == steps  # finish() drained
        taus, trace_workers = load_trace(path, return_workers=True)
        assert len(taus) == steps
        assert taus.min() >= 0 and taus.max() < steps
        assert int(np.asarray(res.state.adapt.hist).sum()) == steps
        assert all(np.isfinite(r["value"]) for r in bench.rows)
        retrace_rows = [r for r in bench.rows if r["name"].endswith("retraces")]
        assert retrace_rows and retrace_rows[0]["value"] == 1.0  # one compile

    def test_refresh_runs_inside_the_server(self, tiny_cfg):
        spec = _spec(tiny_cfg, workers=2, num_steps=6, refresh_every=3)
        res = run(spec)
        assert res.step == 6
        # the refresh drained the in-jit histogram into the host estimator
        est = T.staleness_link(spec.pipeline).estimator
        assert est.n_seen > 0

    def test_checkpoint_resume_extends_server_state_and_trace(self, tiny_cfg, tmp_path):
        path = str(tmp_path / "resume.bin")
        ckdir = str(tmp_path / "ck")
        spec_a = _spec(tiny_cfg, workers=4, num_steps=4, trace_path=path)
        run(spec_a, hooks=[CheckpointHook(ckdir, every=4)])
        taus_a = load_trace(path)
        assert len(taus_a) == 4  # drained + finalized
        # the checkpoint was taken mid-flight (before the final drain): the
        # saved server version k may lag the tick count
        (ck_file,) = glob.glob(ckdir + "/step_00000004.npz")
        k = int(np.load(ck_file)[".step"])
        assert 1 <= k <= 4

        spec_b = _spec(tiny_cfg, workers=4, num_steps=8, trace_path=path)
        res_b = run(spec_b, resume_from=ckdir)
        assert res_b.start_step == 4 and res_b.step == 8
        # the server resumed from version k and applied the 4 new batches
        assert int(np.asarray(res_b.state.step)) == k + 4
        taus_all = load_trace(path)  # finalized again — never corrupted
        assert len(taus_all) == len(taus_a) + 4
        np.testing.assert_array_equal(taus_all[: len(taus_a)], taus_a)

    def test_failure_aborts_cluster_and_leaves_salvageable_trace(self, tiny_cfg, tmp_path):
        path = str(tmp_path / "crash.bin")

        class Boom(Hook):
            def on_tick(self, ctx):
                if ctx.step == 3:
                    raise RuntimeError("injected failure")

        spec = _spec(tiny_cfg, workers=2, num_steps=8, trace_path=path)
        with pytest.raises(RuntimeError, match="injected failure"):
            run(spec, hooks=[Boom()])
        # no finalized trace — but the partial capture is salvageable
        with pytest.raises(TraceError, match="never finalized"):
            load_trace(path)
        salvaged = load_trace(path, allow_partial=True)
        assert len(salvaged) >= 1

    def test_trace_replay_roundtrip(self, tiny_cfg, tmp_path, workers_mesh):
        """Live capture -> per-worker trace samplers -> sharded replay: the
        measured distribution drives the simulator and the run converges."""
        path = str(tmp_path / "replay.bin")
        steps, workers = 24, 4
        spec = _spec(tiny_cfg, workers=workers, num_steps=steps, trace_path=path)
        losses = _LossesHook()
        run(spec, hooks=[losses])
        taus, who = load_trace(path, return_workers=True)

        # measured-vs-modeled: the Table-I machinery accepts live data
        fits = fit_all_models(taus, m=workers)
        assert all(np.isfinite(d) for _, d in fits.values())

        per_worker = [
            taus[who == w] if np.any(who == w) else taus for w in range(workers)
        ]
        adapt = make_worker_adapt(
            _sched().table[: TAU_MAX + 1],
            [np.asarray(t, np.int64) for t in per_worker],
            cdf_support=RING,
        )
        # One fixed batch: the loss of a fresh random batch each step moves
        # by more than 24 small steps do, so only a fixed one shows training.
        batch = spec.batch_fn(0)
        replay = RunSpec(
            cfg=tiny_cfg,
            pipeline=_pipeline(workers),
            mode="sharded_async",
            num_steps=steps,
            batch_fn=lambda t: batch,
            num_workers=workers,
            ring=RING,
            adapt=adapt,
            mesh=workers_mesh,
            seed=0,
        )
        replay_losses = _LossesHook()
        res = run(replay, hooks=[replay_losses])
        assert res.step == steps
        assert np.isfinite(replay_losses.losses).all()
        # converges: the replayed run trains (loss moves down from init)
        assert replay_losses.losses[-1] < replay_losses.losses[0]


class _LossesHook(Hook):
    def __init__(self):
        self.losses = []

    def on_tick(self, ctx):
        self.losses.append(float(np.asarray(ctx.metrics["loss"])))


@pytest.fixture(scope="module")
def workers_mesh():
    from repro.launch.mesh import make_workers_mesh

    return make_workers_mesh()


# ---------------------------------------------------------------------------
# Socket transport: true multi-process workers on localhost
# ---------------------------------------------------------------------------


class TestSocketTransport:
    def test_socket_run_spawns_real_processes(self, tiny_cfg, tmp_path):
        path = str(tmp_path / "sock.bin")
        spec = _spec(
            tiny_cfg, workers=2, num_steps=3, trace_path=path, transport="socket"
        )
        res = run(spec)
        assert res.step == 3
        assert int(np.asarray(res.state.step)) == 3
        taus = load_trace(path)
        assert len(taus) == 3

    def test_worker_processes_refused_on_accelerator(self, tiny_cfg, monkeypatch):
        """A chip serves one process: on an accelerator backend the socket
        transport refuses to spawn JAX worker processes and names inproc."""
        import jax

        from repro.distributed.transport import SocketTransport

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        transport = SocketTransport()
        try:
            with pytest.raises(RuntimeError, match="inproc"):
                transport.start_worker(0, tiny_cfg)
        finally:
            transport.close()


# ---------------------------------------------------------------------------
# Fault plans: parsing + injector scoping
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_parse_faults_syntax(self):
        plan = parse_faults(
            "crash_before_push:worker=1:after=2,delay_push:seconds=0.2:count=inf"
        )
        a, b = plan.faults
        assert a == FaultSpec("crash_before_push", worker=1, after=2)
        assert b == FaultSpec("delay_push", seconds=0.2, count=None)

    def test_parse_faults_rejects_garbage(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            parse_faults("segfault")
        with pytest.raises(ValueError, match="unknown fault field"):
            parse_faults("delay_push:sec=1")
        with pytest.raises(ValueError, match="not key=value"):
            parse_faults("delay_push:seconds")
        with pytest.raises(ValueError, match="empty fault plan"):
            parse_faults("  ,")

    def test_spec_normalizes_fault_strings(self, tiny_cfg):
        spec = _spec(tiny_cfg, faults="crash_after_push:worker=0")
        assert isinstance(spec.faults, FaultPlan)
        assert spec.faults.faults[0].kind == "crash_after_push"

    def test_injector_scoping_after_count(self):
        plan = FaultPlan(
            (
                FaultSpec("crash_before_push", worker=1, after=1, count=1),
                FaultSpec("slow_apply", after=2, count=None, seconds=0.1),
            )
        )
        # worker 0 never arms worker 1's fault; server scope filters worker kinds
        assert plan.for_worker(0).fire("crash_before_push", 0) is None
        assert plan.for_server().fire("crash_before_push", 1) is None
        inj = plan.for_worker(1)
        assert inj.fire("crash_before_push", 1) is None  # after=1: first passes
        assert inj.fire("crash_before_push", 1) is not None  # second fires
        assert inj.fire("crash_before_push", 1) is None  # count=1 spent
        srv = plan.for_server()
        assert srv.fire("slow_apply", 0) is None
        assert srv.fire("slow_apply", 1) is None
        for w in range(5):  # count=None: every event after the first two
            assert srv.fire("slow_apply", w) is not None


# ---------------------------------------------------------------------------
# Transport API: registry factory, context managers, failure semantics
# ---------------------------------------------------------------------------


class TestTransportAPI:
    def test_factory_and_registry(self):
        assert set(transport_kinds()) >= {"inproc", "socket"}
        with pytest.raises(ValueError, match="unknown transport"):
            make_transport("carrier-pigeon")
        with make_transport("inproc", capacity=4) as tr:
            assert isinstance(tr, InProcTransport)
            assert not tr.closed
        assert tr.closed
        tr.close()  # idempotent: closing a closed fabric is a no-op

    def test_third_transport_is_one_registry_entry(self):
        from repro.distributed.transport import _TRANSPORTS, register_transport

        @register_transport("loopback-test")
        class _Loopback(InProcTransport):
            pass

        try:
            assert "loopback-test" in transport_kinds()
            assert isinstance(make_transport("loopback-test"), _Loopback)
        finally:
            _TRANSPORTS.pop("loopback-test")

    def test_inproc_rpc_raises_eof_when_transport_closes(self):
        tr = make_transport("inproc")
        ep = tr.worker_endpoint()
        tr.close()
        t0 = time.monotonic()
        with pytest.raises(EOFError):
            ep.rpc(("pull", 0), timeout=30.0)
        assert time.monotonic() - t0 < 5.0  # immediate, not the 30s deadline

    def test_inproc_rpc_times_out_without_a_server(self):
        tr = make_transport("inproc")
        with pytest.raises(TimeoutError, match="no reply"):
            tr.worker_endpoint().rpc(("pull", 0), timeout=0.2)
        tr.close()

    def test_socket_endpoint_eof_immediately_on_server_death(self):
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.bind(("127.0.0.1", 0))
        srv.listen()
        try:
            ep = SocketWorkerEndpoint(srv.getsockname(), timeout=30.0)
            conn, _ = srv.accept()
            conn.close()  # the server dies mid-conversation
            t0 = time.monotonic()
            with pytest.raises(EOFError):
                ep.rpc(("pull", 0))
            assert time.monotonic() - t0 < 5.0  # EOF beats the 30s rpc timeout
            ep.close()
            with pytest.raises(EOFError):
                ep.rpc(("pull", 0))  # closed endpoints refuse further rpcs
        finally:
            srv.close()

    def test_server_shutdown_is_idempotent(self, tiny_cfg):
        _state, tr, server = _server_for(tiny_cfg, _pipeline(), _adapt())
        server.shutdown()
        server.shutdown()  # teardown paths can race finish/abort: no-op
        tr.close()
        tr.close()


# ---------------------------------------------------------------------------
# Worker resilience: retry-with-backoff, EOF-means-exit
# ---------------------------------------------------------------------------


class _FlakyEndpoint:
    """Endpoint double whose every rpc raises ``exc``; counts the attempts."""

    def __init__(self, exc):
        self.exc = exc
        self.calls = 0
        self.closed = False

    def rpc(self, msg, timeout=None):
        self.calls += 1
        raise self.exc

    def close(self):
        self.closed = True


@pytest.mark.chaos
class TestWorkerRetry:
    def test_transient_errors_retried_with_backoff_then_clean_exit(self):
        ep = _FlakyEndpoint(TimeoutError("no reply"))
        policy = RetryPolicy(
            rpc_timeout=0.01, max_retries=3, backoff_base=0.01, backoff_max=0.02
        )
        t0 = time.monotonic()
        worker_loop(ep, None, 0, retry=policy)  # grad_fn unused: pull never lands
        assert ep.calls == 1 + policy.max_retries
        assert ep.closed
        # the backoff really slept: 0.01 + 0.02 + 0.02 (doubled, then capped)
        assert time.monotonic() - t0 >= 0.04

    def test_connection_errors_are_transient_too(self):
        ep = _FlakyEndpoint(ConnectionResetError("peer reset"))
        policy = RetryPolicy(max_retries=2, backoff_base=0.0, backoff_max=0.0)
        worker_loop(ep, None, 0, retry=policy)
        assert ep.calls == 3 and ep.closed

    def test_server_gone_exits_without_retry(self):
        ep = _FlakyEndpoint(EOFError("server gone"))
        worker_loop(ep, None, 0, retry=RetryPolicy(max_retries=5))
        assert ep.calls == 1 and ep.closed  # EOF is terminal, never retried


# ---------------------------------------------------------------------------
# Liveness: scripted reclaim, resurrection, server death
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestLivenessAndReclaim:
    def test_reclaimed_inflight_slot_never_deadlocks(self, tiny_cfg):
        """Worker 0 takes a batch and goes silent: the sweep hands its batch
        to worker 1 (no deadlock), and its very-late push resurrects it."""
        from repro.async_engine.delayed import flat_size

        pipeline = _pipeline(2)
        state = init_train_state(jax.random.PRNGKey(0), tiny_cfg, pipeline, adapt=_adapt())
        tr = InProcTransport()
        server = ParameterServer(state, pipeline, tr, worker_timeout=0.3, num_workers=2)
        server.start()
        g = np.zeros(flat_size(state.params), np.float32)
        batch = make_batch_for(tiny_cfg, batch=1, seq=8, seed=0)
        try:
            e0, e1 = tr.worker_endpoint(), tr.worker_endpoint()
            server.submit_batch(batch)
            server.submit_batch(batch)
            w0 = e0.rpc(("pull", 0))  # worker 0 takes work, then goes silent
            w1 = e1.rpc(("pull", 1))
            assert e1.rpc(("push", 1, w1[1], w1[2], g, 1.0))[0] == "ack"
            # only worker 0's stranded batch remains: this pull parks until
            # the liveness sweep reclaims the in-flight slot and re-dispatches
            w1b = e1.rpc(("pull", 1), timeout=10.0)
            assert w1b[0] == "work"
            assert e1.rpc(("push", 1, w1b[1], w1b[2], g, 1.0))[0] == "ack"
            server.await_applied(2, timeout=10)
            live = server.liveness()
            assert live["dead"] == [0] and live["reclaimed"] == 1
            assert live["in_flight"] == [] and live["live_frac"] == 0.5
            # the ghost was merely slow: its late push lands (very stale) and
            # resurrects it
            assert e0.rpc(("push", 0, w0[1], w0[2], g, 1.0)) == ("ack", 2)
            server.await_applied(3, timeout=10)
            live = server.liveness()
            assert live["dead"] == [] and live["live_frac"] == 1.0
        finally:
            server.request_stop()
            server.shutdown()
            tr.close()

    def test_server_death_leaves_salvageable_part(self, tiny_cfg, tmp_path):
        """The server dying mid-capture leaves a ``.part`` with every applied
        record, and workers see EOFError (clean exit), not a timeout hang."""
        from repro.async_engine.delayed import flat_size

        path = str(tmp_path / "dead.bin")
        trace = TraceWriter(path)
        state, tr, server = _server_for(tiny_cfg, _pipeline(), _adapt(), trace=trace)
        g = np.zeros(flat_size(state.params), np.float32)
        batch = make_batch_for(tiny_cfg, batch=1, seq=8, seed=0)
        ep = tr.worker_endpoint()
        server.submit_batch(batch)
        w = ep.rpc(("pull", 0))
        assert ep.rpc(("push", 0, w[1], w[2], g, 1.0))[0] == "ack"
        server.shutdown()  # the server dies: loop gone, fabric closed...
        tr.close()
        trace.abort()  # ...and the capture never finalizes
        with pytest.raises(EOFError):
            ep.rpc(("pull", 0))
        with pytest.raises(TraceError, match="never finalized"):
            load_trace(path)
        assert len(load_trace(path, allow_partial=True)) == 1


# ---------------------------------------------------------------------------
# The chaos matrix: every fault kind through a live run(spec, hooks=...)
# ---------------------------------------------------------------------------

# Short rpc deadlines so a dropped ack re-pushes within the test budget; a
# generous retry count absorbs compile-time stalls on the serial apply loop.
CHAOS_RETRY = RetryPolicy(rpc_timeout=5.0, max_retries=8, backoff_base=0.05, backoff_max=0.5)

CHAOS_FAULTS = {
    "crash_before_push": FaultPlan((FaultSpec("crash_before_push", worker=1, after=1),)),
    "crash_after_push": FaultPlan((FaultSpec("crash_after_push", worker=2, after=1),)),
    "delay_push": FaultPlan(
        (FaultSpec("delay_push", worker=0, after=1, seconds=0.4, count=2),)
    ),
    "drop_reply": FaultPlan((FaultSpec("drop_reply", worker=1, after=1),)),
    "slow_apply": FaultPlan((FaultSpec("slow_apply", after=2, seconds=0.25, count=2),)),
}


@pytest.mark.chaos
class TestChaosMatrix:
    @pytest.mark.parametrize("kind", sorted(CHAOS_FAULTS))
    def test_injected_fault_still_completes_and_finalizes(self, tiny_cfg, tmp_path, kind):
        """Each fault kind, injected into a live W=4 run: the run completes
        (reclaim keeps the pacing deadlock-free), every loss is finite, and
        the trace finalizes with at least one record per submitted batch
        (crash reclaims and push retries may add stale duplicates)."""
        path = str(tmp_path / f"{kind}.bin")
        steps, workers = 8, 4
        spec = _spec(
            tiny_cfg,
            workers=workers,
            num_steps=steps,
            trace_path=path,
            faults=CHAOS_FAULTS[kind],
            worker_timeout=1.5,
            retry=CHAOS_RETRY,
        )
        losses = _LossesHook()
        res = run(spec, hooks=[losses])
        assert res.step == steps
        assert int(np.asarray(res.state.step)) >= steps  # drained (dups allowed)
        assert np.isfinite(losses.losses).all()
        taus, _who, t_pull, t_push = load_trace(path, return_workers=True, return_times=True)
        assert len(taus) >= steps
        assert taus.min() >= 0
        assert t_pull is not None and np.all(t_push - t_pull >= 0)

    def test_crash_plus_stragglers_converges_with_v2_trace(self, tiny_cfg, tmp_path):
        """The ISSUE-9 acceptance run: W=4, one worker crashes before its
        first push AND another straggles — ``run(spec, hooks=...)`` still
        converges and finalizes a v2 trace whose wall-clock stamps are
        monotone per worker."""
        path = str(tmp_path / "accept.bin")
        steps, workers = 20, 4
        faults = FaultPlan(
            (
                FaultSpec("crash_before_push", worker=3),
                FaultSpec("delay_push", worker=1, after=1, seconds=0.3, count=2),
            )
        )
        spec = _spec(
            tiny_cfg,
            workers=workers,
            num_steps=steps,
            trace_path=path,
            faults=faults,
            worker_timeout=1.0,
            retry=CHAOS_RETRY,
        )
        # one fixed batch, so the loss series shows training and not the
        # spread between random batches
        batch = spec.batch_fn(0)
        spec = dataclasses.replace(spec, batch_fn=lambda t: batch)
        losses = _LossesHook()
        snaps = _LivenessHook()
        res = run(spec, hooks=[losses, snaps])
        assert res.step == steps
        assert losses.losses[-1] < losses.losses[0]  # converges through chaos
        # liveness surfaced through the Engine protocol during the run
        assert snaps.snaps and all(s["num_workers"] == workers for s in snaps.snaps)
        taus, who, t_pull, t_push = load_trace(path, return_workers=True, return_times=True)
        # worker 3 crashed before ever pushing: the run completing at all
        # proves its stranded batch was reclaimed for the live workers
        assert 3 not in set(who.tolist())
        assert len(taus) >= steps
        assert np.all(t_push - t_pull >= 0)  # pull precedes push, per record
        assert np.all(np.diff(t_push) >= 0)  # applies are serial: stamp order
        for w in set(who.tolist()):  # per worker, pulls happen in real time
            assert np.all(np.diff(t_pull[who == w]) >= 0)


class _LivenessHook(Hook):
    def __init__(self):
        self.snaps = []

    def on_tick(self, ctx):
        self.snaps.append(ctx.engine.liveness())
