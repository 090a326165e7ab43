"""Sharded async engine + scenario matrix + bench gate.

The load-bearing guarantees of the workers-mesh-axis design:

* a W-worker sharded step on a 1-device mesh reproduces the single-shard
  ``delayed_apply_batch`` trajectory BIT-exactly (same gathers, same
  contraction, psum degenerates to identity);
* the psum-merged global histogram equals a concatenated per-worker host
  replay of the heterogeneous samplers;
* ``launch/scenarios.py --smoke`` emits schema-valid ``BENCH_scenarios.json``;
* the bench gate passes on itself and fails on a synthetic 25%+ regression.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.async_engine.events import EventSimConfig, simulate_staleness_trace
from repro.bench_schema import bench_row, read_bench_json, validate_rows, write_bench_json
from repro.configs import get_config, reduced
from repro.core.staleness import CMP, Geometric, Poisson
from repro.core.step_size import make_schedule
from repro.data import lm_batches
from repro.launch.mesh import make_workers_mesh
from repro.optim import mindthestep, sgd
from repro.optim import transform as T
from repro.training import (
    init_sharded_async_state,
    init_train_state,
    make_adapt,
    make_async_train_step,
    make_sharded_async_train_step,
    make_step,
    make_worker_adapt,
    merge_worker_hist,
    worker_host_refresh,
)
from repro.training.adapt import sample_worker_taus, worker_sampler_tables


@pytest.fixture(scope="module")
def small_cfg():
    return reduced(get_config("stablelm-1.6b"), d_model=128)


@pytest.fixture(scope="module")
def workers_mesh():
    return make_workers_mesh()


class TestShardedBitMatch:
    """Acceptance: sharded W-worker step == single-shard trajectory, bitwise."""

    def test_sharded_matches_single_shard_trajectory(self, small_cfg, workers_mesh):
        opt = sgd(0.05)
        model = Poisson(4.0)
        W, ring = 4, 8
        sched = make_schedule("poisson_momentum", 0.05, model, K=0.05, tau_max=31)
        adapt1 = make_adapt(sched, model, cdf_support=ring, tau_max=31)
        adapt2 = make_worker_adapt(sched.table[:32], [model] * W, cdf_support=ring)
        # homogeneous workers share the single-shard sampler CDF row-for-row
        np.testing.assert_array_equal(
            np.asarray(adapt1.tau_cdf), np.asarray(adapt2.tau_cdf[0])
        )

        s1 = init_train_state(
            jax.random.PRNGKey(0), small_cfg, opt, async_ring=ring, adapt=adapt1
        )
        s2 = init_sharded_async_state(
            jax.random.PRNGKey(0), small_cfg, opt, ring=ring, adapt=adapt2, mesh=workers_mesh
        )
        step1 = jax.jit(make_async_train_step(small_cfg, opt, alpha_c=0.05, num_workers=W))
        step2 = jax.jit(
            make_sharded_async_train_step(small_cfg, opt, alpha_c=0.05, mesh=workers_mesh)
        )
        batches = lm_batches(small_cfg.vocab_size, 2, 16, seed=0)
        for t in range(8):
            batch = next(batches)
            s1, m1 = step1(s1, batch)
            s2, m2 = step2(s2, batch)
            for l1, l2 in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
                np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
            assert float(m1["loss"]) == float(m2["loss"]), f"loss diverged at step {t}"
        # the per-worker histograms psum-merge to the single-shard histogram
        np.testing.assert_array_equal(
            np.asarray(merge_worker_hist(s2.adapt, workers_mesh)),
            np.asarray(s1.adapt.hist),
        )

    def test_sharded_chain_matches_legacy_factory(self, small_cfg, workers_mesh):
        """API-redesign acceptance, sharded mode: make_step with the
        acceptance chain == make_sharded_async_train_step(sgd), bit-exactly
        (staleness link absorbed into the per-worker combine weights)."""
        W, ring = 4, 8
        model = Poisson(4.0)
        sched = make_schedule("poisson_momentum", 0.05, model, K=0.05, tau_max=31)
        opt = sgd(0.05)
        pipe = T.chain(
            T.scale_by_staleness(sched, 0.05),
            T.clip_by_global_norm(1e9),
            T.scale(-0.05),
        )
        adapt = make_worker_adapt(sched.table[:32], [model] * W, cdf_support=ring)
        s1 = init_sharded_async_state(
            jax.random.PRNGKey(0), small_cfg, opt, ring=ring, adapt=adapt, mesh=workers_mesh
        )
        s2 = init_sharded_async_state(
            jax.random.PRNGKey(0), small_cfg, pipe, ring=ring, adapt=adapt, mesh=workers_mesh
        )
        step1 = jax.jit(
            make_sharded_async_train_step(small_cfg, opt, alpha_c=0.05, mesh=workers_mesh)
        )
        step2 = jax.jit(make_step(small_cfg, pipe, mode="sharded_async", mesh=workers_mesh))
        b1 = lm_batches(small_cfg.vocab_size, 2, 16, seed=0)
        b2 = lm_batches(small_cfg.vocab_size, 2, 16, seed=0)
        for t in range(6):
            s1, m1 = step1(s1, next(b1))
            s2, m2 = step2(s2, next(b2))
            for l1, l2 in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
                np.testing.assert_array_equal(
                    np.asarray(l1), np.asarray(l2), err_msg=f"diverged at step {t}"
                )
            assert float(m1["loss"]) == float(m2["loss"])
        np.testing.assert_array_equal(
            np.asarray(s1.adapt.hist), np.asarray(s2.adapt.hist)
        )

    def test_adam_pipeline_cell_runs_sharded(self, small_cfg, workers_mesh):
        """The optimizer axis the redesign opens: an adam-preconditioned
        pipeline through the sharded engine (the scenarios.py adam cell)."""
        W, ring = 4, 8
        sched = make_schedule("constant", 0.05, tau_max=31)
        pipe = T.chain(
            T.scale_by_staleness(sched, 0.05), T.scale_by_adam(), T.scale(-0.05)
        )
        adapt = make_worker_adapt(sched.table[:32], [Poisson(3.0)] * W, cdf_support=ring)
        state = init_sharded_async_state(
            jax.random.PRNGKey(0), small_cfg, pipe, ring=ring, adapt=adapt, mesh=workers_mesh
        )
        step = jax.jit(make_step(small_cfg, pipe, mode="sharded_async", mesh=workers_mesh))
        batches = lm_batches(small_cfg.vocab_size, 2, 16, seed=0)
        for _ in range(6):
            state, m = step(state, next(batches))
        assert bool(jnp.isfinite(m["loss"]))
        # the adam link's moments advanced inside the compiled sharded step
        assert int(np.asarray(state.opt_state[1]["t"])) == 6

    def test_worker_refresh_no_retrace(self, small_cfg, workers_mesh):
        """worker_host_refresh swaps tables without retracing the sharded step."""
        opt = sgd(0.05)
        W, ring = 4, 8
        sched = make_schedule("constant", 0.05, tau_max=31)
        adapt = make_worker_adapt(sched.table[:32], [Poisson(3.0)] * W, cdf_support=ring)
        mts = mindthestep(opt, sched, 0.05, m=W, tau_max=31)
        state = init_sharded_async_state(
            jax.random.PRNGKey(0), small_cfg, opt, ring=ring, adapt=adapt, mesh=workers_mesh
        )
        traces = []
        base = make_sharded_async_train_step(small_cfg, opt, alpha_c=0.05, mesh=workers_mesh)

        def counting(s, b):
            traces.append(1)
            return base(s, b)

        step = jax.jit(counting)
        batches = lm_batches(small_cfg.vocab_size, 2, 16, seed=0)
        for _ in range(6):
            state, m0 = step(state, next(batches))
        assert len(traces) == 1
        assert float(m0["alpha_mean"]) == pytest.approx(0.05)

        state = dataclasses.replace(
            state, adapt=worker_host_refresh(state.adapt, mts, mesh=workers_mesh, logger=None)
        )
        assert mts.estimator.n_seen == 6 * W, "merged histogram drains into the estimator"
        assert int(np.asarray(state.adapt.hist).sum()) == 0
        state, m1 = step(state, next(batches))
        assert len(traces) == 1, "worker refresh must not retrace the compiled step"
        assert float(m1["alpha_mean"]) != pytest.approx(0.05, rel=1e-4)


def _replay_hist(adapt, rng0, n_steps, bins):
    """Host replay: per-worker tau draws from the same rng chain, concatenated."""
    W = adapt.num_workers
    counts = np.zeros((W, bins), np.int64)
    rng = rng0
    step = 0
    for _ in range(n_steps):
        rng, sub = jax.random.split(rng)
        u = jax.random.uniform(sub, (W,))
        taus = np.asarray(
            sample_worker_taus(
                u, adapt.tau_cdf, adapt.tau_trace, adapt.use_trace, jnp.int32(step)
            )
        )
        for w in range(W):
            counts[w, min(int(taus[w]), bins - 1)] += 1
        step += 1
    return counts


def _run_sharded(small_cfg, mesh, samplers, n_steps=10, ring=8):
    opt = sgd(0.05)
    sched = make_schedule("constant", 0.05, tau_max=31)
    adapt = make_worker_adapt(sched.table[:32], samplers, cdf_support=ring)
    state = init_sharded_async_state(
        jax.random.PRNGKey(1), small_cfg, opt, ring=ring, adapt=adapt, mesh=mesh
    )
    rng0 = state.rng
    step = jax.jit(make_sharded_async_train_step(small_cfg, opt, alpha_c=0.05, mesh=mesh))
    batches = lm_batches(small_cfg.vocab_size, 2, 16, seed=1)
    for _ in range(n_steps):
        state, metrics = step(state, next(batches))
    assert bool(jnp.isfinite(metrics["loss"]))
    return state, adapt, rng0


class TestHeterogeneousSamplers:
    """Per-worker histograms == concatenated host replay, per staleness family."""

    @pytest.mark.staleness_geometric
    def test_geometric_workers(self, small_cfg, workers_mesh):
        samplers = [Geometric(p) for p in (0.2, 0.4, 0.6, 0.8)]
        state, adapt, rng0 = _run_sharded(small_cfg, workers_mesh, samplers)
        want = _replay_hist(state.adapt, rng0, 10, 32)
        np.testing.assert_array_equal(np.asarray(state.adapt.hist), want)
        np.testing.assert_array_equal(
            np.asarray(merge_worker_hist(state.adapt, workers_mesh)), want.sum(axis=0)
        )

    @pytest.mark.staleness_cmp
    def test_cmp_and_poisson_workers(self, small_cfg, workers_mesh):
        samplers = [CMP.from_mode(4, 0.8), CMP.from_mode(4, 1.4), Poisson(2.0), Poisson(6.0)]
        state, adapt, rng0 = _run_sharded(small_cfg, workers_mesh, samplers)
        want = _replay_hist(state.adapt, rng0, 10, 32)
        np.testing.assert_array_equal(np.asarray(state.adapt.hist), want)

    @pytest.mark.staleness_trace
    def test_trace_replay_workers(self, small_cfg, workers_mesh):
        traces = [
            simulate_staleness_trace(EventSimConfig(m=4), num_updates=32, seed=s)
            for s in range(3)
        ]
        samplers = traces + [Poisson(3.0)]  # mixed trace + parametric
        state, adapt, rng0 = _run_sharded(small_cfg, workers_mesh, samplers)
        want = _replay_hist(state.adapt, rng0, 10, 32)
        np.testing.assert_array_equal(np.asarray(state.adapt.hist), want)
        # trace workers really replayed their traces: row w counts == histogram
        # of the first 10 (cyclic) trace entries
        for w, tr in enumerate(traces):
            replayed = np.asarray(tr, np.int64)[np.arange(10) % len(tr)]
            want_row = np.bincount(np.clip(replayed, 0, 31), minlength=32)
            np.testing.assert_array_equal(np.asarray(state.adapt.hist)[w], want_row)

    @pytest.mark.staleness_trace
    def test_sampler_tables_shapes(self):
        trace = np.asarray([1, 2, 3], np.int64)
        cdf, traces, flags = worker_sampler_tables(
            [Geometric(0.5), trace, Poisson(2.0)], support=8
        )
        assert cdf.shape == (3, 8)
        assert traces.shape == (3, 3)
        np.testing.assert_array_equal(flags, [0, 1, 0])
        np.testing.assert_array_equal(traces[1], [1, 2, 3])


class TestScenarioMatrix:
    def test_smoke_matrix_writes_schema_valid_json(self, tmp_path):
        """2 archs x 2 staleness models -> >= 4 cells of schema-valid rows."""
        from repro.launch import scenarios

        out = str(tmp_path / "BENCH_scenarios.json")
        scenarios.main([
            "--smoke", "--steps", "3", "--out", out,
        ])
        rows = read_bench_json(out)  # validates schema
        cells = {r["name"].rsplit("/", 1)[0] for r in rows}
        assert len(cells) >= 4
        archs = {c.split("/")[1] for c in cells}
        models = {c.split("/")[2] for c in cells}
        assert len(archs) == 2 and len(models) == 2
        for cell in cells:
            names = {r["name"] for r in rows}
            assert {f"{cell}/final_loss", f"{cell}/wall_s", f"{cell}/retraces"} <= names
        for r in rows:
            if r["name"].endswith("/retraces"):
                assert r["value"] == 1.0, f"{r['name']}: online step must compile once"
            if r["name"].endswith("/final_loss"):
                assert np.isfinite(r["value"])
                assert len(r["meta"]["losses"]) == 3  # loss-vs-updates series

    def test_cell_rows_reject_bad_schema(self):
        with pytest.raises(ValueError):
            validate_rows([{"name": "x", "unit": "s", "config": "abc"}])  # no value
        with pytest.raises(ValueError):
            validate_rows([
                bench_row("dup", 1.0, "s", {}),
                bench_row("dup", 2.0, "s", {}),
            ])


class TestBenchGate:
    def _write(self, path, value, *, gate="higher", tol=0.25, config=None):
        write_bench_json(
            str(path),
            [bench_row("kernels/k/speedup", value, "x", config or {"k": 1}, gate=gate, tol=tol)],
        )

    def test_gate_passes_within_band(self, tmp_path):
        from benchmarks import bench_gate

        (tmp_path / "base").mkdir()
        (tmp_path / "cur").mkdir()
        self._write(tmp_path / "base" / "BENCH_kernels.json", 8.0)
        self._write(tmp_path / "cur" / "BENCH_kernels.json", 7.0)  # -12.5% < 25%
        bench_gate.main([
            "--current", str(tmp_path / "cur"), "--baselines", str(tmp_path / "base"),
        ])

    def test_gate_fails_on_25pct_regression(self, tmp_path):
        """Acceptance: a synthetic >25% regression must fail the gate."""
        from benchmarks import bench_gate

        (tmp_path / "base").mkdir()
        (tmp_path / "cur").mkdir()
        self._write(tmp_path / "base" / "BENCH_kernels.json", 8.0)
        self._write(tmp_path / "cur" / "BENCH_kernels.json", 5.9)  # -26%
        with pytest.raises(SystemExit, match="regress"):
            bench_gate.main([
                "--current", str(tmp_path / "cur"), "--baselines", str(tmp_path / "base"),
            ])

    def test_gate_fails_on_wallclock_regression(self, tmp_path):
        from benchmarks import bench_gate

        (tmp_path / "base").mkdir()
        (tmp_path / "cur").mkdir()
        self._write(tmp_path / "base" / "BENCH_smoke.json", 10.0, gate="lower")
        self._write(tmp_path / "cur" / "BENCH_smoke.json", 13.0, gate="lower")  # +30%
        with pytest.raises(SystemExit, match="regress"):
            bench_gate.main([
                "--current", str(tmp_path / "cur"), "--baselines", str(tmp_path / "base"),
            ])

    def test_gate_fails_on_missing_current(self, tmp_path):
        from benchmarks import bench_gate

        (tmp_path / "base").mkdir()
        (tmp_path / "cur").mkdir()
        self._write(tmp_path / "base" / "BENCH_kernels.json", 8.0)
        with pytest.raises(SystemExit, match="not produced"):
            bench_gate.main([
                "--current", str(tmp_path / "cur"), "--baselines", str(tmp_path / "base"),
            ])

    def test_gate_skips_on_config_change(self, tmp_path):
        from benchmarks import bench_gate

        (tmp_path / "base").mkdir()
        (tmp_path / "cur").mkdir()
        self._write(tmp_path / "base" / "BENCH_kernels.json", 8.0, config={"k": 1})
        self._write(tmp_path / "cur" / "BENCH_kernels.json", 1.0, config={"k": 2})
        # changed config -> incomparable -> skip, not a spurious failure
        bench_gate.main([
            "--current", str(tmp_path / "cur"), "--baselines", str(tmp_path / "base"),
        ])

    def test_committed_baselines_are_schema_valid(self):
        import glob
        import os

        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        files = glob.glob(os.path.join(here, "benchmarks", "baselines", "BENCH_*.json"))
        assert files, "benchmarks/baselines/ must ship blessed BENCH_*.json seeds"
        gated = 0
        for f in files:
            rows = read_bench_json(f)
            gated += sum(1 for r in rows if (r.get("meta") or {}).get("gate"))
        assert gated > 0, "at least one baseline row must be regression-gated"


class TestMultiDeviceWorkers:
    @pytest.mark.slow
    def test_two_device_workers_mesh_matches_single(self):
        """W=4 workers split 2x2 over a 2-device workers mesh must reproduce
        the 1-device trajectory (the psum merge is shard-count invariant)."""
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
        env["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=2 " + env.get("XLA_FLAGS", "")
        )
        script = """
import jax, numpy as np
import jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.core.staleness import Geometric, Poisson
from repro.core.step_size import make_schedule
from repro.data import lm_batches
from repro.launch.mesh import make_workers_mesh
from repro.optim import sgd
from repro.training import (init_sharded_async_state, make_sharded_async_train_step,
                            make_worker_adapt, merge_worker_hist)

assert jax.device_count() == 2, jax.devices()
cfg = reduced(get_config("stablelm-1.6b"), d_model=128)
opt = sgd(0.05)
sched = make_schedule("constant", 0.05, tau_max=31)
samplers = [Geometric(0.3), Geometric(0.6), Poisson(2.0), Poisson(5.0)]


def run(mesh):
    adapt = make_worker_adapt(sched.table[:32], samplers, cdf_support=8)
    state = init_sharded_async_state(
        jax.random.PRNGKey(0), cfg, opt, ring=8, adapt=adapt, mesh=mesh
    )
    step = jax.jit(make_sharded_async_train_step(cfg, opt, alpha_c=0.05, mesh=mesh))
    batches = lm_batches(cfg.vocab_size, 2, 16, seed=0)
    for _ in range(6):
        state, metrics = step(state, next(batches))
    return state, metrics


s2, m2 = run(make_workers_mesh(2))
s1, m1 = run(make_workers_mesh(1))
for l1, l2 in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-6, atol=1e-7)
np.testing.assert_array_equal(
    np.asarray(merge_worker_hist(s1.adapt, make_workers_mesh(1))),
    np.asarray(merge_worker_hist(s2.adapt, make_workers_mesh(2))),
)
print("OK 2-device == 1-device")
"""
        proc = subprocess.run(
            [sys.executable, "-c", script],
            env=env, cwd=repo, capture_output=True, text=True, timeout=560,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "OK 2-device == 1-device" in proc.stdout


class TestServeJson:
    def test_serve_json_rows(self, tmp_path):
        """launch.serve --json writes schema-valid timing rows."""
        import subprocess
        import sys
        import os

        out = str(tmp_path / "BENCH_serve.json")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", "--arch", "stablelm-1.6b",
             "--reduced", "--batch", "1", "--prompt_len", "8", "--gen", "2",
             "--json", out],
            env=env, cwd=repo, capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        rows = read_bench_json(out)
        names = {r["name"] for r in rows}
        assert {"serve/stablelm-1.6b/prefill_s", "serve/stablelm-1.6b/decode_s",
                "serve/stablelm-1.6b/tok_per_s"} <= names
