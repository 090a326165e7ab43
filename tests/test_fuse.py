"""Pipeline fusion compiler: plan classification, one-kernel lowering, and
the correctness contract — fused ``make_step(..., fuse=True)`` trajectories
are BIT-IDENTICAL (f32) to the unfused link-by-link pipeline for the
sgd / momentum / adam chain bodies in all three engine modes (clip variants
match to f32 round-off: the global-norm reduction runs flat instead of
leaf-wise).  Pallas interpret-mode kernel-vs-oracle parity runs under the
``pallas`` mark (the CI ``kernels`` leg)."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.staleness import Poisson
from repro.core.step_size import make_schedule
from repro.data import lm_batches
from repro.launch.mesh import make_workers_mesh
from repro.optim import transform as T
from repro.optim.fuse import flat_chain_step, fuse_pipeline, plan_fusion
from repro.training import (
    init_sharded_async_state,
    init_train_state,
    make_adapt,
    make_step,
    make_worker_adapt,
    param_view,
    train_loop,
)


@pytest.fixture(scope="module")
def small_cfg():
    return reduced(get_config("stablelm-1.6b"), d_model=128)


@pytest.fixture(scope="module")
def workers_mesh():
    return make_workers_mesh()


def _sched(tau_max=31, alpha_c=0.05):
    return make_schedule("poisson_momentum", alpha_c, Poisson(4.0), K=alpha_c,
                         tau_max=tau_max)


def _chains(sched, lr=0.05, with_staleness=True):
    prefix = (T.scale_by_staleness(sched, lr),) if with_staleness else ()
    return {
        "sgd": T.chain(*prefix, T.scale(-lr)),
        "momentum": T.chain(*prefix, T.scale(-lr), T.trace(0.9)),
        "adam": T.chain(*prefix, T.scale_by_adam(), T.scale(-lr)),
    }


def _custom_link():
    return T.GradientTransform(
        init=lambda p: (), update=lambda u, s, p, c: (u, s), kind="custom"
    )


class TestPlanFusion:
    def test_classifies_kernel_family(self):
        sched = _sched()
        for kind, pipe in _chains(sched).items():
            plan = plan_fusion(pipe)
            assert plan is not None and plan.kind == kind
            assert plan.staleness is not None
            assert plan.scale == -0.05
        assert plan_fusion(_chains(sched)["momentum"]).mu == 0.9

    def test_fused_apply_terminal_is_momentum_plan(self):
        plan = plan_fusion(T.chain(T.fused_apply(0.05, 0.9)))
        assert plan.kind == "momentum"
        assert plan.scale == -0.05 and plan.mu == 0.9

    def test_clip_and_drop_classify(self):
        sched = _sched()
        pipe = T.chain(
            T.scale_by_staleness(sched, 0.05), T.drop_stale(5),
            T.clip_by_global_norm(0.5), T.scale(-0.05), T.trace(0.9),
        )
        plan = plan_fusion(pipe)
        assert plan.kind == "momentum" and plan.clip == 0.5
        assert plan.drop is not None and plan.drop.tau_drop == 5

    def test_custom_link_is_unfuseable(self):
        assert plan_fusion(T.chain(T.scale(-0.05), _custom_link())) is None

    def test_unsupported_order_is_unfuseable(self):
        # clip AFTER the base scale is not a recognized body
        assert plan_fusion(T.chain(T.scale(-0.05), T.clip_by_global_norm(1.0))) is None

    def test_fused_pipeline_keeps_links_introspectable(self):
        """staleness_link / drop_link must see through the fused chain — the
        train_loop refresh boundary and make_step's absorption depend on it."""
        sched = _sched()
        link = T.scale_by_staleness(sched, 0.05, m=4)
        pipe = T.chain(link, T.drop_stale(7), T.scale(-0.05))
        fused = fuse_pipeline(pipe)
        assert fused.applies_params and fused.kind == "fused_chain"
        assert T.staleness_link(fused) is link
        assert T.drop_link(fused).tau_drop == 7


class TestFusedTrajectoryParity:
    """Acceptance: fuse=True == link-by-link, bitwise, in every engine mode."""

    def _compare(self, cfg, step_u, s_u, step_f, s_f, n=5):
        b1 = lm_batches(cfg.vocab_size, 2, 16, seed=0)
        b2 = lm_batches(cfg.vocab_size, 2, 16, seed=0)
        for t in range(n):
            s_u, m_u = step_u(s_u, next(b1))
            s_f, m_f = step_f(s_f, next(b2))
            # fused all-f32 states are flat-native: unpack through param_view
            # so the leaf-wise comparison sees the same tree on both sides.
            lu = jax.tree.leaves(param_view(s_u, cfg))
            lf = jax.tree.leaves(param_view(s_f, cfg))
            assert len(lu) == len(lf)
            for x, y in zip(lu, lf):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y), err_msg=f"diverged at step {t}"
                )
            assert float(m_u["loss"]) == float(m_f["loss"])
        return s_u, s_f

    @pytest.mark.parametrize("kind", ["sgd", "momentum", "adam"])
    def test_sync_mode_bit_exact(self, small_cfg, kind):
        pipe = _chains(_sched())[kind]
        s_u = init_train_state(jax.random.PRNGKey(0), small_cfg, pipe)
        s_f = init_train_state(jax.random.PRNGKey(0), small_cfg, pipe, fuse=True)
        step_u = jax.jit(make_step(small_cfg, pipe, mode="sync"))
        step_f = jax.jit(make_step(small_cfg, pipe, mode="sync", fuse=True))
        self._compare(small_cfg, step_u, s_u, step_f, s_f, n=4)

    @pytest.mark.parametrize("kind", ["sgd", "momentum", "adam"])
    def test_async_mode_bit_exact(self, small_cfg, kind):
        sched = _sched()
        pipe = _chains(sched)[kind]
        model = Poisson(4.0)
        kwargs = dict(async_ring=8, adapt=make_adapt(model=model, schedule=sched,
                                                     cdf_support=8, tau_max=31))
        s_u = init_train_state(jax.random.PRNGKey(0), small_cfg, pipe, **kwargs)
        s_f = init_train_state(jax.random.PRNGKey(0), small_cfg, pipe, fuse=True, **kwargs)
        step_u = jax.jit(make_step(small_cfg, pipe, mode="async", num_workers=4))
        step_f = jax.jit(make_step(small_cfg, pipe, mode="async", num_workers=4, fuse=True))
        s_u, s_f = self._compare(small_cfg, step_u, s_u, step_f, s_f)
        # flat-resident layout really engaged: one (K, N) f32 ring AND
        # flat-NATIVE params (the packed (N,) buffer IS the train state —
        # no per-step pack/unpack round-trip)
        assert isinstance(s_f.delayed.ring, jax.Array) and s_f.delayed.ring.ndim == 2
        assert s_f.delayed.ring.dtype == jnp.float32
        assert isinstance(s_f.params, jax.Array) and s_f.params.ndim == 1
        np.testing.assert_array_equal(np.asarray(s_u.adapt.hist), np.asarray(s_f.adapt.hist))

    @pytest.mark.parametrize("kind", ["sgd", "momentum", "adam"])
    def test_sharded_mode_bit_exact(self, small_cfg, workers_mesh, kind):
        sched = _sched()
        pipe = _chains(sched)[kind]
        W, ring = 4, 8
        adapt = make_worker_adapt(sched.table[:32], [Poisson(4.0)] * W, cdf_support=ring)
        s_u = init_sharded_async_state(
            jax.random.PRNGKey(0), small_cfg, pipe, ring=ring, adapt=adapt, mesh=workers_mesh
        )
        s_f = init_sharded_async_state(
            jax.random.PRNGKey(0), small_cfg, pipe, ring=ring, adapt=adapt, mesh=workers_mesh,
            fuse=True,
        )
        step_u = jax.jit(make_step(small_cfg, pipe, mode="sharded_async", mesh=workers_mesh))
        step_f = jax.jit(
            make_step(small_cfg, pipe, mode="sharded_async", mesh=workers_mesh, fuse=True)
        )
        s_u, s_f = self._compare(small_cfg, step_u, s_u, step_f, s_f)
        assert isinstance(s_f.delayed.ring, jax.Array) and s_f.delayed.ring.ndim == 3
        assert isinstance(s_f.params, jax.Array) and s_f.params.ndim == 1

    def test_clip_chain_matches_to_rounding(self, small_cfg):
        """The clip variant's norm reduces over the flat buffer instead of
        leaf-wise — same update to f32 round-off, not bitwise (documented)."""
        sched = _sched()
        pipe = T.chain(
            T.scale_by_staleness(sched, 0.05), T.clip_by_global_norm(0.5),
            T.scale(-0.05), T.trace(0.9),
        )
        model = Poisson(4.0)
        adapt = make_adapt(sched, model, cdf_support=8, tau_max=31)
        s_u = init_train_state(
            jax.random.PRNGKey(0), small_cfg, pipe, async_ring=8, adapt=adapt
        )
        s_f = init_train_state(
            jax.random.PRNGKey(0), small_cfg, pipe, async_ring=8, adapt=adapt, fuse=True
        )
        step_u = jax.jit(make_step(small_cfg, pipe, mode="async", num_workers=4))
        step_f = jax.jit(make_step(small_cfg, pipe, mode="async", num_workers=4, fuse=True))
        b1 = lm_batches(small_cfg.vocab_size, 2, 16, seed=0)
        b2 = lm_batches(small_cfg.vocab_size, 2, 16, seed=0)
        for _ in range(5):
            s_u, _ = step_u(s_u, next(b1))
            s_f, _ = step_f(s_f, next(b2))
        lu = jax.tree.leaves(param_view(s_u, small_cfg))
        lf = jax.tree.leaves(param_view(s_f, small_cfg))
        assert len(lu) == len(lf)
        for x, y in zip(lu, lf):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-7)

    def test_fused_refresh_without_retrace(self, small_cfg):
        """The refresh boundary drives the fused pipeline exactly like the
        unfused one (the staleness link is shared), without retracing."""
        sched = _sched()
        link = T.scale_by_staleness(sched, 0.05, m=4, tau_max=31)
        pipe = T.chain(link, T.scale(-0.05))
        adapt = make_adapt(sched, Poisson(4.0), cdf_support=16, tau_max=31)
        state = init_train_state(
            jax.random.PRNGKey(0), small_cfg, pipe, async_ring=16, adapt=adapt, fuse=True
        )
        traces = []
        base = make_step(small_cfg, pipe, mode="async", num_workers=4, fuse=True)

        def counting(s, b):
            traces.append(1)
            return base(s, b)

        state, _ = train_loop(
            jax.jit(counting), state, lm_batches(small_cfg.vocab_size, 2, 16, seed=0),
            num_steps=10, log_every=10, pipeline=pipe, refresh_every=5,
        )
        assert len(traces) == 1, "refresh must not retrace the fused step"
        assert link.estimator.n_seen == 4 * 10
        assert int(np.asarray(state.adapt.hist).sum()) == 0


class TestFallback:
    def test_unfuseable_chain_falls_back_with_single_warning(self, small_cfg):
        bad = T.chain(T.scale(-0.05), _custom_link())
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            step = make_step(small_cfg, bad, mode="sync", fuse=True)
        ours = [w for w in rec if "not fuseable" in str(w.message)]
        assert len(ours) == 1, f"expected exactly one fallback warning, got {len(ours)}"
        # the fallback still trains (link-by-link), with the standard layout
        state = init_train_state(jax.random.PRNGKey(0), small_cfg, bad, fuse=True)
        state, m = jax.jit(step)(
            state, next(lm_batches(small_cfg.vocab_size, 2, 16, seed=0))
        )
        assert bool(jnp.isfinite(m["loss"]))
        # and matches the explicit unfused build bitwise
        s2 = init_train_state(jax.random.PRNGKey(0), small_cfg, bad)
        s2, _ = jax.jit(make_step(small_cfg, bad, mode="sync"))(
            s2, next(lm_batches(small_cfg.vocab_size, 2, 16, seed=0))
        )
        for x, y in zip(jax.tree.leaves(state.params), jax.tree.leaves(s2.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_mismatched_ring_layout_rejected(self, small_cfg):
        """A fused step over a pytree ring (or vice versa) is a layout bug —
        fail fast instead of a cryptic tree-structure error."""
        sched = _sched()
        pipe = T.chain(T.scale_by_staleness(sched, 0.05), T.scale(-0.05))
        adapt = make_adapt(sched, Poisson(4.0), cdf_support=8, tau_max=31)
        state = init_train_state(
            jax.random.PRNGKey(0), small_cfg, pipe, async_ring=8, adapt=adapt
        )
        step = make_step(small_cfg, pipe, mode="async", num_workers=4, fuse=True)
        with pytest.raises(AssertionError, match="ring layout"):
            step(state, next(lm_batches(small_cfg.vocab_size, 2, 16, seed=0)))


@pytest.mark.pallas
class TestFusedChainKernels:
    """Pallas interpret-mode kernel family vs the jnp oracle (CI kernels leg).

    Tolerances are tight-but-not-bitwise: inside the interpreter XLA may
    contract multiply-adds to FMA differently than in the oracle expression.
    """

    def _data(self, n=70001):
        rng = np.random.default_rng(0)
        return [jnp.asarray(rng.standard_normal(n), jnp.float32) for _ in range(4)]

    def _scalars(self, **kw):
        base = {
            "f_stale": jnp.float32(1.3), "f_keep": jnp.float32(1.0),
            "f_clip": jnp.float32(0.7), "m_scale": jnp.float32(-0.05),
        }
        base.update({k: jnp.float32(v) for k, v in kw.items()})
        return base

    def test_sgd_kernel_matches_ref(self):
        from repro.kernels.adaptive_update.fused import fused_chain_call
        from repro.kernels.adaptive_update.ref import fused_chain_ref

        p, g, _, _ = self._data()
        s = self._scalars()
        pk, _ = fused_chain_call("sgd", p, g, (), s, interpret=True)
        pr, _ = fused_chain_ref("sgd", p, g, (), s)
        np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), rtol=1e-6, atol=1e-6)

    def test_momentum_kernel_matches_ref(self):
        from repro.kernels.adaptive_update.fused import fused_chain_call
        from repro.kernels.adaptive_update.ref import fused_chain_ref

        p, g, v, _ = self._data()
        s = self._scalars(mu=0.9)
        pk, (vk,) = fused_chain_call("momentum", p, g, (v,), s, interpret=True)
        pr, vr = fused_chain_ref("momentum", p, g, v, s)
        np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vk), np.asarray(vr), rtol=1e-6, atol=1e-6)

    def test_adam_kernel_matches_ref(self):
        from repro.kernels.adaptive_update.fused import fused_chain_call
        from repro.kernels.adaptive_update.ref import fused_chain_ref

        p, g, m, v = self._data()
        s = self._scalars(b1=0.9, omb1=0.1, b2=0.999, omb2=0.001, eps=1e-8,
                          c1=10.0, c2=1000.0)
        pk, (mk, vk) = fused_chain_call("adam", p, g, (m, v), s, interpret=True)
        pr, mv = fused_chain_ref("adam", p, g, {"m": m, "v": v}, s)
        np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(mk), np.asarray(mv["m"]), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vk), np.asarray(mv["v"]), rtol=1e-6, atol=1e-6)

    def test_flat_tick_equals_unfused_combine_and_chain_bitwise(self):
        """The production CPU lowering of the whole tick (fused_tick_ref: ring
        push + combine + chain body) is bit-identical to the unfused ring ops
        followed by the link-by-link chain — the f32 tick-level contract."""
        from repro.async_engine.delayed import DelayedGradients, delayed_combine
        from repro.kernels.adaptive_update.ref import fused_tick_ref

        rng = np.random.default_rng(3)
        n, K, W = 997, 8, 4
        g = jnp.asarray(rng.standard_normal(n), jnp.float32)
        ring = jnp.asarray(rng.standard_normal((K, n)), jnp.float32)
        step = jnp.int32(11)
        taus = jnp.asarray([0, 2, 5, 2], jnp.int32)  # two workers share a slot
        weights = jnp.asarray(rng.uniform(0.1, 1.0, W), jnp.float32)
        p = jnp.asarray(rng.standard_normal(n), jnp.float32)
        v = jnp.zeros(n, jnp.float32)
        s = {
            "f_stale": jnp.float32(1.0), "f_keep": jnp.float32(1.0),
            "f_clip": jnp.float32(1.0), "m_scale": jnp.float32(-0.05),
            "mu": jnp.float32(0.9),
        }
        g_eff, live_u, new = delayed_combine(
            DelayedGradients(ring=ring, step=step), g, taus, weights
        )
        from repro.kernels.adaptive_update.ref import fused_chain_ref

        p_u, v_u = fused_chain_ref("momentum", p, g_eff, v, s)
        p_f, v_f, ring_f, live_f = fused_tick_ref(
            "momentum", p, g, v, s, ring, step, taus, weights
        )
        np.testing.assert_array_equal(np.asarray(p_u), np.asarray(p_f))
        np.testing.assert_array_equal(np.asarray(v_u), np.asarray(v_f))
        np.testing.assert_array_equal(np.asarray(new.ring), np.asarray(ring_f))
        np.testing.assert_array_equal(np.asarray(live_u), np.asarray(live_f))

    def test_flat_step_equals_unfused_chain_bitwise(self):
        """The production CPU lowering (oracle path) of flat_chain_step is
        bit-identical to the link-by-link chain on packed buffers — the f32
        correctness contract at the kernel-entry level."""
        tree = {
            "a": jnp.asarray(np.random.default_rng(1).standard_normal((37, 5)), jnp.float32),
            "b": jnp.asarray(np.random.default_rng(2).standard_normal(11), jnp.float32),
        }
        grads = jax.tree.map(lambda p: p * 0.1 + 0.01, tree)
        for kind, pipe in _chains(None, with_staleness=False).items():
            fused = fuse_pipeline(pipe)
            p_u, s_u = tree, pipe.init(tree)
            p_f, bufs = T.pack_flat(tree), fused.init(tree)["bufs"]
            for _ in range(4):
                p_u, s_u = T.run_pipeline(pipe, grads, s_u, p_u, T.StepContext())
                p_f, bufs = flat_chain_step(
                    fused.plan, T.pack_flat(grads), bufs, p_f, T.StepContext()
                )
            np.testing.assert_array_equal(
                np.asarray(T.pack_flat(p_u)), np.asarray(p_f), err_msg=kind
            )


@pytest.mark.pallas
class TestOneLaunchTickKernels:
    """The one-launch Pallas tick (ring push + slot-folded combine + chain
    body) vs the exact-composition oracle ``fused_tick_ref`` (CI kernels leg).

    Tolerances are tight-but-not-bitwise: the kernel folds same-slot worker
    weights BEFORE the multiply (one contraction over K) where the oracle
    sums per-worker products — associativity, not math, differs.
    """

    def _tick_data(self, n=70001, K=8, W=4):
        rng = np.random.default_rng(7)
        p = jnp.asarray(rng.standard_normal(n), jnp.float32)
        g = jnp.asarray(rng.standard_normal(n), jnp.float32)
        ring = jnp.asarray(rng.standard_normal((K, n)), jnp.float32)
        step = jnp.int32(11)
        taus = jnp.asarray([0, 2, 5, 2], jnp.int32)  # two workers share a slot
        weights = jnp.asarray(rng.uniform(0.1, 1.0, W), jnp.float32)
        return p, g, ring, step, taus, weights

    def _scalars(self, **kw):
        base = {
            "f_stale": jnp.float32(1.3), "f_keep": jnp.float32(1.0),
            "f_clip": jnp.float32(0.7), "m_scale": jnp.float32(-0.05),
        }
        base.update({k: jnp.float32(v) for k, v in kw.items()})
        return base

    def _check(self, kind, bufs_k, bufs_r, s):
        from repro.kernels.adaptive_update.fused import fused_tick_flat
        from repro.kernels.adaptive_update.ref import fused_tick_ref

        p, g, ring, step, taus, weights = self._tick_data()
        pk, bk, rk, lk = fused_tick_flat(
            kind, p, g, bufs_k, s, ring, step, taus, weights,
            use_pallas=True, interpret=True,
        )
        pr, br, rr, lr = fused_tick_ref(kind, p, g, bufs_r, s, ring, step, taus, weights)
        np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(rk), np.asarray(rr), rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(lk), np.asarray(lr))
        for x, y in zip(jax.tree.leaves(bk), jax.tree.leaves(br)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-6)

    def test_sgd_tick_matches_oracle(self):
        self._check("sgd", (), (), self._scalars())

    def test_momentum_tick_matches_oracle(self):
        v = jnp.zeros(70001, jnp.float32) + 0.3
        self._check("momentum", v, v, self._scalars(mu=0.9))

    def test_adam_tick_matches_oracle(self):
        m = jnp.zeros(70001, jnp.float32) + 0.1
        v = jnp.zeros(70001, jnp.float32) + 0.2
        s = self._scalars(b1=0.9, omb1=0.1, b2=0.999, omb2=0.001, eps=1e-8,
                          c1=10.0, c2=1000.0)
        self._check("adam", {"m": m, "v": v}, {"m": m, "v": v}, s)

    def test_combine_kernel_bf16_ring_and_drop(self):
        """The standalone combine launch (clip / sharded two-launch path):
        bf16 ring storage, and a tau >= K worker must drop dead."""
        from repro.kernels.adaptive_update.fused import fused_combine_flat

        p, g, ring, step, taus, weights = self._tick_data(n=9001)
        ring = ring.astype(jnp.bfloat16)
        taus = jnp.asarray([0, 9, 5, 2], jnp.int32)  # worker 1: tau >= K, dead
        gk, lk, rk = fused_combine_flat(
            g, ring, step, taus, weights, use_pallas=True, interpret=True
        )
        gr, lr, rr = fused_combine_flat(g, ring, step, taus, weights, use_pallas=False)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(lk), np.asarray(lr))
        assert rk.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(rk).view(np.uint16), np.asarray(rr).view(np.uint16)
        )


class TestFlatNativeRuntime:
    """Satellites: ring-dtype configurability and fused-tick buffer donation."""

    def _async_spec(self, small_cfg, **kw):
        from repro.run import RunSpec

        sched = _sched()
        adapt = make_adapt(sched, Poisson(4.0), cdf_support=8, tau_max=31)
        pipe = T.chain(T.scale_by_staleness(sched, 0.05), T.scale(-0.05), T.trace(0.9))
        return RunSpec(
            cfg=small_cfg, pipeline=pipe, mode="async", num_steps=2, ring=8,
            adapt=adapt, num_workers=4, fuse=True, **kw,
        )

    def test_ring_dtype_for(self):
        from repro.async_engine.delayed import ring_dtype_for

        f32tree = {"a": jnp.zeros(3, jnp.float32)}
        mixed = {"a": jnp.zeros(3, jnp.float32), "b": jnp.zeros(3, jnp.bfloat16)}
        assert ring_dtype_for(f32tree) == jnp.float32
        assert ring_dtype_for(mixed) == jnp.bfloat16
        assert ring_dtype_for(f32tree, jnp.bfloat16) == jnp.bfloat16

    def test_ring_dtype_threads_through_init(self, small_cfg):
        sched = _sched()
        adapt = make_adapt(sched, Poisson(4.0), cdf_support=8, tau_max=31)
        pipe = _chains(sched)["momentum"]
        kw = dict(async_ring=8, adapt=adapt, fuse=True)
        st = init_train_state(jax.random.PRNGKey(0), small_cfg, pipe, **kw)
        # all-f32 tree: the ring defaults to the params dtype (no software
        # casts in the combine hot loop)
        assert st.delayed.ring.dtype == jnp.float32
        st_bf = init_train_state(
            jax.random.PRNGKey(0), small_cfg, pipe, ring_dtype=jnp.bfloat16, **kw
        )
        assert st_bf.delayed.ring.dtype == jnp.bfloat16

    def test_ring_dtype_through_runspec_engine(self, small_cfg):
        from repro.run.engine import make_engine

        spec = self._async_spec(small_cfg, ring_dtype=jnp.bfloat16)
        state = make_engine(spec).build()
        assert state.delayed.ring.dtype == jnp.bfloat16

    def test_fused_tick_donates_ring_and_params(self, small_cfg):
        """Regression (satellite): the fused tick must donate its state — the
        previous tick's (K, N) ring and (N,) flat params are consumed in
        place, never copied per step — while the spec's own arrays survive
        for the next run built from the same spec."""
        from repro.run.engine import make_engine

        spec = self._async_spec(small_cfg)
        eng = make_engine(spec)
        state = eng.build()
        ring0, p0 = state.delayed.ring, state.params
        assert p0.ndim == 1  # flat-native engaged
        batches = lm_batches(small_cfg.vocab_size, 2, 16, seed=0)
        with warnings.catch_warnings():
            # a missed donation surfaces as a "donated buffer was not usable"
            warnings.simplefilter("error")
            state2, _ = eng.tick(state, next(batches))
            assert ring0.is_deleted() and p0.is_deleted()
            assert not state2.delayed.ring.is_deleted()
            # spec-held arrays must outlive the donation (engine owns a copy)
            assert not spec.adapt.hist.is_deleted()
            state3, _ = eng.tick(state2, next(batches))
            assert state2.delayed.ring.is_deleted() and state2.params.is_deleted()
        assert eng.retraces == 1

    def test_two_runs_from_one_spec_bit_identical(self, small_cfg):
        """Donation must not poison the spec: run(spec) twice == same result."""
        from repro.run import run

        spec = self._async_spec(small_cfg)
        r1 = run(spec)
        r2 = run(spec)
        np.testing.assert_array_equal(np.asarray(r1.state.params), np.asarray(r2.state.params))
        assert [h["loss"] for h in r1.history] == [h["loss"] for h in r2.history]
