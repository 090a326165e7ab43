"""The work counts and the peaks table the per-layer metrics divide by."""

from __future__ import annotations

import functools
import math

import numpy as np
import pytest

from bench_tiny import ROOT, TINY_CONFIG, TINY_VLM


def _subjaxprs(params):
    for value in params.values():
        for s in value if isinstance(value, (list, tuple)) else [value]:
            if hasattr(s, "eqns"):
                yield s
            elif hasattr(getattr(s, "jaxpr", None), "eqns"):
                yield s.jaxpr


def _dot_flops(jaxpr, scale=1) -> float:
    """2 x multiply-adds of every dot_general in a jaxpr, scan bodies times
    their length (an independent count from the computation itself)."""
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            out = eqn.outvars[0].aval.shape
            total += scale * 2 * math.prod(out) * math.prod(lhs[i] for i in lc)
        n = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
        for inner in _subjaxprs(eqn.params):
            total += _dot_flops(inner, scale * n)
    return total


@pytest.mark.parametrize("config", [TINY_CONFIG, TINY_VLM], ids=["dense", "vlm"])
def test_model_flops_match_the_reference_forward(config):
    import jax

    from bench import reference
    from bench.cells import module
    from bench.counts.dense_decoder import train_flops
    from bench.data import _make_pool, _make_weights

    model = module("models", config["model"])
    traffic = {"batch": 2, "positions": 32, "pool": 1, "bigram_follow": 0.8}
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: _make_weights(model.weight_shapes(config), k), key)
    pool = jax.eval_shape(lambda k: _make_pool(config, traffic, k), key)
    batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), pool)
    jaxpr = jax.make_jaxpr(functools.partial(reference.loss, model, config))(params, batch)
    counted = _dot_flops(jaxpr.jaxpr)
    # the reference scores every (query, key) pair and masks; the count
    # keeps the pairs a causal mask keeps, counted here one by one
    T, B, L = 32, 2, config["num_layers"]
    per_pair = 2 * 2 * config["num_heads"] * config["head_dim"]
    causal = sum(1 for q in range(T) for k in range(T) if k <= q)
    expected = train_flops(config, B, T) / 3 + B * L * per_pair * (T * T - causal)
    assert counted == pytest.approx(expected, rel=1e-12)


def test_update_bytes_count_only_the_selected_rows():
    from bench.counts.update_momentum import update_cost

    n = 1000
    a = {"engine": "async", "ring": 4, "ring_dtype": "bfloat16"}
    base = 20 * n + 2 * n  # p, g, v read; p, v written; the fresh row written
    # four draws of one stale slot read that row once
    assert update_cost(a, n, [2, 2, 2, 2], 10)[1] == base + 2 * n
    # draws of 0 use the fresh gradient: no ring row is read
    assert update_cost(a, n, [0, 0, 0, 0], 10)[1] == base
    # three distinct stale slots, never the K = 4 rows of the ring
    assert update_cost(a, n, [1, 2, 3, 3], 10)[1] == base + 3 * 2 * n
    # rows older than the run do not exist and are not read
    assert update_cost(a, n, [1, 2, 3, 3], 1)[1] == base + 2 * n
    f32 = dict(a, ring_dtype="float32")
    assert update_cost(f32, n, [1, 2, 3, 3], 10)[1] == 20 * n + 4 * n * 4
    sync = {"engine": "sync"}
    assert update_cost(sync, n) == (4.0 * n, 20.0 * n)


def test_peaks_by_device_kind():
    from bench.run import peaks

    v5e = peaks("TPU v5 lite", ROOT)
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v99", ROOT)


def test_metrics_read_none_when_nothing_to_read():
    from bench import trace as tr
    from bench.cells import module
    from bench.run import TraceRecord

    plain = {"window_ns": [0.0, 1e9], "devices": {"0": [["fusion.1", 0.0, 5e8]]}, "host": []}
    rec = TraceRecord(cell=None, reduced=tr.Reduced(plain), ticks=10, chips=1,
                      peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
                      flops_per_tick=1e9, update_costs=[(1.0, 1e6)] * 10, refresh_s=[])
    assert module("metrics", "update_roofline", ROOT).read(rec) is None
    assert module("metrics", "refresh_ms", ROOT).read(rec) is None
    assert module("metrics", "idle_share", ROOT).read(rec) == pytest.approx(50.0)
    assert module("metrics", "mfu", ROOT).read(rec) == pytest.approx(1.0)
    assert module("metrics", "fwd_bwd_ms", ROOT).read(rec) == pytest.approx(50.0)
    assert np.isfinite(rec.reduced.window_s)


@pytest.mark.parametrize("seed", [5, 2**31 + 7])
def test_reference_alpha_tables_follow_the_recipe(seed):
    """The reference's alpha(tau), at the start and after a refit from the
    first period's draws, is the table the program's recipe builds."""
    import dataclasses

    import jax.numpy as jnp

    from bench import reference
    from repro.launch.train import mindthestep_pipeline
    from repro.run.ckpt import refresh_link_of
    from repro.training.adapt import host_refresh

    lr, W, K = 0.01, 4, 4
    pipeline, adapt = mindthestep_pipeline(lr, W, K, momentum=0.9)
    start = reference.initial_table(lr, W, K)
    np.testing.assert_allclose(start.astype(np.float32), np.asarray(adapt.alpha_table), rtol=1e-6)
    taus = reference.tick_taus(seed, 10, W, K)
    hist = jnp.asarray(np.bincount(taus.ravel(), minlength=adapt.hist.shape[0]), jnp.int32)
    refit = host_refresh(dataclasses.replace(adapt, hist=hist), refresh_link_of(pipeline), logger=None)
    got = reference.refit_table(lr, taus, K)
    assert not np.allclose(got[:K], start[:K], rtol=1e-3)  # the refit moves the table
    np.testing.assert_allclose(got.astype(np.float32), np.asarray(refit.alpha_table), rtol=1e-6)
