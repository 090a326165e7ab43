"""The model a configuration names, behind the harness's generic parts.

* ``fixtures/dense_decoder_golden.json`` holds what the harness gave for the
  tiny dense decoders below (LayerNorm and RMSNorm, tied and untied heads,
  gated and plain feed-forward, with and without an image prefix) when the
  dense decoder still lived inside ``data.py`` and ``reference.py``: the
  weights' norms for two seeds, the parameter count, the loss and gradient
  norms at the highest precision, under the fp8 control and under the
  half-batch fault, and the reference's followed ticks.  Reached through
  ``models/dense_decoder.py``, every one is reproduced exactly.
* A model module that exists only in a copy of the benchmark is loaded,
  made, packed, measured and followed by the reference with no file of the
  harness changed; a configuration naming a model that has no module is
  refused.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import make_root, tiny_traffic

GOLDEN = Path(__file__).parent / "fixtures" / "dense_decoder_golden.json"
VARIANTS = ("layernorm_tied", "rmsnorm_untied_prefix", "layernorm_untied", "rmsnorm_tied_ungated")
FOLLOWED = [
    ("layernorm_tied", "async-1x512"), ("layernorm_tied", "sync-4x1024"),
    ("rmsnorm_untied_prefix", "async-4x1024"), ("rmsnorm_untied_prefix", "sync-4x1024"),
    ("layernorm_untied", "async-1x512"), ("rmsnorm_tied_ungated", "async-1x512"),
]
FOLLOWED_TICKS = 13


@pytest.fixture(scope="module")
def golden():
    return json.loads(GOLDEN.read_text())


@pytest.fixture(scope="module")
def dense():
    from bench.cells import module

    return module("models", "dense_decoder")


def _floats(x) -> list[float]:
    return [float(v) for v in np.asarray(x, np.float64).ravel()]


def test_the_golden_values_cover_every_variant(golden):
    assert set(golden["variants"]) == set(VARIANTS)
    followed = {(v, t) for v, rec in golden["variants"].items() for t in rec["follow"]}
    assert followed == set(FOLLOWED)


@pytest.mark.parametrize("variant", VARIANTS)
def test_weights_and_count_are_the_recorded_ones(golden, dense, variant):
    from bench import data

    rec = golden["variants"][variant]
    shapes = dense.weight_shapes(rec["config"])
    assert data.param_count(shapes) == rec["param_count"]
    norms = jax.jit(functools.partial(data.leaf_norms, shapes))
    for seed in golden["weight_seeds"]:
        assert _floats(norms(data.make_weights(shapes, seed))) == rec["weight_norms"][str(seed)]


@pytest.mark.parametrize("kind", ["highest", "fp8", "half_batch"])
@pytest.mark.parametrize("variant", VARIANTS)
def test_loss_and_gradient_are_the_recorded_ones(golden, dense, variant, kind):
    from bench import data, reference

    rec = golden["variants"][variant]
    config, seed = rec["config"], golden["seed"]
    shapes = dense.weight_shapes(config)
    batch = data.make_pool(config, tiny_traffic("async-1x512"), seed)[0]
    opts = {"fp8": {"low": "fp8"}, "half_batch": {"fault": "half_batch"}}.get(kind, {})
    grad = jax.jit(jax.value_and_grad(functools.partial(reference.loss, dense, config, **opts)))
    value, g = grad(data.make_weights(shapes, seed), batch)
    norms = jax.jit(functools.partial(data.leaf_norms, shapes))(g)
    assert float(value) == rec["loss"][kind]["loss"]
    assert _floats(norms) == rec["loss"][kind]["grad_norms"]


@pytest.mark.parametrize("variant,traffic", FOLLOWED)
def test_follow_gives_the_recorded_ticks(golden, dense, variant, traffic):
    from bench import data, reference

    rec = golden["variants"][variant]
    config, seed = rec["config"], golden["seed"]
    t = tiny_traffic(traffic)
    pool = data.make_pool(config, t, seed)
    batches = [pool[i % len(pool)] for i in range(FOLLOWED_TICKS)]
    got = reference.follow(dense, config, t, batches, seed=seed)
    assert {k: _floats(v) for k, v in got.items()} == rec["follow"][traffic]


# A one-layer decoder with a dense feed-forward and no attention.  It lives
# only in the checkout's ``bench/models``; nothing of the harness names it.
TOY_MODEL = '''
import jax
import jax.numpy as jnp


def weight_shapes(config):
    d, f, V = config["d_model"], config["d_ff"], config["vocab_size"]
    return {
        "embed/embedding": ((V, d), ("normal", d**-0.5)),
        "stack/pos0/mlp/w_up": ((1, d, f), ("normal", d**-0.5)),
        "stack/pos0/mlp/w_down": ((1, f, d), ("normal", f**-0.5)),
        "final_norm/scale": ((d,), ("ones",)),
    }


def loss(config, mm, params, batch):
    x = params["embed"]["embedding"][batch["tokens"]]
    p = params["stack"]["pos0"]["mlp"]
    x = x + mm("btf,fd->btd", jax.nn.silu(mm("btd,df->btf", x, p["w_up"][0])), p["w_down"][0])
    logits = mm("btd,vd->btv", x * params["final_norm"]["scale"], params["embed"]["embedding"])
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = labels >= 0
    return jnp.sum(jnp.where(mask, lse - ll, 0.0)) / jnp.maximum(jnp.sum(mask), 1)
'''
TOY_CONFIG = {"model": "toy_mlp", "source": "a toy for the CPU tests", "d_model": 16, "d_ff": 32,
              "vocab_size": 64}
SEED = 2**31 + 3


def test_a_model_added_as_files_runs_through_the_harness(tmp_path):
    from bench import data, reference
    from bench.cells import load_cell
    from bench.window import followed_batches

    root = make_root(tmp_path, {"tiny-toy": (TOY_CONFIG, "async-1x512")}, models={"toy_mlp": TOY_MODEL})
    cell = load_cell("tiny-toy", root)
    assert Path(cell.model.__file__) == root / "bench" / "models" / "toy_mlp.py"
    shapes = cell.shapes
    assert data.param_count(shapes) == 64 * 16 + 16 * 32 + 32 * 16 + 16

    params = data.make_weights(shapes, SEED)
    leaves = [params["embed"]["embedding"], params["final_norm"]["scale"],
              params["stack"]["pos0"]["mlp"]["w_down"], params["stack"]["pos0"]["mlp"]["w_up"]]
    # the packing order: sorted paths
    assert all(a is b for a, b in zip(jax.tree.leaves(params), leaves, strict=True))
    flat = jnp.concatenate([x.ravel() for x in leaves])
    assert jax.tree.all(jax.tree.map(jnp.array_equal, data.unflatten(shapes, flat), params))
    expected = [float(np.linalg.norm(np.asarray(x, np.float64))) for x in leaves]
    np.testing.assert_allclose(np.asarray(data.leaf_norms(shapes, params)), expected, rtol=1e-6)
    assert expected[1] == pytest.approx(4.0)  # ones

    batches = followed_batches(cell, data.make_pool(cell.config, cell.traffic, SEED))
    got = reference.follow(cell.model, cell.config, cell.traffic, batches, seed=SEED)
    assert got["losses"].shape == (cell.followed_ticks,) and np.all(np.isfinite(got["losses"]))
    assert got["grad_norms"].shape == got["change_norms"].shape == (4,)
    assert np.all(got["grad_norms"] > 0) and np.all(got["change_norms"] > 0)


def test_a_model_without_a_module_is_refused(tmp_path):
    from bench.cells import load_cell

    root = make_root(tmp_path, {"tiny-none": (dict(TOY_CONFIG, model="no_such_model"), "async-1x512")})
    with pytest.raises(FileNotFoundError, match="no_such_model.py"):
        load_cell("tiny-none", root)
