"""The DeepSeek-V2 cell's work counts and the readers of its scopes."""

from __future__ import annotations

import functools
import re
import types

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import ROOT
from test_bench_counts import _dot_flops
from test_mla_moe import TINY_MLA, TRAFFIC


def test_train_flops_match_the_reference_forward():
    """The count against every dot of the reference's forward, one by one.
    The reference scores every (query, key) pair and masks, runs each held
    expert on every token and weights it by a gate that is zero where the
    token chose another, and sums the experts' outputs with one more
    product; the count keeps the causal pairs and the token-choices the
    held experts take at the expected load, so those are added here."""
    from bench import reference
    from bench.cells import module
    from bench.counts.mla_moe_decoder import train_flops
    from bench.data import _make_pool, _make_weights

    c = TINY_MLA
    model = module("models", c["model"])
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: _make_weights(model.weight_shapes(c), k), key)
    pool = jax.eval_shape(lambda k: _make_pool(c, TRAFFIC, k), key)
    batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), pool)
    jaxpr = jax.make_jaxpr(functools.partial(reference.loss, model, c))(params, batch)
    counted = _dot_flops(jaxpr.jaxpr)

    B, T, L, moe_layers = 2, 32, c["num_layers"], c["num_layers"] - c["first_k_dense_replace"]
    d, h, held, fe = c["d_model"], c["num_heads"], c["experts_held"], c["d_ff_expert"]
    per_pair = 2 * h * (c["head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    causal = sum(1 for q in range(T) for k in range(T) if k <= q)
    masked_pairs = B * L * per_pair * (T * T - causal)
    every_token = B * T * held  # rows the dense mask runs
    expected_rows = B * T * c["top_k"] * held / c["num_experts"]
    masked_experts = moe_layers * 2 * 3 * d * fe * (every_token - expected_rows)
    combine = moe_layers * 2 * B * T * held * d
    expected = train_flops(c, B, T) / 3 + masked_pairs + masked_experts + combine
    assert counted == pytest.approx(expected, rel=1e-12)


def test_train_flops_of_the_cell_by_hand():
    """2 x 4096 positions of the cut configuration, written out term by term."""
    import json

    from bench.counts.mla_moe_decoder import train_flops

    c = json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8-5l.json").read_text())
    B, T = 2, 4096
    proj = 2 * (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048)
    attn = 2 * 16 * (192 + 128) * T * (T + 1) // 2
    dense = 2 * 3 * 2048 * 10944
    moe = 2 * (2048 * 64 + 3 * 2048 * 2816)
    routed = 2 * 3 * 2048 * 1408 * (B * T * 6 * 8 // 64)
    head = 2 * 2048 * 12800
    forward = B * (5 * (T * proj + attn) + T * dense + 4 * T * moe + T * head) + 4 * routed
    assert train_flops(c, B, T) == 3 * forward
    assert 15.2e12 < 3 * forward < 15.3e12


def test_expert_cost_by_hand():
    from bench.counts.mla_moe_decoder import expert_cost

    c = dict(TINY_MLA, d_model=4, d_ff_expert=3, experts_held=2, num_experts=4, top_k=2,
             num_layers=2, first_k_dense_replace=1)
    flops, nbytes = expert_cost(c, 1, 8)
    rows = 8 * 2 * 2 / 4  # 8 token-choices of 16 go to the 2 held experts
    assert flops == 3 * 2 * 3 * rows * 4 * 3
    fwd = 2 * (rows * 4 + 2 * 4 * 3 + rows * 3) + (rows * 3 + 2 * 3 * 4 + rows * 4)
    assert nbytes == 2 * 3 * fwd  # the backward moves twice the forward's elements, bf16


def _op(name, start, dur, path):
    return [name, float(start), float(dur)], path


# ops of one tick, paths as the TPU's op_name metadata gives them (a scope
# wrapped by the transpose, a fused op with two paths, a loop holding ops)
SCOPED_OPS = [
    _op("%fusion.1", 0, 100, "jit(train_step)/jvp(forward)/while/body/closed_call/mla/dot_general"),
    _op("%fusion.2", 100, 50, "jit(train_step)/jvp(forward)/while/body/closed_call/moe/router/dot_general"),
    _op("%gmm.3", 150, 200, "jit(train_step)/jvp(forward)/while/body/closed_call/moe/experts/jit(gmm)/pallas_call"),
    _op("%while.4", 350, 300, "jit(train_step)/transpose(jvp(forward))/while"),
    _op("%fusion.5", 360, 80, "jit(train_step)/transpose(jvp(forward))/while/body/transpose(jvp(mla))/dot_general"),
    _op("%fusion.6", 440, 120,
        "jit(train_step)/transpose(jvp(forward))/while/body/moe/experts/mul;jit(train_step)/jvp(forward)/mla/x"),
    _op("%fusion.7", 700, 40, "jit(train_step)/jvp(forward)/moe_x/add"),  # another name
    _op("%fused_tick_call.1", 800, 100, "jit(train_step)/update/jit(fused_tick_call)/pallas_call"),
]


@pytest.fixture()
def scoped_rec():
    from bench import trace as tr
    from bench.cells import Cell

    plain = {
        "window_ns": [0.0, 1000.0],
        "devices": {"0": [op for op, _ in SCOPED_OPS]},
        "device_scopes": {"0": [path for _, path in SCOPED_OPS]},
        "host": [["window", 0.0, 1000.0]],
        "program": [],
    }
    import json

    config = json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8-5l.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / "async-2x4096.json").read_text())
    cell = Cell(name="dsv2lite-async-2x4096", chips=1, config_name="", traffic_name="",
                config=config, model=None, traffic=traffic, limits={}, end_to_end=(), per_layer=())
    return types.SimpleNamespace(reduced=tr.Reduced(plain), ticks=1, cell=cell,
                                 peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def _read(name, rec):
    from bench.cells import module

    return module("metrics", name, ROOT).read(rec)


@pytest.mark.parametrize("path,names,expected", [
    ("jit(f)/jvp(forward)/while/body/mla/dot", ("mla",), True),
    ("jit(f)/transpose(jvp(forward))/while/body/transpose(jvp(mla))/dot", ("mla",), True),
    ("jit(f)/jvp(forward)/moe/experts/pallas_call", ("moe", "experts"), True),
    ("jit(f)/jvp(forward)/moe/while/body/checkpoint/experts/dot", ("moe", "experts"), True),
    ("jit(f)/jvp(forward)/experts/moe/dot", ("moe", "experts"), False),
    ("jit(f)/jvp(forward)/moe_x/add", ("moe",), False),
    ("jit(f)/jvp(forward)/add;jit(f)/jvp(forward)/mla/x", ("mla",), False),  # the first path
    ("", ("mla",), False),
])
def test_under(path, names, expected):
    from bench.scopes import under

    assert under(path, names) is expected


def test_readers_on_a_synthetic_trace(scoped_rec):
    # self times within the "other" class: the loop op keeps 100 ns of its 300
    assert _read("mla_ms", scoped_rec) == pytest.approx(180e-6)
    assert _read("moe_ms", scoped_rec) == pytest.approx(370e-6)
    from bench.counts.mla_moe_decoder import expert_cost

    flops, nbytes = expert_cost(scoped_rec.cell.config, 2, 4096)
    least = max(flops / 197e12, nbytes / 819e9)
    assert _read("expert_roofline", scoped_rec) == pytest.approx(100.0 * least / 320e-9)


def test_readers_return_none_without_the_scopes(scoped_rec):
    from bench import trace as tr

    plain = dict(scoped_rec.reduced.plain)
    plain["device_scopes"] = {"0": ["jit(f)/jvp(forward)/dot"] * len(SCOPED_OPS)}
    rec = types.SimpleNamespace(**{**vars(scoped_rec), "reduced": tr.Reduced(plain)})
    for name in ("mla_ms", "moe_ms", "expert_roofline"):
        assert _read(name, rec) is None
    # a trace loaded without the program's marks
    del plain["device_scopes"]
    rec.reduced = tr.Reduced(plain)
    for name in ("mla_ms", "moe_ms", "expert_roofline"):
        assert _read(name, rec) is None


def test_scopes_reach_the_compiled_tick():
    """The fused async tick of the tiny model, compiled: its ops carry the
    layers' scopes in the forward pass and in its transpose."""
    from bench.cells import model_config
    from repro.launch.train import mindthestep_pipeline
    from repro.training.steps import init_train_state, make_step

    cfg = model_config(TINY_MLA)
    pipeline, adapt = mindthestep_pipeline(0.01, 4, 4, momentum=0.9)
    step = make_step(cfg, pipeline, mode="async", num_workers=4, fuse=True)
    state = jax.eval_shape(
        lambda k: init_train_state(k, cfg, pipeline, async_ring=4, adapt=adapt, fuse=True,
                                   ring_dtype=jnp.bfloat16), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    # the compiled ops' op_name: the lowered text names an op inside a
    # nested call (a dispatch block under lax.map) by the call's own path
    text = jax.jit(step).lower(state, batch).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    from bench.scopes import under

    for names in (("mla",), ("moe", "router"), ("moe", "dispatch"), ("moe", "experts"),
                  ("moe", "combine"), ("moe", "shared")):
        forward = [p for p in paths if under(p, names) and "transpose" not in p]
        backward = [p for p in paths if under(p, names) and "transpose" in p]
        assert forward, names
        if names in (("mla",), ("moe", "experts"), ("moe", "shared")):
            assert backward, names
