"""The program's DeepSeek-V2 layers against the plain reference
``models/mla_moe_decoder.py``, at tiny widths on the CPU.

Latent attention alone, one chip's share of the expert layer alone (and the
shares of all chips together against the uncut layer), the whole model's
loss and per-weight gradients through the fused flat-param view, and the
cell's model through the whole harness.  Activations are float32 and every
matrix product runs at the highest precision, so program and reference
differ only by the order of their float32 sums.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import ROOT, make_root

# deepseek-v2-lite's structure at widths a CPU test can run: one leading
# dense layer and two expert layers; 16 routed experts, 2 held a chip, top-4
TINY_MLA = {
    "arch": "deepseek-v2-lite", "source": "tiny widths for the CPU tests",
    "model": "mla_moe_decoder", "count": "mla_moe_decoder",
    "num_layers": 3, "first_k_dense_replace": 1, "d_model": 64, "num_heads": 4,
    "num_kv_heads": 4, "head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "kv_lora_rank": 32, "d_ff": 128, "vocab_size": 256, "num_experts": 16,
    "experts_held": 2, "expert_offset": 0, "top_k": 4, "d_ff_expert": 32,
    "shared_expert_ff": 64, "shared_expert_gate": False, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "router_aux_coef": 0.001, "norm_eps": 1e-06,
    "rope_theta": 10000, "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096, "type": "yarn"},
    "block_pattern": ["global"], "norm_type": "rmsnorm", "act": "silu", "gated_mlp": True,
    "tie_embeddings": False, "num_prefix_embeddings": 0,
    "activation_dtype": "float32", "param_dtype": "float32",
    "attn_block_q": 16, "attn_block_k": 16,
}
TRAFFIC = {"batch": 2, "positions": 32, "pool": 1, "bigram_follow": 0.8}
SEEDS = (3, 2**31 + 11)
# float32 sums in another order (online softmax over key blocks, grouped
# against masked experts, a packed gradient): a few ulps of each value,
# grown by the layers' depth; 1e-4 is far below what an error of the
# mathematics (a dropped term, a wrong scale, a wrong expert) moves them
RTOL = 1e-4


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    from bench.cells import module

    return module("models", "mla_moe_decoder")


def _cfg(config):
    from bench.cells import model_config

    return model_config(config)


def _weights(model, config, seed):
    from bench.data import make_weights

    return make_weights(model.weight_shapes(config), seed)


def _batch(config, seed):
    from bench.data import make_pool

    return make_pool(config, TRAFFIC, seed)[0]


def _layer(tree, stack, i):
    return jax.tree.map(lambda x: x[i], tree["stack"][stack])


def _hidden(config, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, 32, config["d_model"]), jnp.float32)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _io(x):
    from repro.models.layers import LayerIO

    B, S, _ = x.shape
    return LayerIO(positions=jnp.broadcast_to(jnp.arange(S)[None], (B, S)), causal=True)


@pytest.mark.parametrize("seed", SEEDS)
def test_latent_attention_matches_the_reference(model, seed):
    from repro.models import attention as A

    w = _weights(model, TINY_MLA, seed)
    x = _hidden(TINY_MLA, seed)
    p = _layer(w, "pos0", 1)["attn"]
    got = A.mla_layer(p, x, _io(x), _cfg(TINY_MLA))
    rope = model._yarn(TINY_MLA, x.shape[1])
    mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    want = model._attention(TINY_MLA, mm, p, x, rope)
    assert _rel(got, want) < RTOL


def _program_moe(p, x, config):
    from repro.models import moe as MOE

    return MOE.apply_held_moe(p, x, _cfg(config))


def _reference_moe(model, p, x, config):
    mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    return model._experts(config, mm, p, x)


@pytest.mark.parametrize("offset", [0, 6, 14])
def test_held_expert_layer_matches_the_reference(model, offset):
    config = dict(TINY_MLA, expert_offset=offset)
    w = _weights(model, config, SEEDS[0])
    x = _hidden(config, 5)
    p = _layer(w, "pos0", 0)["moe"]
    assert p["w_gate_e"].shape[0] == config["experts_held"]  # only the held experts
    assert p["router"].shape[-1] == config["num_experts"]  # routing over all of them
    out, aux, counts = _program_moe(p, x, config)
    want, want_aux = _reference_moe(model, p, x, config)
    assert _rel(out, want) < RTOL
    assert float(aux) == pytest.approx(float(want_aux), rel=RTOL)
    # top-4 of all 16 experts: the held ones' share of the 2 x 32 x 4 choices
    probs = jax.nn.softmax(jnp.einsum("btd,de->bte", x, p["router"]), axis=-1)
    top = np.asarray(jax.lax.top_k(probs, 4)[1]).ravel()
    held = (top >= offset) & (top < offset + 2)
    assert int(counts["moe_held_choices"]) == int(held.sum())
    loads = np.bincount(top[held] - offset, minlength=2)
    assert int(counts["moe_max_expert_load"]) == int(loads.max())


def test_dispatch_in_blocks_is_the_layer(model, monkeypatch):
    """The held experts run at most ``DISPATCH_TOKENS`` tokens a block (each
    recomputed in the backward pass): at 16 tokens a block in rows of 8,
    the 64 tokens give the layer, its counters and its gradients of one
    block."""
    from repro.models import moe as MOE

    w = _weights(model, TINY_MLA, SEEDS[1])
    x = _hidden(TINY_MLA, 13)
    p = _layer(w, "pos0", 1)["moe"]

    def run(p, x):
        out, aux, counts = _program_moe(p, x, TINY_MLA)
        return jnp.sum(out**2) + aux, counts

    (whole, whole_counts), whole_g = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(p, x)
    monkeypatch.setattr(MOE, "DISPATCH_TOKENS", 16)
    monkeypatch.setattr(MOE, "ROW_TILE", 8)
    (blocks, counts), g = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(p, x)
    assert float(blocks) == pytest.approx(float(whole), rel=RTOL)
    assert {k: int(v) for k, v in counts.items()} == {k: int(v) for k, v in whole_counts.items()}
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(whole_g)):
        assert _rel(a, b) < RTOL


def test_tokens_that_fill_no_whole_block(model, monkeypatch):
    """2 x 23 tokens at most 16 a block in rows of 8: three blocks of 16,
    the last filled out with two rows that choose no held expert; the layer
    and its counters are the reference's, every choice counted once."""
    from repro.models import moe as MOE

    monkeypatch.setattr(MOE, "DISPATCH_TOKENS", 16)
    monkeypatch.setattr(MOE, "ROW_TILE", 8)
    w = _weights(model, TINY_MLA, SEEDS[0])
    x = _hidden(TINY_MLA, 17)[:, :23]
    p = _layer(w, "pos0", 1)["moe"]
    out, aux, counts = _program_moe(p, x, TINY_MLA)
    want, want_aux = _reference_moe(model, p, x, TINY_MLA)
    assert _rel(out, want) < RTOL
    assert float(aux) == pytest.approx(float(want_aux), rel=RTOL)
    probs = jax.nn.softmax(jnp.einsum("btd,de->bte", x, p["router"]), axis=-1)
    top = np.asarray(jax.lax.top_k(probs, 4)[1]).ravel()
    assert int(counts["moe_held_choices"]) == int((top < 2).sum())


def test_gates_are_not_renormalised(model):
    """``norm_topk_prob`` false keeps each chosen expert's softmax probability
    (published); true divides by the top-k's sum (qwen)."""
    w = _weights(model, TINY_MLA, SEEDS[0])
    x = _hidden(TINY_MLA, 7)
    p = _layer(w, "pos0", 0)["moe"]
    p = {k: v for k, v in p.items() if k != "shared"}
    plain, _, _ = _program_moe(p, x, TINY_MLA)
    renorm, _, _ = _program_moe(p, x, dict(TINY_MLA, norm_topk_prob=True))
    probs = jax.nn.softmax(jnp.einsum("btd,de->bte", x, p["router"]), axis=-1)
    top_sum = jnp.sum(jax.lax.top_k(probs, 4)[0], axis=-1, keepdims=True)
    assert _rel(renorm, plain / top_sum) < RTOL
    assert float(jnp.max(top_sum)) < 0.9  # so the two differ


def test_shares_add_up_to_the_uncut_layer(model):
    """The routed parts of the 8 chips' shares (2 of 16 experts each), plus
    the shared experts counted once, are the uncut layer (all 16 held)."""
    whole = dict(TINY_MLA, experts_held=16)
    w = _weights(model, whole, SEEDS[1])
    x = _hidden(whole, 9)
    p = _layer(w, "pos0", 1)["moe"]
    uncut, uncut_aux, counts = _program_moe(p, x, whole)
    total = jnp.zeros_like(uncut)
    for share in range(8):
        lo = 2 * share
        part = dict(p, **{k: p[k][lo:lo + 2] for k in ("w_gate_e", "w_up_e", "w_down_e")})
        del part["shared"]
        routed, aux, _ = _program_moe(part, x, dict(TINY_MLA, expert_offset=lo))
        assert float(aux) == pytest.approx(float(uncut_aux), rel=1e-6)  # alike on every chip
        total = total + routed
    from repro.models.moe import _shared

    total = total + _shared(p["shared"], x, _cfg(whole))
    assert _rel(total, uncut) < RTOL
    assert int(counts["moe_held_choices"]) == 2 * 32 * 4  # every choice, none dropped
    want, _ = _reference_moe(model, p, x, whole)
    assert _rel(uncut, want) < RTOL


def test_no_token_is_dropped_when_every_token_picks_the_held_experts(model):
    """A router biased so that every token's top-4 are experts 0-3: the two
    held experts each take all 64 tokens, far above a capacity factor's
    share, and the layer still equals the reference."""
    w = _weights(model, TINY_MLA, SEEDS[0])
    x = _hidden(TINY_MLA, 11).at[..., 0].set(10.0)
    p = _layer(w, "pos0", 0)["moe"]
    p = dict(p, router=p["router"].at[0, :4].set(10.0))
    out, _, counts = _program_moe(p, x, TINY_MLA)
    assert int(counts["moe_held_choices"]) == 2 * 64
    assert int(counts["moe_max_expert_load"]) == 64
    want, _ = _reference_moe(model, p, x, TINY_MLA)
    assert _rel(out, want) < RTOL


@pytest.mark.parametrize("seed", SEEDS)
def test_model_loss_and_gradients_through_the_flat_view(model, seed):
    """The loss and every weight's gradient, the program's taken through the
    flat-param view the fused tick trains through (the packed buffer in,
    the packed gradient out)."""
    from bench import reference
    from bench.data import unflatten
    from repro.models import model as M
    from repro.optim import transform as T
    from repro.training.steps import init_params

    cfg = _cfg(TINY_MLA)
    shapes = model.weight_shapes(TINY_MLA)
    w = _weights(model, TINY_MLA, seed)
    batch = _batch(TINY_MLA, seed)
    template = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))

    def flat_loss(pf):
        loss, metrics = M.loss_fn(T.flat_view(pf, template), batch, cfg)
        return loss, metrics

    (loss, metrics), g = jax.value_and_grad(flat_loss, has_aux=True)(T.pack_flat(w))
    want, want_g = jax.value_and_grad(functools.partial(reference.loss, model, TINY_MLA))(w, batch)
    assert float(loss) == pytest.approx(float(want), rel=RTOL)
    assert int(metrics["moe_held_choices"]) > 0
    got_g = unflatten(shapes, g)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got_g)[0], jax.tree.leaves(want_g)):
        assert _rel(a, b) < RTOL, jax.tree_util.keystr(path)


def _tree_shapes(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(k.key) for k in path): tuple(v.shape) for path, v in flat}


@pytest.mark.parametrize("config", ["tiny", "cut"])
def test_program_tree_is_the_reference_weight_tree(model, config):
    """Same paths and shapes; the cut configuration holds 535,060,992
    parameters: the dense layer 81,007,104, each expert layer 100,405,760,
    embedding, head and final norm 52,430,848."""
    from bench.data import param_count
    from repro.training.steps import init_params

    if config == "cut":
        config = json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8-5l.json").read_text())
    else:
        config = TINY_MLA
    cfg = _cfg(config)
    template = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    shapes = model.weight_shapes(config)
    assert _tree_shapes(template) == {k: tuple(s) for k, (s, _) in shapes.items()}
    if config is not TINY_MLA:
        assert param_count(shapes) == 535_060_992 == 81_007_104 + 4 * 100_405_760 + 52_430_848
        assert cfg.param_count() == 535_060_992


def test_published_sizes():
    from repro.configs import get_config

    cfg = get_config("deepseek-v2-lite")
    assert cfg.param_count() == 15_706_484_224
    # top-6 routed experts and the shared ones are active, of 64 a layer
    per_expert = 3 * 2048 * 1408
    assert cfg.active_param_count() == 15_706_484_224 - 26 * 58 * per_expert


def test_the_program_rope_is_the_configuration_files():
    """The registered architecture's YaRN is the file's ``rope_scaling``,
    which the reference reads."""
    from repro.configs import get_config

    config = json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8-5l.json").read_text())
    yarn = get_config(config["arch"]).yarn
    rs = config["rope_scaling"]
    assert rs["type"] == "yarn"
    assert dataclasses.asdict(yarn) == {k: v for k, v in rs.items() if k != "type"}


def test_decode_paths_refuse_latent_attention():
    from repro.configs import get_config, reduced
    from repro.models import model as M

    cfg = reduced(get_config("deepseek-v2-lite"), d_model=64)
    with pytest.raises(NotImplementedError, match="full-sequence"):
        M.init_decode_state(None, cfg, 1, 8)


@pytest.mark.pallas
def test_grouped_matmul_kernel_matches_ragged_dot():
    """The Pallas grouped matmul the TPU path takes (interpret mode here),
    with the absent experts' rows as a last group it never visits, against
    ``lax.ragged_dot``; the absent rows come out zero."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k, n, held = 256, 128, 256, 3
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (held, k, n), jnp.float32)
    sizes = jnp.asarray([40, 0, 100, 116], jnp.int32)
    got = gmm(x, w, sizes, jnp.float32, (128, 128, 128), interpret=True)
    want = jax.lax.ragged_dot(x, w, sizes[:-1], precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)
    assert not np.any(np.asarray(got)[140:])


def test_the_cell_runs_through_the_harness(tmp_path, monkeypatch):
    """The tiny model, a copy of the cell's traffic at 2 x 32 positions,
    through set-up, the window and the check against the reference."""
    import repro.compile_cache
    from bench.cells import load_cell
    from bench.run import run_cell

    monkeypatch.setattr(repro.compile_cache, "use_compile_cache", lambda: "off")
    root = make_root(tmp_path, {"tiny-mla": (TINY_MLA, "async-2x4096")})
    line = run_cell(load_cell("tiny-mla", root), seed=2**31 + 5, seconds=1.0, trace=False,
                    root=root, t_start=time.perf_counter())
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["retraces_in_window"] == {"value": 0, "limit": 0}
    assert line["attempted"] >= 10 and line["failed"] == 0
