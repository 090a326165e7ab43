"""Sharding rules + dry-run machinery (host-side; no fake devices needed).

The actual multi-device lower/compile is exercised by the subprocess test in
``test_dryrun_small.py`` — here we validate the spec assignment logic against
abstract meshes.
"""

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.analysis import model_flops, parse_collective_bytes, roofline_terms
from repro.sharding.specs import auto_spec_for, param_spec_for


class FakeMesh:
    """Duck-typed mesh: axis names + shape only (no devices)."""

    def __init__(self, shape, axes):
        self.axis_names = axes
        self.devices = np.zeros(shape)
        self._shape = dict(zip(axes, shape))

    @property
    def shape(self):
        return self._shape


MESH = FakeMesh((16, 16), ("data", "model"))
MESH3 = FakeMesh((2, 16, 16), ("pod", "data", "model"))


@pytest.mark.parametrize("build", ["make_workers_mesh", "make_small_mesh"])
def test_mesh_axes_are_auto(build):
    """The sharding constraints and the workers shard_map need Auto axes;
    jax.make_mesh defaults to Explicit ones."""
    from jax.sharding import AxisType

    from repro.launch import mesh as M

    mesh = M.make_workers_mesh() if build == "make_workers_mesh" else M.make_small_mesh(1, 1)
    assert mesh.axis_types == (AxisType.Auto,) * len(mesh.axis_names)


class TestParamRules:
    def test_attention_heads_over_model(self):
        spec = param_spec_for("stack/pos0/attn/wq", (2, 4608, 32, 128), MESH)
        assert spec == P(None, ("data",), "model", None)

    def test_wo_transposed(self):
        spec = param_spec_for("stack/pos0/attn/wo", (32, 128, 4608), MESH)
        assert spec == P("model", None, ("data",))

    def test_expert_stack_over_model(self):
        # gate/up shard d_ff over data (weights-stationary decode layout)
        spec = param_spec_for("stack/pos0/moe/w_up_e", (64, 2048, 1408), MESH)
        assert spec == P("model", None, ("data",))
        spec = param_spec_for("stack/pos0/moe/w_down_e", (64, 1408, 2048), MESH)
        assert spec == P("model", ("data",), None)

    def test_embedding_vocab_over_model(self):
        spec = param_spec_for("embed/embedding", (256000, 4608), MESH)
        assert spec == P("model", ("data",))

    def test_norms_replicated(self):
        assert param_spec_for("final_norm/scale", (4608,), MESH) == P()

    def test_indivisible_axis_falls_back(self):
        # 30 heads % 16 != 0 -> heads axis unsharded
        spec = param_spec_for("attn/wq", (4608, 30, 128), MESH)
        assert spec == P(("data",), None, None)

    def test_pod_axis_joins_data(self):
        spec = param_spec_for("attn/wq", (2, 4608, 32, 128), MESH3)
        assert spec == P(None, ("pod", "data"), "model", None)

    def test_mamba_inner_over_model(self):
        assert param_spec_for("ssm/in_proj", (4096, 16384), MESH) == P(("data",), "model")
        assert param_spec_for("ssm/a_log", (8192, 16), MESH) == P("model", None)


class TestAutoRules:
    def test_kv_cache(self):
        spec = auto_spec_for("cache/pos0/k", (23, 128, 32768, 16, 128), MESH, batch=128)
        assert spec == P(None, ("data",), None, "model", None)

    def test_batch1_not_sharded(self):
        spec = auto_spec_for("cache/pos0/k", (23, 1, 524288, 16, 128), MESH, batch=1)
        assert spec == P(None, None, None, "model", None)

    def test_logits(self):
        spec = auto_spec_for("logits", (128, 151936), MESH, batch=128)
        assert spec == P(("data",), "model")

    def test_scalar_metric_replicated(self):
        assert auto_spec_for("loss", (), MESH, batch=128) == P()

    def test_tokens(self):
        assert auto_spec_for("tokens", (256, 4096), MESH, batch=256) == P(("data",), None)

    def test_ssm_state(self):
        spec = auto_spec_for("cache/pos0/h", (64, 128, 8192, 16), MESH, batch=128)
        assert spec == P(None, ("data",), "model", None)


class TestAnalysis:
    def test_parse_collectives(self):
        hlo = """
  %ag = bf16[2,512,128]{2,1,0} all-gather(bf16[2,32,128]{2,1,0} %p), dims={1}
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), to_apply=%sum
  %rs = f32[64]{0} reduce-scatter(f32[1024]{0} %y), dimensions={0}
  %a2a = bf16[16,64]{1,0} all-to-all(bf16[16,64]{1,0} %z), dimensions={0}
  %cp = f32[8]{0} collective-permute(f32[8]{0} %w), source_target_pairs={{0,1}}
  %not = f32[99]{0} add(f32[99]{0} %a, f32[99]{0} %b)
"""
        out = parse_collective_bytes(hlo)
        assert out["all-gather"] == 2 * 512 * 128 * 2
        assert out["all-reduce"] == 1024 * 4
        assert out["reduce-scatter"] == 64 * 4
        assert out["all-to-all"] == 16 * 64 * 2
        assert out["collective-permute"] == 8 * 4
        assert out["total"] == sum(
            out[k] for k in
            ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")
        )

    def test_roofline_dominance(self):
        t = roofline_terms(1e15, 1e9, 1e6, num_chips=256)
        assert t["dominant"] == "compute"
        t = roofline_terms(1e9, 1e12, 1e6, num_chips=256)
        assert t["dominant"] == "memory"

    def test_model_flops_train(self):
        cfg = get_config("stablelm-1.6b")
        mf = model_flops(cfg, batch=256, seq=4096, kind="train")
        assert mf == pytest.approx(6 * cfg.param_count() * 256 * 4096)

    def test_model_flops_moe_uses_active(self):
        cfg = get_config("qwen3-moe-235b-a22b")
        mf = model_flops(cfg, batch=1, seq=1, kind="train")
        assert mf == pytest.approx(6 * cfg.active_param_count())


class TestShapeAssignments:
    def test_all_40_combos_enumerable(self):
        combos = [(a, s) for a in ASSIGNED_ARCHS for s in INPUT_SHAPES]
        assert len(combos) == 40

    @pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
    def test_input_shapes_table(self, arch):
        assert INPUT_SHAPES["train_4k"] == (4096, 256, "train")
        assert INPUT_SHAPES["prefill_32k"] == (32768, 32, "prefill")
        assert INPUT_SHAPES["decode_32k"] == (32768, 128, "decode")
        assert INPUT_SHAPES["long_500k"] == (524288, 1, "decode")
