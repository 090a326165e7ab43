"""A checkout of the benchmark with one more cell, at tiny widths, for the
CPU tests: the real ``BENCHMARK.json`` and files, plus a configuration, a
traffic mix, limits, an entry and, where a test gives one, a model module
that exist only as new files."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# stablelm-1.6b's structure (LayerNorm, tied head) at widths a CPU test can
# run.  Activations stay float32 here: at 64 positions a tick the loss does
# not average bf16's rounding as the cells' 512 and more do, so the cells'
# limits would not hold for it; what these tests check is the harness.
TINY_CONFIG = {
    "arch": "stablelm-1.6b", "source": "tiny widths for the CPU tests",
    "model": "dense_decoder", "count": "dense_decoder",
    "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "head_dim": 16,
    "d_ff": 128, "vocab_size": 256, "block_pattern": ["global"], "norm_type": "layernorm",
    "norm_eps": 1e-06, "act": "silu", "gated_mlp": True, "parallel_residual": False,
    "tie_embeddings": True, "rope_theta": 10000.0, "num_prefix_embeddings": 0,
    "activation_dtype": "float32", "param_dtype": "float32",
    "attn_block_q": 16, "attn_block_k": 16,
}
# internvl2-2b's: RMSNorm, grouped KV heads, untied head, an image prefix
TINY_VLM = dict(
    TINY_CONFIG, arch="internvl2-2b", norm_type="rmsnorm", num_kv_heads=2,
    tie_embeddings=False, rope_theta=1000000.0, frontend="vision", num_prefix_embeddings=8,
)


def tiny_traffic(name: str) -> dict:
    with open(ROOT / "bench" / "traffic" / f"{name}.json") as f:
        t = json.load(f)
    t.update(batch=2, positions=32, pool=4)
    return t


def make_root(tmp: Path, cells: dict, *, limits_from: str = "stablelm-async-1x512",
              models: dict | None = None) -> Path:
    """A copy of the benchmark under ``tmp`` with ``cells`` added:
    ``{cell: (config dict, traffic name)}``; each new cell takes the limits
    of ``limits_from``.  ``models`` adds model modules: ``{name: source}``."""
    root = tmp / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, source in (models or {}).items():
        (root / "bench" / "models" / f"{name}.py").write_text(source)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    limits = json.loads((ROOT / "bench" / "limits" / f"{limits_from}.json").read_text())
    for cell, (config, traffic) in cells.items():
        (root / "bench" / "configs" / f"{cell}.json").write_text(json.dumps(config))
        (root / "bench" / "traffic" / f"tiny-{traffic}.json").write_text(json.dumps(tiny_traffic(traffic)))
        (root / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(limits))
        bench["workloads"].append(
            {"name": cell, "config": cell, "traffic": f"tiny-{traffic}", "chips": 1, "why": "CPU test"}
        )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
