"""The harness end to end on the CPU, at tiny widths.

Set-up through the program's own entry, the window loop with its retrace
count, the check against the reference and the result line, for cells that
exist only as new files in a copy of the benchmark.  On the CPU the
program's tick runs the XLA form of its fused kernels.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from bench_tiny import ROOT, TINY_CONFIG, TINY_VLM, make_root

SEED = 2**31 + 5  # seeds run past 32 bits


@pytest.fixture()
def no_cache(monkeypatch):
    """Keep the CPU tests' compiles out of the checkout's cache."""
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "use_compile_cache", lambda: "off")


def _run(root, cell, *, seconds=1.0):
    from bench.cells import load_cell
    from bench.run import run_cell

    return run_cell(load_cell(cell, root), seed=SEED, seconds=seconds, trace=False, root=root,
                    t_start=time.perf_counter())


def test_a_cell_added_as_files_loads(tmp_path):
    from bench.cells import load_cell

    root = make_root(tmp_path, {"tiny-async": (TINY_CONFIG, "async-1x512")})
    cell = load_cell("tiny-async", root)
    assert cell.config == TINY_CONFIG
    assert cell.traffic["engine"] == "async" and cell.tokens_per_tick == 2 * 32
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s", "setup_s"]
    assert "refresh_ms" not in [m["name"] for m in cell.per_layer]
    with pytest.raises(KeyError):
        load_cell("no-such-cell", root)


@pytest.mark.parametrize("cell,config,traffic", [
    ("tiny-async", TINY_CONFIG, "async-1x512"),
    ("tiny-vlm-sync", TINY_VLM, "sync-4x1024"),
])
def test_run_cell_on_cpu(tmp_path, no_cache, cell, config, traffic):
    root = make_root(tmp_path, {cell: (config, traffic)})
    res = _run(root, cell)
    line = json.loads(json.dumps(res))  # the last line's builder
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 10 and line["attempted"] % 10 == 0 and line["failed"] == 0
    assert line["checks"]["retraces_in_window"] == {"value": 0, "limit": 0}
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1


def test_run_py_exits_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "stablelm-async-1x512",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2, proc.stderr
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""
