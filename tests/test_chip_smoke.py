"""``chip_smoke.py``'s phases, run at a tiny size on the CPU.

The script refuses to run without a TPU, so this drives its phase functions
on a reduced stablelm-1.6b: a change that breaks the smoke's path fails here
before it costs a chip run.  On the CPU the fused steps take the XLA
reference, so the Pallas-kernel checks stay the chip's; the kernel parity
phase runs the kernel in interpret mode.
"""

from __future__ import annotations

import importlib.util
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config, reduced

TINY = dict(batch=2, seq=16)
REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    path = REPO / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.smoke_config = lambda layers: reduced(get_config(mod.ARCH), d_model=64)
    return mod


def _trained(res, steps):
    assert res["steps"] == steps
    assert all(math.isfinite(x) for x in res["losses"])


def test_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) == 2
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_async_phase_traces_once_across_refreshes(smoke):
    res = smoke.async_phase(seed=0, **{**smoke.ASYNC, **TINY})
    _trained(res, smoke.ASYNC["steps"])
    assert res["retraces"] == 1
    assert "HloModule" in res["hlo"]


def test_sync_phase(smoke):
    res = smoke.sync_phase(seed=0, **{**smoke.SYNC, **TINY})
    _trained(res, smoke.SYNC["steps"])
    assert res["retraces"] == 1


def test_kernel_parity_ragged_n(smoke):
    par = smoke.kernel_parity(70001, seed=0, interpret=True)
    assert par["ratio"] <= 1.0
    assert par["max_abs"] > 0.0  # the comparison saw real data


def test_live_phase_applies_every_update(smoke):
    res = smoke.live_phase(seed=0, **{**smoke.LIVE, **TINY})
    _trained(res, smoke.LIVE["steps"])
    assert res["state_step"] >= smoke.LIVE["steps"]


FOUR_DEVICES = """
import importlib.util, sys
import jax
from repro.configs import get_config, reduced

assert jax.device_count() == 4, jax.devices()
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke.smoke_config = lambda layers: reduced(get_config(smoke.ARCH), d_model=64)
smoke.SHARDED = {**smoke.SHARDED, "batch": 2, "seq": 16}
smoke.four_chips(0)
print("OK four_chips")
"""


def test_four_chip_path_on_four_cpu_devices(smoke):
    """``--chips 4``'s path (both ring dtypes, with their checks) on four
    virtual CPU devices: the worker-sharded ring built by a jit with worker
    out_shardings, and the fused apply run per device under shard_map."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4 " + env.get("XLA_FLAGS", "")
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert "OK four_chips" in proc.stdout
    assert proc.stdout.count("diff in f32 ulps") == len(smoke.PAIRS)
