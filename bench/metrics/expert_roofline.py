"""The routed experts' grouped matmuls against their roofline (%).

Their least time a tick is the larger of their FLOPs over the bf16 peak and
their bytes over the HBM peak, at the expected load and without the
recomputation of the forward pass (``counts/<count>.expert_cost``); the
share is that over the device self time a tick of the ops under the
program's ``moe/experts`` scope.
"""

from pathlib import Path

from bench.cells import module
from bench.scopes import scope_ms

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    ms = scope_ms(rec, "moe", "experts")
    if not ms:
        return None
    config, traffic = rec.cell.config, rec.cell.traffic
    count = module("counts", config["count"], ROOT)
    flops, nbytes = count.expert_cost(config, int(traffic["batch"]), int(traffic["positions"]))
    least = max(flops / rec.peak["bf16_flops_per_s"], nbytes / rec.peak["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * ms)
