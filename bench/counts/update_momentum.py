"""FLOPs and bytes one momentum update needs, over ``N`` f32 parameters.

What the algorithm needs, not what a kernel happens to read:

* params, gradient and velocity read once, params and velocity written once
  (f32: 20 bytes a parameter);
* async: the fresh gradient written once into the ring (ring dtype), and
  each ring row the tick's staleness draws select read once, counted as
  distinct slots; a draw of 0 selects the fresh gradient, already read.
  Rows that do not exist yet (the run's first ticks) are not read.  The K
  rows that no draw selects are never counted.

FLOPs: the weighted sum over the selected rows (2 per row), the step scale,
the velocity update (2) and the apply.
"""

from __future__ import annotations

import jax.numpy as jnp


def update_cost(traffic: dict, n: int, taus=None, tick: int | None = None) -> tuple[float, float]:
    """``(flops, bytes)`` of one update; ``taus`` and ``tick`` (0-based) are
    the async tick's staleness draws and index."""
    f32 = 4
    flops, nbytes = 4.0 * n, 5.0 * f32 * n
    if traffic["engine"] != "sync":
        K = int(traffic["ring"])
        row = jnp.dtype(traffic["ring_dtype"]).itemsize
        live = [int(t) for t in taus if tick - int(t) >= 0 and int(t) < K]
        slots = {(tick - t) % K for t in live}
        stored = {(tick - t) % K for t in live if t > 0}
        flops = n * (2.0 * len(slots) + 4.0)
        nbytes += row * n * (1 + len(stored))
    return flops, nbytes
