"""The numbers that decide ``correct``, each against its limit.

The ticks that set-up drives (``Cell.followed_ticks``: the first refresh
period, its refit and three ticks after it; read from the program's state,
see ``window.Reader``) are compared with the reference's
(``reference.follow``) on the same weights and batches:

* ``loss_gap``: the largest ``|loss - ref| / |ref|`` over the ticks;
* ``grad_gap``: over the weights (a stacked layer counts as one weight per
  layer), the largest gap between the norm of the program's first gradient
  and the reference's, over the larger of the reference's norm of that
  weight and the median weight's;
* ``change_gap``: the same for the params' change over the ticks.
  Weights whose reference gradient is under a thousandth of the median
  weight's move by round-off alone and are left out.

A number is sound when it is finite and at most its limit; ``correct``
needs every number sound, every loss of the window finite and no retrace
inside the window.
"""

from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
QUIET = 1e-3  # of the median weight's reference gradient norm


def _norm_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    prog, ref = prog[keep], ref[keep]
    if ref.size == 0:
        return 0.0
    floor = float(np.median(ref))
    gaps = []
    for p, r in zip(prog, ref):
        den = max(r, floor)
        gaps.append(abs(p - r) / den if den > 0 else (0.0 if p == 0 else math.inf))
    return float(max(gaps))


def gaps(program, ref) -> dict[str, float]:
    """The three numbers for one run (program readings vs reference)."""
    loss = float(np.max(np.abs(program.losses - ref["losses"]) / np.abs(ref["losses"])))
    g_ref = ref["grad_norms"]
    everyone = np.ones_like(g_ref, bool)
    moving = g_ref >= QUIET * float(np.median(g_ref))
    return {
        "loss_gap": loss,
        "grad_gap": _norm_gap(program.grad_norms, g_ref, everyone),
        "change_gap": _norm_gap(program.change_norms, ref["change_norms"], moving),
    }


def checks(numbers: dict[str, float], limits: dict, *, retraces: int, nonfinite: int) -> dict:
    """``{name: {"value", "limit"}}`` for every compared number."""
    out = {k: {"value": numbers[k], "limit": float(limits[k])} for k in NUMBERS}
    out["retraces_in_window"] = {"value": retraces, "limit": 0}
    out["nonfinite_losses"] = {"value": nonfinite, "limit": 0}
    return out


def is_correct(checked: dict) -> bool:
    return all(
        isinstance(c["value"], (int, float)) and math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checked.values()
    )
