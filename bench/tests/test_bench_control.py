"""``correct`` refuses the control and the faults a training cell can have.

At tiny widths on the CPU, under the limits of ``stablelm-async-1x512``:

* the control, the reference computed with fp8 matrix operands (forward
  and backward) put in the program's place, fails one of the numbers, while
  the program itself passes;
* a run whose timed path is broken underneath comes out not correct: a tick
  that returns its state unchanged, and a tick that leaves out half of each
  row (the mean taken over the rest), and a refresh that skips the refit
  of alpha(tau).  The harness's look for a chip is skipped; everything else
  is the run as ``run.py`` drives it.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from bench_tiny import TINY_CONFIG, make_root

CELL = "tiny-async"
SEEDS = (3, 2**31 + 11)


@pytest.fixture()
def root(tmp_path, monkeypatch):
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "use_compile_cache", lambda: "off")
    return make_root(tmp_path, {CELL: (TINY_CONFIG, "async-1x512")})


def _over(numbers: dict, limits: dict) -> list[str]:
    return [k for k in ("loss_gap", "grad_gap", "change_gap") if numbers[k] > limits[k]]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_control_fails_where_the_program_passes(root, seed):
    from bench import readings
    from bench.cells import load_cell

    cell = load_cell(CELL, root)
    assert _over(readings.program_reading(cell, seed), cell.limits) == []
    fp8, half_batch = readings.stand_in_readings(cell, seed)
    assert _over(fp8, cell.limits) != []
    assert _over(half_batch, cell.limits) != []


def _unchanged(step):
    def tick(state, batch):
        _, metrics = step(state, batch)
        return state, metrics

    return tick


def _half_batch(step):
    def tick(state, batch):
        labels = batch["labels"]
        keep = jnp.arange(labels.shape[-1]) < labels.shape[-1] // 2
        return step(state, dict(batch, labels=jnp.where(keep, labels, -1)))

    return tick


# fault -> (engine method, patch of it)
FAULTS = {
    "unchanged": ("_make_step", lambda make_step: lambda self: _unchanged(make_step(self))),
    "half_batch": ("_make_step", lambda make_step: lambda self: _half_batch(make_step(self))),
    # the followed ticks run past the first refit: keeping the first
    # alpha(tau) table shows
    "refit_skipped": ("refresh", lambda refresh: lambda self, state: state),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    from bench.cells import load_cell
    from bench.run import run_cell
    from repro.run.engine import AsyncEngine

    method, patch = FAULTS[fault]
    monkeypatch.setattr(AsyncEngine, method, patch(getattr(AsyncEngine, method)))
    res = run_cell(load_cell(CELL, root), seed=SEEDS[0], seconds=0.5, trace=False, root=root,
                   t_start=time.perf_counter())
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
