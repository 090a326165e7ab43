"""The program writes its own host spans into the profiler's trace.

A tiny run of each async engine through ``repro.run.run``, with a refresh
every 3 ticks, under ``jax.profiler.trace`` on the CPU; the host plane is
read back with ``program_trace.load``.
"""

from __future__ import annotations

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repo and src on the path)

TICKS, EVERY = 6, 3


def _spec(mode):
    from repro.configs import get_config, reduced
    from repro.core.staleness import Geometric, Poisson
    from repro.core.step_size import make_schedule
    from repro.optim import transform as T
    from repro.run import RunSpec
    from repro.training import make_adapt, make_worker_adapt

    tau_max, ring, lr = 7, 4, 0.05
    sched = make_schedule("poisson_momentum", lr, Poisson(3.0), K=1.0, tau_max=tau_max)
    pipeline = T.chain(T.scale_by_staleness(sched, lr, m=4, tau_max=tau_max), T.scale(-lr))
    if mode == "async":
        adapt = make_adapt(sched, Poisson(3.0), cdf_support=ring, tau_max=tau_max)
    else:
        samplers = [Geometric(p=0.3), np.asarray([0, 1, 2, 1, 3], np.int64)]
        adapt = make_worker_adapt(sched.table[: tau_max + 1], samplers, cdf_support=ring)
    return RunSpec(
        cfg=reduced(get_config("stablelm-1.6b"), d_model=64), pipeline=pipeline, mode=mode,
        num_steps=TICKS, batch_size=2, seq_len=16, num_workers=4, ring=ring, adapt=adapt,
        fuse=True, refresh_every=EVERY, seed=0,
    )


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


@pytest.mark.parametrize("mode", ["async", "sharded_async"])
def test_run_writes_the_program_spans(tmp_path, mode):
    import jax

    from bench import program_trace as pt
    from bench import trace as tr
    from repro.run import run

    spec = _spec(mode)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the spans are TraceMe events; skip Python calls
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        with jax.profiler.TraceAnnotation("window"):
            jax.block_until_ready(run(spec).state)
    plain = pt.load(tr.find_xplane(str(tmp_path)), [])
    spans = sorted(plain["program"], key=lambda s: s[1])
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)

    assert not set(by) & set(tr.HOST_SPANS)
    assert len(by["engine.tick"]) == TICKS
    assert len(by["run.input"]) == TICKS
    assert len(by["run.hooks"]) == TICKS + TICKS // EVERY
    # one trace, inside the first tick's dispatch
    assert len(by["engine.trace"]) == 1
    assert _inside(by["engine.trace"][0], by["engine.tick"][0])
    # each refresh holds its three phases, in order
    assert len(by["engine.refresh"]) == TICKS // EVERY
    for refresh in by["engine.refresh"]:
        phases = [s for s in spans if s[0].startswith("refresh.") and _inside(s, refresh)]
        assert [s[0] for s in phases] == ["refresh.drain", "refresh.refit", "refresh.swap"]
        ends = [s[1] + s[2] for s in phases]
        assert all(end <= nxt[1] for end, nxt in zip(ends, phases[1:]))
