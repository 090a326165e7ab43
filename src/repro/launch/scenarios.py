"""Scenario-matrix runner: {arch} x {staleness model} x {strategy} x {optimizer}.

    PYTHONPATH=src python -m repro.launch.scenarios --smoke
    PYTHONPATH=src python -m repro.launch.scenarios \
        --archs stablelm-1.6b,qwen2-moe-a2.7b --staleness geometric,cmp,trace \
        --strategies fixed,eq17,eq26 --optims sgd,adam --steps 20 \
        --out BENCH_scenarios.json

Each cell is declared as one :class:`~repro.run.RunSpec` and executed by the
One Run API (:func:`repro.run.run`) through the SHARDED async engine
(per-worker rings + heterogeneous tau samplers under ``shard_map`` over the
``workers`` mesh axis); a :class:`~repro.run.BenchHook` emits one
``BENCH_scenarios.json`` row group per cell: final loss with the full
loss-vs-updates series in ``meta``, wall-clock, and the jit retrace count
(an online-adaptation regression would show up here as retraces > 1 per
cell).

Staleness models are heterogeneous ACROSS workers within each family —
per-worker geometric p / Poisson lambda / CMP nu spreads, and per-worker
event-simulator traces for ``trace`` — exercising exactly the model- and
scale-dependence the single-sampler harness could not.

The optimizer axis exists because the update is a composable pipeline
(:mod:`repro.optim.transform`): a cell's optimizer is just its base links —
``chain(scale(-lr))`` for ``sgd``, ``chain(scale_by_adam(), scale(-lr))`` for
``adam`` — handed to the one :func:`~repro.training.steps.make_step` builder;
adding an optimizer never touches the engine.
"""

from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from repro.async_engine.events import EventSimConfig, simulate_staleness_trace
from repro.bench_schema import write_bench_json
from repro.compile_cache import use_compile_cache
from repro.configs import ASSIGNED_ARCHS, get_config, reduced
from repro.core.staleness import CMP, Geometric, Poisson
from repro.core.step_size import make_schedule
from repro.data import make_batch_for
from repro.launch.mesh import make_workers_mesh
from repro.optim import transform as T
from repro.run import BenchHook, RunSpec, run
from repro.training import make_worker_adapt

STALENESS_FAMILIES = ("geometric", "poisson", "cmp", "trace")
STRATEGY_CHOICES = ("fixed", "eq17", "eq26")
OPTIM_CHOICES = ("sgd", "adam")

SMOKE_ARCHS = ("stablelm-1.6b", "recurrentgemma-9b")
SMOKE_STALENESS = ("geometric", "trace")
SMOKE_STRATEGIES = ("eq26",)
SMOKE_OPTIMS = ("sgd", "adam")


@dataclasses.dataclass(frozen=True)
class ScenarioCell:
    arch: str
    staleness: str
    strategy: str
    optim: str = "sgd"
    workers: int = 4
    ring: int = 8
    steps: int = 6
    batch: int = 2
    seq: int = 16
    d_model: int = 128
    lr: float = 0.05
    seed: int = 0

    @property
    def name(self) -> str:
        return f"scenarios/{self.arch}/{self.staleness}/{self.strategy}/{self.optim}"

    def config(self) -> dict:
        return dataclasses.asdict(self)


def worker_models(cell: ScenarioCell) -> list:
    """Heterogeneous per-worker staleness samplers for one cell."""
    W, m = cell.workers, float(cell.workers)
    if cell.staleness == "geometric":
        # mean staleness spread ~ [m/2, 3m/2] across workers: p = 1/(1+mean)
        means = np.linspace(0.5 * m, 1.5 * m, W)
        return [Geometric(p=1.0 / (1.0 + mu)) for mu in means]
    if cell.staleness == "poisson":
        return [Poisson(lam=lam) for lam in np.linspace(0.5 * m, 1.5 * m, W)]
    if cell.staleness == "cmp":
        # fixed mode m (eq. 13), per-worker decay rate nu
        return [CMP.from_mode(cell.workers, nu) for nu in np.linspace(0.7, 1.6, W)]
    if cell.staleness == "trace":
        # event-simulated traces, one per worker (distinct seeds + jitter)
        return [
            simulate_staleness_trace(
                EventSimConfig(m=cell.workers, jitter=0.01 * w),
                num_updates=256,
                seed=cell.seed + 17 * w,
            )
            for w in range(W)
        ]
    raise ValueError(f"unknown staleness family {cell.staleness!r}")


def cell_schedule(cell: ScenarioCell):
    """fixed / eq.-17 / eq.-26-normalized step-size schedule for one cell."""
    tau_max = 4 * cell.ring
    if cell.strategy == "fixed":
        return make_schedule("constant", cell.lr, tau_max=tau_max)
    model = Poisson(float(cell.workers))
    if cell.strategy == "eq17":
        return make_schedule("poisson_momentum", cell.lr, model, K=cell.lr, tau_max=tau_max)
    if cell.strategy == "eq26":
        pmf = model.pmf_table(cell.ring - 1)
        return make_schedule(
            "poisson_momentum", cell.lr, model, K=cell.lr,
            tau_max=tau_max, normalize_pmf=pmf / np.sum(pmf),
        )
    raise ValueError(f"unknown strategy {cell.strategy!r}")


def cell_pipeline(cell: ScenarioCell, sched) -> T.Chain:
    """The cell's full update pipeline: staleness link + optimizer links."""
    staleness = T.scale_by_staleness(sched, cell.lr)
    if cell.optim == "sgd":
        return T.chain(staleness, T.scale(-cell.lr))
    if cell.optim == "adam":
        return T.chain(staleness, T.scale_by_adam(), T.scale(-cell.lr))
    raise ValueError(f"unknown optimizer {cell.optim!r}")


def run_cell(cell: ScenarioCell, mesh=None) -> list[dict]:
    """Train one matrix cell through the Run API; returns its BENCH rows.

    All bookkeeping (per-step loss series, wall-clock, the gated jit-retrace
    count) is :class:`~repro.run.hooks.BenchHook`'s — this function only
    declares the cell as a :class:`~repro.run.RunSpec`.  The config hash
    still comes from ``cell.config()``, so blessed baselines stay valid.
    """
    mesh = make_workers_mesh() if mesh is None else mesh
    cfg = reduced(get_config(cell.arch), d_model=cell.d_model)
    sched = cell_schedule(cell)
    pipeline = cell_pipeline(cell, sched)
    adapt = make_worker_adapt(
        sched.table, worker_models(cell), cdf_support=cell.ring
    )
    spec = RunSpec(
        cfg=cfg,
        pipeline=pipeline,
        mode="sharded_async",
        num_steps=cell.steps,
        batch_fn=lambda t: make_batch_for(
            cfg, batch=cell.batch, seq=cell.seq, seed=cell.seed + t
        ),
        ring=cell.ring,
        adapt=adapt,
        mesh=mesh,
        seed=cell.seed,
    )
    bench = BenchHook(cell.name, cell.config())
    run(spec, hooks=[bench])
    return bench.rows


def run_matrix(cells: list[ScenarioCell], out: str, logger=print) -> list[dict]:
    mesh = make_workers_mesh()
    rows: list[dict] = []
    failures: list[str] = []
    for cell in cells:
        try:
            cell_rows = run_cell(cell, mesh)
        except Exception as e:  # noqa: BLE001 — matrix must report every cell
            failures.append(f"{cell.name}: {e!r}")
            logger(f"!! {cell.name} FAILED: {e!r}")
            continue
        rows.extend(cell_rows)
        logger(
            f"{cell.name:<56} loss {cell_rows[0]['value']:.4f} "
            f"wall {cell_rows[1]['value']:5.1f}s retraces {int(cell_rows[2]['value'])}"
        )
    write_bench_json(out, rows)
    logger(f"wrote {len(rows)} rows ({len(rows) // 3} cells) -> {out}")
    if failures:
        raise SystemExit("scenario cells failed:\n  " + "\n  ".join(failures))
    return rows


def build_cells(args) -> list[ScenarioCell]:
    return [
        ScenarioCell(
            arch=a, staleness=s, strategy=st, optim=o,
            workers=args.workers, ring=args.ring, steps=args.steps,
            batch=args.batch, seq=args.seq, lr=args.lr, seed=args.seed,
        )
        for a in args.archs
        for s in args.staleness
        for st in args.strategies
        for o in args.optims
    ]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--archs", default=",".join(SMOKE_ARCHS))
    ap.add_argument("--staleness", default=",".join(SMOKE_STALENESS))
    ap.add_argument("--strategies", default=",".join(SMOKE_STRATEGIES))
    ap.add_argument("--optims", default="sgd")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--ring", type=int, default=8)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI cell set (2 archs x 2 models x 2 optims)")
    ap.add_argument("--out", default="BENCH_scenarios.json")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.smoke:
        args.archs = ",".join(SMOKE_ARCHS)
        args.staleness = ",".join(SMOKE_STALENESS)
        args.strategies = ",".join(SMOKE_STRATEGIES)
        args.optims = ",".join(SMOKE_OPTIMS)
    args.archs = [a for a in args.archs.split(",") if a]
    args.staleness = [s for s in args.staleness.split(",") if s]
    args.strategies = [s for s in args.strategies.split(",") if s]
    args.optims = [o for o in args.optims.split(",") if o]
    for a in args.archs:
        assert a in ASSIGNED_ARCHS, f"unknown arch {a!r}"
    for s in args.staleness:
        assert s in STALENESS_FAMILIES, f"unknown staleness family {s!r}"
    for s in args.strategies:
        assert s in STRATEGY_CHOICES, f"unknown strategy {s!r}"
    for o in args.optims:
        assert o in OPTIM_CHOICES, f"unknown optimizer {o!r}"
    run_matrix(build_cells(args), args.out)


if __name__ == "__main__":
    main()
