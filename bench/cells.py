"""Find a cell's files by the names in ``BENCHMARK.json`` and build its run.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
Each lives in a file of its own under this directory:

* ``configs/<config>.json``: the model as it is run.  Every key that is a
  field of the program's ``ModelConfig`` is applied to the program's
  registered architecture (``arch``) and must read back unchanged; the other
  keys (``source``, ``reduced``, ``assumed``, ``deployment``) are the
  configuration's record, and ``model`` and ``count`` name its modules:
  ``models/<model>.py`` (weight shapes and plain loss, for the weights and
  the reference) and ``counts/<count>.py`` (FLOPs a tick).
* ``traffic/<traffic>.json``: the job: engine, workers, ring, optimizer
  body, batch, positions per row, refresh cadence, pool of batches.
* ``limits/<cell>.json``: the limit of each number that decides ``correct``.
* ``metrics/<metric>.py``: the reader of one per-layer metric.

Adding a cell, a configuration, a model, a traffic mix or a metric is adding
files and an entry; nothing here names one.  :func:`module` loads each
module file by its name.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
PAST_REFIT = 3  # ticks followed after the first refresh period


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict  # the configuration file
    model: object  # the module ``models/<config["model"]>.py``
    traffic: dict  # the traffic file
    limits: dict  # number -> limit
    end_to_end: tuple  # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple

    @property
    def shapes(self) -> dict:
        """The weight tree's ``{path: (shape, init)}``."""
        return self.model.weight_shapes(self.config)

    @property
    def prefix(self) -> int:
        """Image-prefix positions per row (0 for a text-only model)."""
        return int(self.config.get("num_prefix_embeddings") or 0)

    @property
    def text_positions(self) -> int:
        return int(self.traffic["positions"]) - self.prefix

    @property
    def tokens_per_tick(self) -> int:
        """Positions trained on in one tick, image-prefix positions included."""
        return int(self.traffic["batch"]) * int(self.traffic["positions"])

    @property
    def chunk(self) -> int:
        """Ticks per orchestrator call: one refresh period (10 without one)."""
        return int(self.traffic.get("refresh_every") or 10)

    @property
    def followed_ticks(self) -> int:
        """Ticks that set-up drives and ``correct`` follows: the first refresh
        period, whose ring wraps and draws every tau the ring holds, and
        ``PAST_REFIT`` ticks under the table its refit made."""
        return self.chunk + PAST_REFIT


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def module(kind: str, name: str, root: Path = ROOT):
    """The module ``<root>/bench/<kind>/<name>.py`` (``kind``: ``models``,
    ``counts`` or ``metrics``), loaded from its file; raises
    ``FileNotFoundError`` naming the file where there is none."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r}: looked for {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files from
    ``<root>/bench``; raises ``KeyError`` for an unknown cell and
    ``FileNotFoundError`` for a configuration whose model has no module."""
    bench = load_benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(entries)}")
    w = entries[name]
    files = root / "bench"

    def read(kind: str, stem: str) -> dict:
        with open(files / kind / f"{stem}.json") as f:
            return json.load(f)

    config = read("configs", w["config"])
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        traffic_name=w["traffic"],
        config=config,
        model=module("models", config["model"], root),
        traffic=read("traffic", w["traffic"]),
        limits=read("limits", name),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
    )


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file.

    Starts from the registered architecture and sets every key of the file
    that names a field; each must then read back as the file states it.
    """
    from repro.configs import get_config

    base = get_config(config["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    values = {}
    for k, v in config.items():
        if k in fields:
            values[k] = tuple(v) if isinstance(v, list) else v
    cfg = dataclasses.replace(base, **values)
    for k, v in values.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"config {config['arch']}: {k} reads {getattr(cfg, k)!r}, file says {v!r}")
    return cfg


def run_spec(cell: Cell, cfg, params, *, seed: int):
    """The program's ``RunSpec`` for ``cell``: weights ``params`` (made by the
    benchmark from the seed), the launcher's MindTheStep pipeline."""
    import jax.numpy as jnp

    from repro.launch.train import mindthestep_pipeline
    from repro.run import RunSpec

    t = cell.traffic
    engine = t["engine"]
    if t["body"] != "momentum" or t.get("staleness", "poisson") != "poisson":
        raise ValueError(f"traffic {cell.traffic_name}: only the poisson/momentum recipe is built")
    W, K = int(t.get("workers", 1)), int(t.get("ring", 0))
    pipeline, adapt = mindthestep_pipeline(
        float(t["lr"]), W, max(K, 1), momentum=float(t["momentum"]),
        staleness=engine != "sync",
    )
    return RunSpec(
        cfg=cfg, pipeline=pipeline, mode=engine, num_steps=cell.chunk,
        batch_size=int(t["batch"]), seq_len=cell.text_positions,
        num_workers=W, ring=K,
        ring_dtype=jnp.dtype(t["ring_dtype"]) if engine != "sync" else None,
        adapt=adapt, fuse=True,
        refresh_every=int(t.get("refresh_every") or 0), seed=seed, params=params,
    )
