#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the program's engine from the cell's files (``cells.py``),
with weights (of the shapes the configuration's ``models/`` module gives)
and a pool of batches made on the device from ``--seed`` (``data.py``), and
drives it through its first refresh period (``window.py``).
Then:

* ``--trace 0``: the measured window runs whole refresh periods for
  ``--seconds`` and gives the end-to-end metrics (``tokens_per_s``,
  ``tick_ms_p95``, ``setup_s``);
* ``--trace 1``: two refresh periods run under the profiler and the
  per-layer metrics are read from that trace, with the program's own spans
  and scopes (``trace.py``, ``program_trace.py``, ``metrics/``).

Either way the ticks that set-up drove (``Cell.followed_ticks``) are then
compared with the plain reference (``reference.py`` around the model's
loss, ``check.py``), once the window is over and the program's state is
freed.  Each compared number is printed beside its limit
as the last lines of standard error; the last line of standard output is the
result as one JSON object.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class TraceRecord:
    """What a per-layer metric reader gets (see ``metrics/__init__.py``)."""

    cell: object
    reduced: object  # program_trace.Scoped (a trace.Reduced with the program's marks)
    ticks: int
    chips: int
    peak: dict
    flops_per_tick: float
    update_costs: list  # (flops, bytes) per traced tick
    refresh_s: list


def peaks(kind: str, root: Path = ROOT) -> dict:
    with open(root / "bench" / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no peaks in bench/peaks.json")
    return table[kind]


def p95(values) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), 95))


def _device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _traced_window(timed, spec, pool, cell):
    """Two refresh periods under the profiler; returns (state, stats, plain),
    the plain form with the program's spans and scopes (``program_trace``)."""
    import jax

    from bench import program_trace
    from bench import trace as tr
    from bench.window import window

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    with jax.profiler.trace(str(TRACE_DIR)):
        with jax.profiler.TraceAnnotation("window"):
            state, stats = window(timed, spec, pool, cell, 0.0, max_chunks=2)
    t0 = time.perf_counter()
    path = tr.find_xplane(str(TRACE_DIR))
    plain = program_trace.load(path, [d.id for d in jax.devices()[: cell.chips]])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    log(f"trace read in {time.perf_counter() - t0:.3f} s")
    return state, stats, plain


def _per_layer(cell, plain, stats, seed: int, chips: int, kind: str, root: Path):
    from bench.cells import module
    from bench.data import param_count
    from bench.program_trace import Scoped
    from bench.reference import tick_taus

    reduced = Scoped(plain)
    t = cell.traffic
    n = param_count(cell.shapes)
    update = module("counts", t["update_count"], root)
    first = cell.followed_ticks
    ticks = stats["ticks"]
    if t["engine"] != "sync":
        taus = tick_taus(seed, first + ticks, int(t["workers"]), int(t["ring"]))
        costs = [update.update_cost(t, n, taus[i], i) for i in range(first, first + ticks)]
    else:
        costs = [update.update_cost(t, n)] * ticks
    flops = module("counts", cell.config["count"], root)
    rec = TraceRecord(
        cell=cell, reduced=reduced, ticks=ticks, chips=chips, peak=peaks(kind, root),
        flops_per_tick=flops.train_flops(cell.config, int(t["batch"]), int(t["positions"])),
        update_costs=costs, refresh_s=list(stats["refresh_s"]),
    )
    metrics = {}
    for m in cell.per_layer:
        value = module("metrics", m["name"], root).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    # the parts of fwd_bwd_ms by the program's scopes; they add up to it
    split = {k: 1e3 * v / ticks for k, v in reduced.other_by_scope().items()}
    log(f"step body, ms a tick: {split}, their sum {sum(split.values())!r}")
    breakdown = {"device_ops": reduced.top_ops(10), "idle_gaps": reduced.idle_gaps(10),
                 "idle_gaps_program": reduced.idle_gaps_program(10)}
    busy = {"busy_s": reduced.mean_busy_s(), "window_s": reduced.window_s}
    return metrics, breakdown, busy


def run_cell(cell, *, seed: int, seconds: float, trace: bool, root: Path = ROOT,
             t_start: float | None = None) -> dict:
    """Set-up, window, check: the result object of one run of ``cell``."""
    import gc

    import jax
    import numpy as np

    from bench import check, reference
    from bench.window import followed_batches, setup, window

    from repro.compile_cache import use_compile_cache

    t_start = _T_START if t_start is None else t_start
    log(f"compile cache: {use_compile_cache()}")
    kind = jax.devices()[0].device_kind
    times = {"import": time.perf_counter() - t_start}
    timed, spec, pool, reader, phases = setup(cell, seed, annotate=trace)
    times.update(phases)
    setup_s = time.perf_counter() - t_start
    log("setup " + ", ".join(f"{k} {v:.3f} s" for k, v in times.items()) + f", total {setup_s:.3f} s")

    if trace:
        state, stats, plain = _traced_window(timed, spec, pool, cell)
    else:
        state, stats = window(timed, spec, pool, cell, seconds)
    log(f"window: {stats['ticks']} ticks in {stats['seconds']:.3f} s, "
        f"retraces in window {stats['retraces']}, refreshes {len(stats['refresh_s'])}, "
        f"longest tick interval {1e3 * max(stats['intervals_s']):.3f} ms")
    losses = np.asarray(jax.device_get(stats["losses"]), np.float64)
    device = _device_info(cell.chips)
    program = reader.result()

    # free the program's state before the reference runs on the chip
    batches = followed_batches(cell, pool)
    del state, timed, reader, pool, spec
    gc.collect()

    ref = reference.follow(cell.model, cell.config, cell.traffic, batches, seed=seed)
    numbers = check.gaps(program, ref)
    nonfinite = int(np.sum(~np.isfinite(losses)))
    checked = check.checks(numbers, cell.limits, retraces=int(stats["retraces"]), nonfinite=nonfinite)
    correct = check.is_correct(checked)

    result = {"correct": correct, "attempted": int(stats["ticks"]), "failed": nonfinite}
    metrics = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    if trace:
        per_layer, breakdown, busy = _per_layer(cell, plain, stats, seed, cell.chips, kind, root)
        metrics = per_layer
        device.update(busy)
    else:
        values = {
            "tokens_per_s": stats["ticks"] * cell.tokens_per_tick / stats["seconds"],
            "tick_ms_p95": 1e3 * p95(stats["intervals_s"]),
            "setup_s": setup_s,
        }
        for name, unit in units.items():
            metrics[name] = {"value": values[name], "unit": unit}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = checked
    for name, c in checked.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.cells import load_cell

    cell = load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found {len(devices)}", file=sys.stderr)
        return 2
    result = run_cell(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
