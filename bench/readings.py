#!/usr/bin/env python3
"""The readings a cell's limits are set from, all in one process.

    python bench/readings.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--out FILE]

For each of ``--seeds``: the program's followed ticks, through the same
set-up as a run (``window.setup``), against the reference: the lower
readings.  For each of ``--control-seeds``: the reference computed with fp8
matrix operands (the control), and the reference that leaves out half of
each row's positions (the half-batch fault), each put in the program's
place against the float32 reference: the upper readings.  A state left
unchanged reads 1 on ``grad_gap`` by the measure and needs no run.

One JSON line per reading goes to standard output and to ``--out``.  The
benchmark's own runs never run this.  Needs a TPU, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def _ints(text: str) -> list[int]:
    return [int(x) for x in text.split(",") if x]


def program_reading(cell, seed: int) -> dict:
    from bench import check, reference
    from bench.window import followed_batches, setup

    timed, spec, pool, reader, times = setup(cell, seed)
    program = reader.result()
    batches = followed_batches(cell, pool)
    del timed, spec, pool, reader
    gc.collect()
    ref = reference.follow(cell.model, cell.config, cell.traffic, batches, seed=seed)
    return {"kind": "program", "seed": seed, **check.gaps(program, ref),
            "first_tick_s": times["first_tick"]}


def stand_in_readings(cell, seed: int) -> list[dict]:
    """The reference computed with fp8 operands (the control), then with half
    of each row's labels (the half-batch fault), each put in the program's
    place against the float32 reference."""
    from bench import check, reference
    from bench.data import make_pool
    from bench.window import Program, followed_batches

    batches = followed_batches(cell, make_pool(cell.config, cell.traffic, seed))
    ref = reference.follow(cell.model, cell.config, cell.traffic, batches, seed=seed)
    rows = []
    for kind, opts in (("fp8", {"low": "fp8"}), ("half_batch", {"fault": "half_batch"})):
        got = reference.follow(cell.model, cell.config, cell.traffic, batches, seed=seed, **opts)
        stand_in = Program(losses=got["losses"], grad_norms=got["grad_norms"],
                           change_norms=got["change_norms"])
        rows.append({"kind": kind, "seed": seed, **check.gaps(stand_in, ref)})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Lower and upper readings of a cell's numbers.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench.cells import load_cell
    from repro.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    cell = load_cell(args.workload)
    out = open(args.out, "a") if args.out else None
    try:
        jobs = [(s, "program") for s in args.seeds] + [(s, "stand-in") for s in args.control_seeds]
        for seed, kind in jobs:
            t0 = time.perf_counter()
            if kind == "program":
                rows = [program_reading(cell, seed)]
            else:
                rows = stand_in_readings(cell, seed)
            for row in rows:
                row.update(workload=cell.name, seconds=time.perf_counter() - t0)
                line = json.dumps(row)
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
            gc.collect()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
