"""Benchmark driver — one section per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--smoke] [--only NAME]
                                            [--json] [--out-dir DIR]

Sections:
  tau_models    Table I + Fig 2  (staleness-model fit quality)
  convergence   Fig 3            (AsyncPSGD vs MindTheStep iterations)
  sync_scaling  Theorem 1        (effective batch, variance scaling)
  convex_bounds Thm 6 / Cor 3-4  (measured vs analytic bounds)
  kernels       (system)         Pallas kernels + TPU roofline
  roofline      (system)         dry-run roofline table per arch x shape
  distributed   (system)         LIVE parameter server: updates/sec +
                                 measured-vs-modeled staleness fit

With ``--json`` every section's wall-clock and pass/fail status lands in
``BENCH_smoke.json`` and sections that produce schema rows (kernels) write
their own ``BENCH_<section>.json`` — the machine-readable inputs of the CI
bench-gate (``benchmarks/bench_gate.py``).  A failing section is reported by
NAME both immediately (``!! FAILED``) and in the nonzero exit, never silently
folded into a later section's output.
"""

from __future__ import annotations

import argparse
import os
import time
import traceback

from benchmarks import (
    ablation_momentum,
    convergence,
    convex_bounds,
    distributed_bench,
    kernels_bench,
    roofline,
    sync_scaling,
    tau_models,
)
from repro.compile_cache import use_compile_cache

SECTIONS = {
    "tau_models": tau_models.main,
    "convergence": convergence.main,
    "sync_scaling": sync_scaling.main,
    "convex_bounds": convex_bounds.main,
    "kernels": kernels_bench.main,
    "roofline": roofline.main,
    "ablation_momentum": ablation_momentum.main,
    "distributed": distributed_bench.main,
}


# CI smoke set: every perf script is imported and executed at reduced scale
# so the benchmarks can't silently rot; the one exclusion is the heavyweight
# dry-run roofline section, exercised by tests/test_dryrun_small.py instead.
SMOKE_SECTIONS = (
    "tau_models", "convergence", "sync_scaling", "convex_bounds",
    "ablation_momentum", "kernels", "distributed",
)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true", help="reduced iteration counts")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: fast iteration counts over the smoke section set")
    ap.add_argument("--only", choices=list(SECTIONS), default=None)
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_*.json (bench.v1 schema) for the CI gate")
    ap.add_argument("--out-dir", default=".", help="directory for BENCH_*.json files")
    args = ap.parse_args()
    use_compile_cache()
    if args.smoke:
        args.fast = True

    names = ([args.only] if args.only
             else list(SMOKE_SECTIONS) if args.smoke
             else list(SECTIONS))
    mode = {"fast": args.fast, "smoke": args.smoke}
    failures = []
    summary_rows = []
    total_t0 = time.perf_counter()
    for name in names:
        print(f"\n{'=' * 72}\n>> benchmark: {name}\n{'=' * 72}")
        t0 = time.perf_counter()
        section_rows, ok = None, True
        try:
            section_rows = SECTIONS[name](fast=args.fast)
        except Exception as e:  # noqa: BLE001 — every section must run; exit is nonzero below
            ok = False
            failures.append(name)
            traceback.print_exc()
            print(f"!! FAILED: {name}: {e!r}")
        wall = time.perf_counter() - t0
        print(f"<< {name} done in {wall:.1f}s")
        if args.json:
            from repro.bench_schema import bench_row, write_bench_json

            summary_rows.append(
                bench_row(f"smoke/{name}/wall_s", wall, "s", {"section": name, **mode})
            )
            summary_rows.append(
                bench_row(f"smoke/{name}/ok", 1.0 if ok else 0.0, "bool",
                          {"section": name, **mode}, gate="higher", tol=0.0)
            )
            if ok and section_rows:
                write_bench_json(
                    os.path.join(args.out_dir, f"BENCH_{name}.json"), section_rows
                )
    if args.json:
        from repro.bench_schema import bench_row, write_bench_json

        summary_rows.append(
            bench_row(
                "smoke/total_wall_s", time.perf_counter() - total_t0, "s",
                {"sections": names, **mode}, gate="lower", tol=0.25,
            )
        )
        path = write_bench_json(os.path.join(args.out_dir, "BENCH_smoke.json"), summary_rows)
        print(f"wrote {path}")
    if failures:
        raise SystemExit(
            "benchmark sections FAILED: " + ", ".join(failures)
            + " (see tracebacks above)"
        )


if __name__ == "__main__":
    main()
