#!/usr/bin/env python3
"""Smoke run of the staleness-adaptive async trainer on a TPU.

Drives the main path once, through the entry points a user calls
(``repro.run.run`` on a ``RunSpec``, with the launcher's MindTheStep
pipeline ``scale_by_staleness -> scale(-lr) -> trace(0.9)``), at the
published widths of stablelm-1.6b (d_model 2048, 32 x 64 heads, d_ff 5632,
vocab 100352) with random weights from ``--seed``.  Phases, all in this one
process (a TPU chip serves one process):

1. async: ``mode="async", fuse=True`` -- the one-launch Pallas tick (ring
   push, alpha(tau)-weighted combine, momentum apply), W=4 simulated workers,
   a K=4 bf16 ring, a host refresh every 2 ticks.  Checks finite losses, one
   trace across the refreshes, and a Pallas kernel (``tpu_custom_call``) in
   the compiled tick.
2. sync: ``mode="sync", fuse=True`` -- the fused chain kernel.
3. kernel parity: one tick of the Pallas tick kernel against its jnp
   reference at the async phase's parameter count.
4. live: ``mode="distributed"`` over the ``inproc`` transport, W=2 workers.

``--chips 4`` runs only the multi-chip path instead: ``sharded_async`` with
the two-launch fused tick on a 4-device ``workers`` mesh, compared with the
same spec on a 1-device mesh, once in bf16 (ring and activations) and once
in f32 at the highest matmul precision, which shows the two programs agree
to f32 round-off.

Depth is cut, widths are not: stablelm-1.6b's 24 layers are all of one kind
(pattern period 1), so a cut keeps whole periods, and the layers left out
stand for further chips holding later pipeline stages.  One chip holds 4
layers (N = 411M parameters) with the f32 params, momentum, bf16 ring and
activations of the async step.

Usage::

    python chip_smoke.py [--seed 0] [--chips 4]

Lines starting ``[info]`` carry compile times, tick times and peak device
memory.  The last line of stdout is ``{"ok": true, "device": {...}}``; any
failed check exits non-zero before it.  Without a TPU the script exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "stablelm-1.6b"
LR = 0.01  # the launcher's default
MU = 0.9
W, K = 4, 4  # simulated workers, ring depth
ASYNC = dict(layers=4, batch=4, seq=1024, steps=6, refresh_every=2)
SYNC = dict(layers=4, batch=4, seq=1024, steps=3)
# The live server holds params + momentum, the apply's new copy of both,
# and each worker's pulled params and gradient: 4 layers would not fit.
LIVE = dict(layers=2, batch=2, seq=512, steps=4, workers=2)
# The 1-device reference holds the whole (W, K, N) ring: 1 layer fits.
SHARDED = dict(layers=1, batch=2, seq=512, steps=4, refresh_every=2)
# The 4-vs-1-device pairs, as (dtype, ring depth, limit), where the dtype is
# that of the ring and of the activations.  The bf16 pair is what users run.
# Its two programs are compiled apart and, at the TPU's default matmul
# precision, compute the first tick's gradient differently by bf16 rounding,
# so the limit is two bf16 steps (2^-7) of the largest update.  The f32 pair,
# at the highest matmul precision, is the witness that the sharded combine
# and apply keep f32: its programs differ by f32 round-off only (the psum's
# order, the gradient's sums), so their params must agree within a few f32
# ulps of the largest parameter.  An f32 ring of depth 4 does not fit one
# device at 1 layer; depth 2 holds the same bytes as the bf16 ring of depth 4.
PAIRS = (("bfloat16", K, 2.0**-7), ("float32", 2, 4.0))
# Tolerances of the interpret-mode kernel parity tests (tests/test_fuse.py):
# the kernel folds same-slot worker weights before the multiply, the
# reference after, so the two differ by f32 re-association only.
RTOL = ATOL = 1e-6
# The parity reference runs chunk by chunk: the kernel updates its N-element
# operands in place, and a whole-N reference would not fit beside them.
PARITY_CHUNKS = 8


def info(msg: str) -> None:
    print(f"[info] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def smoke_config(layers: int):
    """stablelm-1.6b at its published widths, cut to ``layers`` layers."""
    from repro.configs import get_config

    return dataclasses.replace(get_config(ARCH), num_layers=layers)


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def drive(spec, *hooks, hlo: bool = True) -> dict:
    """Run ``spec`` through ``repro.run.run`` with ``hooks``; host-side
    results only, so the phase's device state is freed when it returns.

    Each tick is timed to the end of its device work.  With ``hlo`` the
    result holds the compiled tick's text (the live server has no tick of
    its own: its work runs in the worker threads and the server's apply).
    """
    import jax
    import numpy as np

    from repro.run import Hook, make_engine, run

    class TickTimer(Hook):
        def on_start(self, ctx):
            self.seconds, self.losses = [], []
            self._t = time.perf_counter()

        def on_tick(self, ctx):
            jax.block_until_ready((ctx.state, ctx.metrics))
            now = time.perf_counter()
            self.seconds.append(now - self._t)
            self._t = now
            self.losses.append(float(np.asarray(ctx.metrics["loss"])))

    engine = make_engine(spec)
    timer = TickTimer()
    result = run(spec, hooks=[timer, *hooks], engine=engine)
    out = {
        "losses": timer.losses,
        "seconds": timer.seconds,
        "retraces": engine.retraces,
        "steps": result.step,
        "state_step": int(np.asarray(result.state.step)),
        "peak": peak_bytes(),  # the process's peak so far, this run included
        "hlo": None,
        "memory": None,
    }
    if hlo:
        # the tick the run compiled (lowering reuses its trace; the compile
        # is served by the persistent cache)
        batch = next(spec.batch_stream())
        compiled = engine._tick.lower(result.state, batch).compile()
        out["hlo"] = compiled.as_text()
        out["memory"] = compiled.memory_analysis()
    return out


def report(name: str, res: dict) -> None:
    steady = sorted(res["seconds"][1:])
    tick_ms = 1e3 * steady[len(steady) // 2] if steady else float("nan")
    info(
        f"{name}: first tick (trace + compile + run) {res['seconds'][0]:.1f} s, "
        f"median later tick {tick_ms:.1f} ms, losses "
        + " ".join(f"{x:.4f}" for x in res["losses"])
        + f", retraces {res['retraces']}, peak_bytes_in_use {res['peak']}"
    )
    if res["memory"] is not None:
        # peak_bytes_in_use counts live buffers; the compiled tick's own
        # scratch (activations, gradient) shows only in its memory analysis
        m = res["memory"]
        info(
            f"{name}: compiled tick memory_analysis argument {m.argument_size_in_bytes} B, "
            f"output {m.output_size_in_bytes} B, alias {m.alias_size_in_bytes} B, "
            f"temp {m.temp_size_in_bytes} B"
        )


def check_trained(name: str, res: dict, steps: int) -> None:
    import math

    check(res["steps"] == steps, f"{name}: ran {res['steps']} of {steps} ticks")
    check(all(math.isfinite(x) for x in res["losses"]), f"{name}: non-finite loss")


def async_phase(*, layers, batch, seq, steps, refresh_every, seed) -> dict:
    import jax.numpy as jnp

    from repro.launch.train import mindthestep_pipeline
    from repro.run import RunSpec

    pipeline, adapt = mindthestep_pipeline(LR, W, K, momentum=MU)
    spec = RunSpec(
        cfg=smoke_config(layers), pipeline=pipeline, mode="async", num_steps=steps,
        batch_size=batch, seq_len=seq, num_workers=W, ring=K,
        ring_dtype=jnp.bfloat16, adapt=adapt, fuse=True,
        refresh_every=refresh_every, seed=seed,
    )
    return drive(spec)


def sync_phase(*, layers, batch, seq, steps, seed) -> dict:
    from repro.launch.train import mindthestep_pipeline
    from repro.run import RunSpec

    pipeline, _ = mindthestep_pipeline(LR, W, K, momentum=MU, staleness=False)
    spec = RunSpec(
        cfg=smoke_config(layers), pipeline=pipeline, mode="sync", num_steps=steps,
        batch_size=batch, seq_len=seq, fuse=True, seed=seed,
    )
    return drive(spec)


def live_phase(*, layers, batch, seq, steps, workers, seed) -> dict:
    from repro.launch.train import mindthestep_pipeline
    from repro.run import RunSpec

    pipeline, adapt = mindthestep_pipeline(LR, workers, K, momentum=MU)
    spec = RunSpec(
        cfg=smoke_config(layers), pipeline=pipeline, mode="distributed", transport="inproc",
        num_steps=steps, batch_size=batch, seq_len=seq, num_workers=workers,
        adapt=adapt, seed=seed,
    )
    return drive(spec, hlo=False)


def kernel_parity(n: int, *, seed: int, interpret: bool = False) -> dict:
    """One momentum tick of the Pallas tick kernel on ``n``-element operands
    against ``fused_tick_ref``.

    The operands are made chunk by chunk from the seed, so the reference
    runs one chunk at a time on operands made again (``PARITY_CHUNKS``).
    Returns the largest ``|kernel - ref| / (ATOL + RTOL |ref|)`` over
    params, velocity and ring (at most 1 passes) and the largest
    ``|kernel - ref|``.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.adaptive_update.fused import HBM_TILE, fused_tick_flat
    from repro.kernels.adaptive_update.ref import fused_tick_ref

    size = -(-n // PARITY_CHUNKS)
    size = -(-size // HBM_TILE) * HBM_TILE  # chunk bounds on HBM tiles; last one ragged
    bounds = [(a, min(a + size, n)) for a in range(0, n, size)]
    key = jax.random.PRNGKey(seed)

    def piece(operand: int, c: int):
        a, b = bounds[c]
        k = jax.random.fold_in(jax.random.fold_in(key, operand), c)
        if operand == 3:  # the (K, n) ring, stored in bf16
            return jax.random.normal(k, (K, b - a), jnp.float32).astype(jnp.bfloat16)
        return jax.random.normal(k, (b - a,), jnp.float32)

    def whole(operand: int):
        return jnp.concatenate([piece(operand, c) for c in range(len(bounds))], axis=-1)

    scalars = {
        "f_stale": 1.3, "f_keep": 1.0, "f_clip": 0.7, "m_scale": -0.05, "mu": MU,
    }
    scalars = {k: jnp.float32(v) for k, v in scalars.items()}
    step = jnp.int32(11)
    taus = jnp.asarray([0, 1, 3, 1], jnp.int32)  # workers 1 and 3 share a slot
    weights = jnp.asarray([0.9, 0.5, 0.3, 0.7], jnp.float32)

    tick = jax.jit(
        functools.partial(fused_tick_flat, "momentum", use_pallas=True, interpret=interpret),
        donate_argnums=(0, 2, 4),  # p, velocity, ring: updated in place
    )
    p, v, ring = tick(whole(0), whole(1), whole(2), scalars, whole(3), step, taus, weights)[:3]

    @jax.jit
    def ref_chunk(p, g, v, r):
        return fused_tick_ref("momentum", p, g, v, scalars, r, step, taus, weights)

    @jax.jit
    def errors(x, y):
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        d = jnp.abs(x - y)
        return jnp.max(d / (ATOL + RTOL * jnp.abs(y))), jnp.max(d)

    ratio = err = 0.0
    for c, (a, b) in enumerate(bounds):
        rp, rv, rr, _ = ref_chunk(piece(0, c), piece(1, c), piece(2, c), piece(3, c))
        for got, want in ((p[a:b], rp), (v[a:b], rv), (ring[:, a:b], rr)):
            r, e = (float(np.asarray(x)) for x in errors(got, want))
            ratio, err = max(ratio, r), max(err, e)
    return {"ratio": ratio, "max_abs": err}


def sharded_spec(cfg, mesh, *, ring, ring_dtype, batch, seq, steps, refresh_every, seed):
    """The same sharded_async RunSpec for any ``workers`` mesh."""
    from repro.launch.train import mindthestep_pipeline
    from repro.run import RunSpec
    from repro.training import default_adapt_setup, make_worker_adapt

    pipeline, _ = mindthestep_pipeline(LR, W, ring, momentum=MU)
    sched, model, _ = default_adapt_setup(LR, W, ring)
    adapt = make_worker_adapt(sched.table, [model] * W, cdf_support=ring)
    return RunSpec(
        cfg=cfg, pipeline=pipeline, mode="sharded_async", num_steps=steps,
        batch_size=batch, seq_len=seq, num_workers=W, ring=ring,
        ring_dtype=ring_dtype, adapt=adapt, mesh=mesh, fuse=True,
        refresh_every=refresh_every, seed=seed,
    )


def sharded_pair(*, layers, activations, seed, **kw) -> dict:
    """``sharded_async`` on 4 devices vs the same spec on 1 device.

    Returns both runs, the largest update ``max |p1 - p0|``, the largest
    parameter ``max |p1|`` and ``max |p4 - p1|``.
    """
    import jax
    import numpy as np

    from repro.launch.mesh import make_workers_mesh
    from repro.optim import transform as T
    from repro.run import Hook
    from repro.training import init_params

    class Keep(Hook):
        def on_end(self, ctx):
            self.params = np.asarray(ctx.state.params)

    cfg = dataclasses.replace(smoke_config(layers), activation_dtype=activations)
    p0 = np.asarray(T.pack_flat(init_params(jax.random.PRNGKey(seed), cfg)))
    finals, results = [], []
    for devices in (4, 1):
        spec = sharded_spec(cfg, make_workers_mesh(devices), seed=seed, **kw)
        keep = Keep()
        results.append(drive(spec, keep, hlo=False))
        finals.append(keep.params)
    p4, p1 = finals
    return {
        "runs": results,
        "max_update": float(np.max(np.abs(p1 - p0))),
        "max_param": float(np.max(np.abs(p1))),
        "max_diff": float(np.max(np.abs(p4 - p1))),
    }


def param_count(layers: int) -> int:
    import jax

    from repro.async_engine.delayed import flat_size
    from repro.training import init_params

    cfg = smoke_config(layers)
    return flat_size(jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))


def one_chip(seed: int) -> None:
    n = param_count(ASYNC["layers"])
    info(f"{ARCH} at published widths, {ASYNC['layers']} of 24 layers: N = {n} parameters")

    res = async_phase(seed=seed, **ASYNC)
    report("async (fuse=True, W=4, K=4 bf16 ring)", res)
    check_trained("async", res, ASYNC["steps"])
    check(res["retraces"] == 1, f"async: {res['retraces']} traces across host refreshes")
    check("tpu_custom_call" in res["hlo"], "async: no Pallas kernel in the compiled tick")

    res = sync_phase(seed=seed, **SYNC)
    report("sync (fuse=True)", res)
    check_trained("sync", res, SYNC["steps"])
    check(res["retraces"] == 1, f"sync: {res['retraces']} traces")
    check("tpu_custom_call" in res["hlo"], "sync: no Pallas kernel in the compiled step")

    par = kernel_parity(n, seed=seed)
    info(
        f"kernel parity (momentum tick, N = {n}, K = {K} bf16): max |kernel - ref| "
        f"{par['max_abs']:.3e}, worst error / (atol + rtol |ref|) {par['ratio']:.3e} "
        f"(rtol = atol = {RTOL}), peak_bytes_in_use {peak_bytes()}"
    )
    check(par["ratio"] <= 1.0, "kernel parity outside tolerance")

    info(f"live server: depth cut to {LIVE['layers']} layers to fit the chip "
         f"(N = {param_count(LIVE['layers'])})")
    res = live_phase(seed=seed, **LIVE)
    report(f"live (distributed, inproc, W={LIVE['workers']})", res)
    check_trained("live", res, LIVE["steps"])
    check(res["state_step"] >= LIVE["steps"], f"live: {res['state_step']} updates applied")


def four_chips(seed: int) -> None:
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    n = param_count(SHARDED["layers"])
    for dtype, ring, limit in PAIRS:
        witness = dtype == "float32"
        name = f"W = {W}, K = {ring} {dtype} ring, {dtype} activations"
        if witness:
            name += ", highest matmul precision"
        info(f"sharded_async on 4 devices vs 1: {ARCH} at published widths, "
             f"{SHARDED['layers']} of 24 layers, N = {n} parameters, {name}")
        precision = (
            jax.default_matmul_precision("highest") if witness else contextlib.nullcontext()
        )
        with precision:
            pair = sharded_pair(seed=seed, activations=dtype, ring=ring,
                                ring_dtype=jnp.dtype(dtype), **SHARDED)
        for devices, res in zip((4, 1), pair["runs"]):
            report(f"sharded_async fuse=True on {devices} device(s), {name}", res)
            check_trained(f"sharded on {devices}, {name}", res, SHARDED["steps"])
            check(res["retraces"] == 1, f"sharded on {devices}, {name}: {res['retraces']} traces")
        diff, update = pair["max_diff"], pair["max_update"]
        ulps = diff / (float(np.finfo(np.float32).eps) * pair["max_param"])
        info(f"params 4 vs 1 device, {name}: max |diff| {diff:.3e}, max |update| {update:.3e}, "
             f"diff / update {diff / update:.3e}, max |param| {pair['max_param']:.4f}, "
             f"diff in f32 ulps of max |param| {ulps:.3f}")
        check(update > 0, f"sharded, {name}: params never moved")
        if witness:
            check(ulps <= limit, f"sharded, {name}: 4 devices disagree beyond {limit} ulps")
        else:
            check(diff <= limit * update,
                  f"sharded, {name}: 4 devices disagree beyond {limit} of the update")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Smoke run of the async trainer on a TPU.")
    ap.add_argument("--seed", type=int, default=0, help="seed of weights and data")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path on four chips, against one")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.compile_cache import use_compile_cache

    info(f"compile cache: {use_compile_cache()}")
    info(f"device: {devices[0].device_kind} x {len(devices)}")
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    dev = jax.devices()[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
