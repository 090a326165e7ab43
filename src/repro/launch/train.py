"""Production training launcher (One Run API).

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --steps 200 --batch 8 --seq 256 --reduced --async_psgd --strategy poisson_momentum

The step runs on one device: the launcher builds no mesh (only
``sharded_async`` runs, which the scenario matrix drives, span a ``workers``
mesh).  Full configs are sized for the chip; on CPU (CI) the ``--reduced``
flag trains the reduced config.
The MindTheStep configuration mirrors the paper's Fig. 3 protocol: Poisson
staleness model with lambda = m, eq. (17) step size with K = alpha_c (the
implicit-momentum magnitude, in step-size units), normalization (eq. 26)
against the observed tau histogram, clip at 5 alpha_c, drop tau>150.

The update is assembled as ONE gradient-transform pipeline
(:mod:`repro.optim.transform`), and the run is declared as ONE
:class:`~repro.run.RunSpec` executed by :func:`repro.run.run` — engine mode
(``sync``/``async``), fusion, the online refresh policy, and the data stream
are all spec fields; logging and checkpointing are hooks.  With
``--refresh_every N`` the compiled step samples W worker taus per tick and
histograms them in-jit; every N steps the host drains the histogram, refits,
and swaps fresh tables into the jit-resident :class:`AdaptState` (no
retrace).  ``--fused`` applies updates through the fused flat-buffer path;
``--fuse`` lowers the whole pipeline to one Pallas kernel per step.

Checkpoint/resume is first-class: ``--checkpoint_dir D --checkpoint_every N``
saves full-fidelity checkpoints (params, optimizer state, delayed rings,
adaptation tables + histograms, host estimator, rng); add ``--resume`` to
continue the latest one bit-identically to an uninterrupted run.
"""

from __future__ import annotations

import argparse

from repro.compile_cache import use_compile_cache
from repro.configs import ASSIGNED_ARCHS, get_config, reduced
from repro.distributed.transport import transport_kinds
from repro.optim import transform as T
from repro.run import CheckpointHook, LogHook, RunSpec, run
from repro.training import default_adapt_setup


def mindthestep_pipeline(
    lr: float,
    workers: int,
    ring: int,
    *,
    momentum: float | None = None,
    fused: bool = False,
    staleness: bool = True,
):
    """The launcher's update pipeline and its jit-resident adaptation state.

    Base links: ``fused_apply(lr, mu)`` with ``fused`` (mu defaults to 0.9),
    ``scale(-lr) -> trace(momentum)`` with a momentum, else ``scale(-lr)``.
    With ``staleness`` (the async and live engines) a ``scale_by_staleness``
    link leads the chain and ``adapt`` is its :class:`AdaptState`; otherwise
    ``adapt`` is None.  Returns ``(pipeline, adapt)``.
    """
    if fused:
        base_links = (T.fused_apply(lr, 0.9 if momentum is None else momentum),)
    elif momentum is not None:
        base_links = (T.scale(-lr), T.trace(momentum))
    else:
        base_links = (T.scale(-lr),)
    if not staleness:
        return T.chain(*base_links), None
    sched, _model, adapt = default_adapt_setup(lr, workers, ring)
    # m enables the online estimator; its tau_max must cover adapt's so a
    # refreshed table always fills the jit-resident one.
    link = T.scale_by_staleness(sched, lr, m=workers, tau_max=adapt.tau_max)
    return T.chain(link, *base_links), adapt


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="stablelm-1.6b", choices=list(ASSIGNED_ARCHS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--reduced", action="store_true", help="CPU-sized same-family variant")
    ap.add_argument("--async_psgd", action="store_true", help="MindTheStep async step")
    ap.add_argument("--engine", default=None, choices=["sync", "async", "distributed"],
                    help="engine mode override; 'distributed' runs the LIVE "
                         "parameter server (repro.distributed): --workers real "
                         "workers over --transport, measured staleness")
    ap.add_argument("--transport", default="inproc", choices=list(transport_kinds()),
                    help="distributed worker fabric (make_transport registry): "
                         "threads/queues, or TCP + multiprocessing.spawn for "
                         "true multi-process")
    ap.add_argument("--trace_out", default=None,
                    help="stream the live run's measured staleness to this "
                         "events-format trace file (distributed engine only; "
                         "v2 records carry wall-clock pull/push stamps)")
    ap.add_argument("--faults", default=None,
                    help="chaos injection for the live parameter server, e.g. "
                         "'crash_before_push:worker=1:after=2,delay_push:"
                         "worker=0:seconds=0.2' (see repro.distributed.faults."
                         "parse_faults; distributed engine only)")
    ap.add_argument("--worker_timeout", type=float, default=None,
                    help="seconds of worker silence (after taking work) before "
                         "the server declares it dead and reclaims its "
                         "in-flight batch (distributed engine only)")
    ap.add_argument("--workers", type=int, default=16, help="modeled async workers m")
    ap.add_argument("--ring", type=int, default=16, help="delayed-gradient ring size")
    ap.add_argument("--ring_dtype", default=None, choices=["float32", "bfloat16"],
                    help="delayed-ring storage dtype (default: the params "
                         "dtype for all-f32 trees, bf16-compressed otherwise)")
    ap.add_argument("--refresh_every", type=int, default=0, help="online refit cadence")
    ap.add_argument("--fused", action="store_true",
                    help="fused flat-buffer momentum apply (Pallas on TPU)")
    ap.add_argument("--fuse", action="store_true",
                    help="lower the WHOLE pipeline to one Pallas flat-buffer "
                         "kernel per step (repro.optim.fuse; flat-resident "
                         "delayed rings in async mode)")
    ap.add_argument("--momentum", type=float, default=None,
                    help="heavy-ball mu (adds the trace link; defaults to 0.9 "
                         "when --fused is set; 0.0 is honored)")
    ap.add_argument("--checkpoint_dir", default=None,
                    help="full-fidelity checkpoint directory (enables saving)")
    ap.add_argument("--checkpoint_every", type=int, default=0,
                    help="save cadence in steps (requires --checkpoint_dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --checkpoint_dir "
                         "(bit-identical to the uninterrupted run)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint_dir")
    if args.checkpoint_every and not args.checkpoint_dir:
        ap.error("--checkpoint_every requires --checkpoint_dir")
    if args.checkpoint_dir and not args.checkpoint_every and not args.resume:
        ap.error(
            "--checkpoint_dir does nothing without --checkpoint_every N "
            "(to save) and/or --resume (to restore)"
        )
    mode = args.engine or ("async" if args.async_psgd else "sync")
    if args.trace_out and mode != "distributed":
        ap.error("--trace_out needs --engine distributed (live staleness capture)")
    if args.faults and mode != "distributed":
        ap.error("--faults needs --engine distributed (live fault injection)")
    if args.worker_timeout is not None and mode != "distributed":
        ap.error("--worker_timeout needs --engine distributed (server liveness)")
    # The live and simulated async engines share the MindTheStep pipeline;
    # only sync mode trains the plain chain.
    use_staleness = args.async_psgd or mode in ("async", "distributed")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    pipeline, adapt = mindthestep_pipeline(
        args.lr, args.workers, args.ring,
        momentum=args.momentum, fused=args.fused, staleness=use_staleness,
    )

    import jax

    from repro.async_engine.delayed import flat_size
    from repro.training import init_params

    # The size comes from shapes alone: the engine initializes the params
    # from the seed, so no second full-width copy stays alive in the spec.
    n_params = flat_size(
        jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(args.seed))
    )

    spec = RunSpec(
        cfg=cfg,
        pipeline=pipeline,
        mode=mode,
        num_steps=args.steps,
        batch_size=args.batch,
        seq_len=args.seq,
        num_workers=args.workers,
        ring=args.ring if mode == "async" else 0,
        ring_dtype=(
            None
            if args.ring_dtype is None
            else {"float32": jax.numpy.float32, "bfloat16": jax.numpy.bfloat16}[
                args.ring_dtype
            ]
        ),
        adapt=adapt,
        fuse=args.fuse,
        transport=args.transport,
        trace_path=args.trace_out,
        faults=args.faults,  # RunSpec parses the --faults string
        worker_timeout=args.worker_timeout,
        refresh_every=args.refresh_every,
        seed=args.seed,
    )

    print(f"arch={cfg.name} params={n_params / 1e6:.1f}M mode={mode} "
          f"fused={args.fused} fuse={args.fuse}")

    if args.resume:
        from repro.checkpoint import latest_step

        try:
            at = latest_step(args.checkpoint_dir)
        except FileNotFoundError:
            raise SystemExit(
                f"--resume: no checkpoint found under {args.checkpoint_dir!r} "
                "(no 'latest' pointer — did a previous run save with "
                "--checkpoint_every?)"
            ) from None
        if at > args.steps:
            raise SystemExit(
                f"--resume: checkpoint is at step {at} but --steps is "
                f"{args.steps}; pass --steps >= {at} to continue the run"
            )
        print(f"resuming at step {at} from {args.checkpoint_dir}")

    hooks = [LogHook(log_every=max(args.steps // 10, 1))]
    if args.checkpoint_dir and args.checkpoint_every:
        hooks.append(CheckpointHook(args.checkpoint_dir, every=args.checkpoint_every))
    result = run(
        spec,
        hooks=hooks,
        resume_from=args.checkpoint_dir if args.resume else None,
    )
    if not result.history:
        print(f"nothing to do: checkpoint already at step {result.step} "
              f"of {args.steps}")
        return
    if use_staleness and args.refresh_every:
        est = T.staleness_link(pipeline).estimator
        lam = est.fit("poisson").lam
        print(f"online estimator: lam={lam:.2f} (m={args.workers}), "
              f"n_seen={est.n_seen}")
    if args.trace_out:
        import numpy as np

        from repro.async_engine.events import load_trace
        from repro.core.staleness import fit_all_models

        taus, _who, t_pull, t_push = load_trace(
            args.trace_out, return_workers=True, return_times=True
        )
        fits = fit_all_models(taus, m=args.workers)
        name, (_, dist) = min(fits.items(), key=lambda kv: kv[1][1])
        latency = ""
        if t_pull is not None and len(taus):
            ms = float(np.mean(t_push - t_pull)) * 1e3
            latency = f"  latency mean={ms:.1f}ms"
        print(f"live trace: {len(taus)} updates -> {args.trace_out}  "
              f"tau mean={float(np.mean(taus)):.2f}{latency}  "
              f"best model={name} (Bhattacharyya {dist:.4f})")
    print(f"final loss: {result.history[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
