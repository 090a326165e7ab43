"""Grouped matmuls of one held-expert layer, forward and backward, three ways.

    PYTHONPATH=src python -m benchmarks.grouped_matmul_bench [--reps 20] [--seed 0]

The expert layer of the ``dsv2lite-async-2x4096`` cell: 2 x 4096 tokens,
top-6 of 64 experts, experts 0-7 held, d_model 2048, experts 1408 wide,
bf16.  A seeded random router gives each held expert its rows, in the
order ``models.moe`` sorts them, with the rows of absent experts as a last
group.  The gate, up and down products and their gradients are timed
through the Pallas megablox ``gmm`` with the layer's tiling
(``models.moe._gmm_tiling``), through ``gmm`` with 128 tiles, and through
``lax.ragged_dot``: one JSON line a way, with the median and least time of
``--reps`` calls after one compiling call.  Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

TOKENS, TOP_K, EXPERTS, HELD, D_MODEL, D_EXPERT = 8192, 6, 64, 8, 2048, 1408


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from repro.models.moe import _gmm_tiling

    if jax.devices()[0].platform != "tpu":
        print("grouped_matmul_bench: needs a TPU", file=sys.stderr)
        return 2
    k = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    x = jax.random.normal(k[0], (TOKENS, D_MODEL), jnp.float32)
    router = jax.random.normal(k[1], (D_MODEL, EXPERTS), jnp.float32) / D_MODEL**0.5
    choice = jax.lax.top_k(x @ router, TOP_K)[1].reshape(-1)
    slot = jnp.where(choice < HELD, choice, HELD)
    order = jnp.argsort(slot, stable=True)
    sizes = jnp.bincount(slot, length=HELD + 1).astype(jnp.int32)
    rows = x.astype(jnp.bfloat16)[order // TOP_K]
    scale = (D_MODEL**-0.5, D_MODEL**-0.5, D_EXPERT**-0.5)
    shapes = ((HELD, D_MODEL, D_EXPERT), (HELD, D_MODEL, D_EXPERT), (HELD, D_EXPERT, D_MODEL))
    w = tuple((jax.random.normal(key, s, jnp.float32) * c).astype(jnp.bfloat16)
              for key, s, c in zip(k[2:], shapes, scale))

    ways = {
        "gmm_layer_tiling": lambda a, b: megablox.gmm(a, b, sizes, a.dtype, _gmm_tiling),
        "gmm_128": lambda a, b: megablox.gmm(a, b, sizes, a.dtype, (128, 128, 128)),
        "ragged_dot": lambda a, b: jax.lax.ragged_dot(a, b, sizes[:-1], preferred_element_type=a.dtype),
    }
    for name, mm in ways.items():
        def layer(w, rows, mm=mm):
            h = jax.nn.silu(mm(rows, w[0])) * mm(rows, w[1])
            return jnp.sum(mm(h, w[2]).astype(jnp.float32) ** 2)

        fn = jax.jit(jax.value_and_grad(layer, argnums=(0, 1)))
        t0 = time.perf_counter()
        value, grads = jax.block_until_ready(fn(w, rows))
        compile_s = time.perf_counter() - t0
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(w, rows))
            times.append((time.perf_counter() - t0) * 1e3)
        print(json.dumps({
            "way": name, "held_rows": int(jnp.sum(sizes[:HELD])), "compile_s": compile_s,
            "ms_median": statistics.median(times), "ms_min": min(times), "value": float(value),
            "grad_norms": [float(jnp.linalg.norm(g.astype(jnp.float32))) for g in grads[0]],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
