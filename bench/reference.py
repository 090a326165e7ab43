"""Plain reference: the training recipe around a model's loss and gradient,
the delayed ring, the alpha(tau)-weighted combine and the momentum apply,
in float32.

Nothing here imports the program.  The model is the module of
``bench/models`` that the configuration names (its ``model`` key): its
weight shapes and its loss.  The recipe follows the equations the program
states:

* async (paper eq. 4, Algorithm 1 as delayed gradients): each tick pushes
  the gradient into a K-slot ring in the ring's dtype, draws W staleness
  values from a Poisson(W) law truncated to the ring, weights the ring row
  of worker w by alpha(tau_w) / (lr W) when it exists, and applies
  ``v <- mu v - lr sum_w weight_w row_w``, ``p <- p + v``;
* sync: ``v <- mu v - lr g``, ``p <- p + v``.

alpha(tau) starts as eq. 17 (MindTheStep, Poisson staleness with lambda = W
and K = lr), normalised so its mean under the ring-truncated law is lr (eq.
26), clipped at 5 lr and zero above tau = 150: the recipe the configuration's
traffic names.  After every ``refresh_every`` ticks it is refit the same way
from the draws: lambda their mean, normalised under their histogram (the
sampler's law stays as it was).  The staleness draws use the run's PRNG stream: the tick's
key is split from the one that ``jax.random.split(PRNGKey(seed))[1]``
starts, one split per tick, and ``tau_w`` is the inverse CDF at a uniform
draw.

Matrix products run at ``highest`` precision.  ``low`` (``"fp8"``) rounds
every operand of every matrix product, forward and backward, to float8 e4m3
with a per-tensor scale, accumulating in float32: the control that
``correct`` must refuse.  ``fault="half_batch"`` drops the labels of
the second half of each row's text positions (the mean over the rest),
for any model.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.data import leaf_norms, make_weights

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Precision of the matrix operands
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 with a per-tensor scale onto its largest value."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HIGHEST)


def _einsum_fp8_fwd(spec, a, b):
    a8, b8 = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a8, b8, precision=HIGHEST), (a8, b8)


def _einsum_fp8_bwd(spec, saved, ct):
    # the backward products take fp8 operands too: the saved forward ones
    # and the incoming cotangent, each on its own scale
    _, vjp = jax.vjp(lambda a, b: jnp.einsum(spec, a, b, precision=HIGHEST), *saved)
    return vjp(_fp8(ct))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def _mm(low):
    if low == "fp8":
        return _einsum_fp8

    def einsum(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    return einsum


# ---------------------------------------------------------------------------
# The model's loss, under the recipe's precision and fault
# ---------------------------------------------------------------------------

def _fault(batch, fault):
    """``"half_batch"`` drops the labels of the second half of each row's
    text positions."""
    if fault != "half_batch":
        return batch
    labels = batch["labels"]
    keep = np.arange(labels.shape[1]) < labels.shape[1] // 2
    return dict(batch, labels=jnp.where(keep, labels, -1))


def loss(model, config, params, batch, *, low=None, fault=None):
    """The loss of ``model`` (a module of ``bench/models``) for one batch,
    its matrix products at the highest precision or at ``low``."""
    return model.loss(config, _mm(low), params, _fault(batch, fault))


# ---------------------------------------------------------------------------
# Staleness recipe: the law the ticks draw from and alpha(tau)
# ---------------------------------------------------------------------------

ALPHA_CLIP = 5.0  # alpha(tau) <= 5 lr (paper, section VI)
TAU_DROP = 150  # no step above this staleness (paper, section VI)
NORMALISE_ROUNDS = 8  # normalise-then-clip rounds at most (eq. 26 against the clip)
LAMBDA_FLOOR = 1e-3  # the refit's Poisson rate when every draw was 0


def poisson_pmf(lam: float, n: int) -> np.ndarray:
    k = np.arange(n, dtype=np.float64)
    log = k * math.log(lam) - np.array([math.lgamma(i + 1.0) for i in k]) - lam
    return np.exp(log)


def tau_cdf(workers: int, ring: int) -> np.ndarray:
    """Inverse-CDF table of Poisson(W) truncated to the ring's K slots."""
    p = poisson_pmf(float(workers), ring)
    return np.cumsum(p / p.sum()).astype(np.float32)


def alpha_table(lr: float, lam: float, pmf: np.ndarray, tau_max: int) -> np.ndarray:
    """alpha(tau) for tau in [0, tau_max]: eq. 17 for Poisson(``lam``)
    staleness with K = lr (the implicit momentum in step-size units),
    negative steps set to 0, clipped at ``ALPHA_CLIP`` lr, zero above
    ``TAU_DROP``; then normalised (eq. 26) so its mean under ``pmf`` (over
    tau = 0, 1, ...) is lr and clipped again, until the clip changes nothing
    or ``NORMALISE_ROUNDS`` rounds have run (normalising raises the mean
    that each clip lowers)."""
    taus = np.arange(tau_max + 1, dtype=np.float64)
    lgam = np.array([math.lgamma(t + 1.0) for t in taus])
    below = np.concatenate([[0.0], np.cumsum(np.exp(taus * math.log(lam) - lgam - lam))[:-1]])
    c = 1.0 - below  # 1 - (K / lr) P[Poisson(lam) < tau], K = lr
    table = c * np.exp(-taus * math.log(lam) + lgam) * lr
    table = np.clip(np.maximum(table, 0.0), 0.0, ALPHA_CLIP * lr)
    table[TAU_DROP + 1:] = 0.0
    pmf = np.asarray(pmf, np.float64)
    for _ in range(NORMALISE_ROUNDS):
        table = table * (lr * pmf.sum() / float(np.sum(pmf * table[:len(pmf)])))
        clipped = np.clip(table, 0.0, ALPHA_CLIP * lr)
        done = np.allclose(clipped, table, rtol=1e-6, atol=0)
        table = clipped
        if done:
            break
    return table


def initial_table(lr: float, workers: int, ring: int) -> np.ndarray:
    """The run's first alpha(tau): Poisson(W), normalised under the law the
    ticks draw from (Poisson(W) truncated to the ring)."""
    return alpha_table(lr, float(workers), poisson_pmf(float(workers), ring), 4 * ring)


def refit_table(lr: float, taus: np.ndarray, ring: int) -> np.ndarray:
    """alpha(tau) refit from every draw so far: Poisson at their mean,
    normalised under their histogram."""
    counts = np.bincount(np.ravel(taus), minlength=4 * ring + 1).astype(np.float64)
    lam = max(float(np.mean(taus)), LAMBDA_FLOOR)
    return alpha_table(lr, lam, counts, 4 * ring)


def tick_taus(seed: int, ticks: int, workers: int, ring: int) -> np.ndarray:
    """(ticks, W) staleness draws of the run's first ``ticks`` ticks."""
    cdf = jnp.asarray(tau_cdf(workers, ring))
    _, rng = jax.random.split(jax.random.PRNGKey(seed))
    out = []
    for _ in range(ticks):
        rng, sub = jax.random.split(rng)
        u = jax.random.uniform(sub, (workers,))
        out.append(np.asarray(jnp.searchsorted(cdf, u)))
    return np.stack(out).astype(np.int64)


def combine_weights(taus: np.ndarray, t: int, table: np.ndarray, lr: float, workers: int):
    """Per-row weights of tick ``t`` (0-based): ``{tick whose gradient the
    row holds: weight}``; a draw that reaches before the first tick selects
    no row."""
    out: dict[int, float] = {}
    for tau in taus:
        if t - tau < 0:
            continue
        w = np.float32(table[min(tau, len(table) - 1)]) / np.float32(lr * workers)
        out[t - tau] = out.get(t - tau, 0.0) + float(w)
    return out


# ---------------------------------------------------------------------------
# Following the first ticks
# ---------------------------------------------------------------------------

def follow(model, config, traffic, batches, *, seed, low=None, fault=None):
    """Run the reference of ``model`` over one tick per batch of ``batches``,
    from the seed's weights.

    Returns host arrays: ``losses`` (one per tick), ``grad_norms`` (per
    weight, of the first gradient), ``change_norms`` (per weight, of the
    params' change over the ticks).  Async refits alpha(tau) after every
    ``refresh_every`` ticks, as the run does.
    """
    lr, mu = float(traffic["lr"]), float(traffic["momentum"])
    engine = traffic["engine"]
    W, K = int(traffic.get("workers", 1)), int(traffic.get("ring", 0))
    refit_every = int(traffic.get("refresh_every") or 0)
    ticks = len(batches)
    shapes = model.weight_shapes(config)
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(loss, model, config, low=low, fault=fault)))
    norms = jax.jit(functools.partial(leaf_norms, shapes))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def apply(p, v, u):
        v = jax.tree.map(lambda v, u: mu * v + u, v, u)
        return jax.tree.map(jnp.add, p, v), v

    @jax.jit
    def combine(rows, weights):
        acc = jax.tree.map(lambda r: weights[0] * r.astype(jnp.float32), rows[0])
        for r, w in zip(rows[1:], weights[1:]):
            acc = jax.tree.map(lambda a, r: a + w * r.astype(jnp.float32), acc, r)
        return jax.tree.map(lambda a: -lr * a, acc)

    @jax.jit
    def change(p, p0):
        return leaf_norms(shapes, jax.tree.map(jnp.subtract, p, p0))

    if engine != "sync":
        taus = tick_taus(seed, ticks, W, K)
        table = initial_table(lr, W, K).astype(np.float32)
        ring_dtype = jnp.dtype(traffic["ring_dtype"])
    p = make_weights(shapes, seed)
    v = jax.tree.map(jnp.zeros_like, p)
    ring: dict[int, object] = {}  # tick -> its gradient, while a later tick can read it
    losses, grad_norms = [], None
    for t in range(ticks):
        value, g = grad_fn(p, batches[t])
        losses.append(value)
        if t == 0:
            grad_norms = norms(g)
        if engine == "sync":
            u = jax.tree.map(lambda g: -lr * g, g)
        else:
            ring[t] = jax.tree.map(lambda g: g.astype(ring_dtype), g)
            weights = combine_weights(taus[t], t, table, lr, W)
            if not weights:
                u = jax.tree.map(jnp.zeros_like, g)
            else:
                rows = sorted(weights)
                u = combine([ring[r] for r in rows], jnp.asarray([weights[r] for r in rows], jnp.float32))
            ring.pop(t - K + 1, None)  # the next tick reaches back K - 1 at most
            if refit_every and (t + 1) % refit_every == 0:
                table = refit_table(lr, taus[: t + 1], K).astype(np.float32)
        del g
        p, v = apply(p, v, u)
        del u
    del v, ring
    delta = change(p, make_weights(shapes, seed))
    return {
        "losses": np.asarray(jnp.stack(losses), np.float64),
        "grad_norms": np.asarray(grad_norms, np.float64),
        "change_norms": np.asarray(delta, np.float64),
    }
