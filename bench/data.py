"""Weights and batches made from ``--seed``, on the device, in one jit each.

The program receives only what is made here: the weight tree and a pool of
batches.  The reference reads the same weights and batches by calling these
functions again with the same seed.

The weights are made, packed and measured from their shapes alone: the
``{path: (shape, init)}`` that the configuration's model module gives
(``bench/models``, ``weight_shapes``).  Leaf ``i`` in sorted path order
draws from the seed's weight key folded with ``i``.

Batches follow ``repro.data.synthetic.lm_batches``: a planted bigram table
makes the traffic's ``bigram_follow`` share of the transitions
deterministic, the rest uniform; labels are the next token, ``-1`` at the
last position.  Here every row of the pool is drawn at once on the device
instead of position by position on the host.
A vision configuration adds ``num_prefix_embeddings`` standard-normal image
embeddings per row (the projector's output, one 448 x 448 tile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_STREAM_WEIGHTS, _STREAM_BATCHES = 1, 2


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key for one stream of ``seed``, all 64 bits of the seed kept."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def _make_weights(shapes: dict, key):
    out = {}
    for i, (path, (shape, init)) in enumerate(sorted(shapes.items())):
        if init[0] == "normal":
            k = jax.random.fold_in(key, i)
            out[path] = init[1] * jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
        elif init[0] == "ones":
            out[path] = jnp.ones(shape, jnp.float32)
        else:
            out[path] = jnp.zeros(shape, jnp.float32)
    return _nest(out)


def make_weights(shapes: dict, seed: int):
    """The f32 weight tree of ``shapes`` for ``seed``, made on the device in
    one jit."""
    key = seed_key(seed, _STREAM_WEIGHTS)
    return jax.jit(functools.partial(_make_weights, shapes))(key)


def _leaf(tree: dict, path: str):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def unflatten(shapes: dict, flat):
    """The weight tree over a packed ``(N,)`` buffer.

    The packing order is ``jax.tree.leaves`` order of the weight tree
    (sorted keys), which is the order the program packs it in.
    """
    out, off = {}, 0
    for path, (shape, _) in sorted(shapes.items()):
        size = int(np.prod(shape))
        out[path] = flat[off:off + size].reshape(shape)
        off += size
    return _nest(out)


def leaf_norms(shapes: dict, tree):
    """The f32 norm of each weight of a weight tree, a stacked weight once
    per layer: the weights whose norms ``correct`` compares."""
    out = []
    for path, (shape, _) in sorted(shapes.items()):
        sq = jnp.square(_leaf(tree, path).astype(jnp.float32))
        if path.startswith("stack/"):
            out.append(jnp.sqrt(jnp.sum(sq.reshape(shape[0], -1), axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(sq))[None])
    return jnp.concatenate(out)


def param_count(shapes: dict) -> int:
    return sum(int(np.prod(shape)) for shape, _ in shapes.values())


def _make_pool(config: dict, traffic: dict, key):
    P, B = int(traffic["pool"]), int(traffic["batch"])
    V = int(config["vocab_size"])
    prefix = int(config.get("num_prefix_embeddings") or 0)
    S = int(traffic["positions"]) - prefix
    follow = float(traffic["bigram_follow"])
    k_table, k_first, k_follow, k_rand, k_img = jax.random.split(key, 5)
    next_tok = jax.random.randint(k_table, (V,), 0, V, jnp.int32)
    first = jax.random.randint(k_first, (P * B,), 0, V, jnp.int32)
    follows = jax.random.uniform(k_follow, (S - 1, P * B)) < follow
    rand = jax.random.randint(k_rand, (S - 1, P * B), 0, V, jnp.int32)

    def step(prev, xs):
        f, r = xs
        tok = jnp.where(f, next_tok[prev], r)
        return tok, tok

    _, rest = jax.lax.scan(step, first, (follows, rand))
    tokens = jnp.concatenate([first[None], rest], axis=0).T.reshape(P, B, S)
    labels = jnp.concatenate([tokens[..., 1:], jnp.full((P, B, 1), -1, jnp.int32)], axis=-1)
    pool = {"tokens": tokens, "labels": labels}
    if prefix:
        d = int(config["d_model"])
        pool["prefix_embeds"] = jax.random.normal(k_img, (P, B, prefix, d), jnp.float32)
    return pool


def make_pool(config: dict, traffic: dict, seed: int) -> list[dict]:
    """``traffic["pool"]`` distinct batches for ``seed``, made in one jit and
    handed out as separate device arrays (no device work per tick)."""
    key = seed_key(seed, _STREAM_BATCHES)
    pool = jax.jit(functools.partial(_make_pool, config, traffic))(key)
    n = int(traffic["pool"])
    split = jax.jit(lambda t: [jax.tree.map(lambda x: x[i], t) for i in range(n)])
    return split(pool)
