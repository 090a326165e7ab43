"""The dense decoder of global-attention layers, in plain float32.

Nothing here imports the program.  It follows the equations the program
states for these configurations:

* pre-norm decoder blocks: LayerNorm (scale, bias) or RMSNorm (1 + scale);
  attention with full rotary embeddings on every head dimension (split
  halves), grouped KV heads, causal softmax; gated SiLU feed-forward;
  sequential residual; final norm; a tied or untied unembedding;
* a vision configuration puts its image embeddings in front of the tokens
  and drops those positions before the head; the loss is the mean
  cross-entropy of the labelled text positions.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def weight_shapes(config: dict) -> dict:
    """The weight tree's leaf shapes: ``{path: (shape, init)}`` with ``init``
    one of ``("normal", scale)``, ``("ones",)``, ``("zeros",)``.

    The layers of the one-layer pattern are stacked on a leading axis, as
    the program's scanned stack keeps them.
    """
    if list(config.get("block_pattern", ["global"])) != ["global"] or config.get("num_experts"):
        raise ValueError("dense_decoder: only a dense decoder of global-attention layers")
    L, d, f = config["num_layers"], config["d_model"], config["d_ff"]
    hq, hkv, hd, V = config["num_heads"], config["num_kv_heads"], config["head_dim"], config["vocab_size"]

    def norm(prefix):
        if config["norm_type"] == "layernorm":
            return {f"{prefix}/scale": (prefix_shape(prefix, d), ("ones",)),
                    f"{prefix}/bias": (prefix_shape(prefix, d), ("zeros",))}
        return {f"{prefix}/scale": (prefix_shape(prefix, d), ("zeros",))}

    def prefix_shape(prefix, *shape):
        return ((L,) if prefix.startswith("stack/") else ()) + shape

    s = {
        "embed/embedding": ((V, d), ("normal", d**-0.5)),
        "stack/pos0/attn/wq": ((L, d, hq, hd), ("normal", d**-0.5)),
        "stack/pos0/attn/wk": ((L, d, hkv, hd), ("normal", d**-0.5)),
        "stack/pos0/attn/wv": ((L, d, hkv, hd), ("normal", d**-0.5)),
        "stack/pos0/attn/wo": ((L, hq, hd, d), ("normal", (hq * hd) ** -0.5)),
        "stack/pos0/mlp/w_up": ((L, d, f), ("normal", d**-0.5)),
        "stack/pos0/mlp/w_down": ((L, f, d), ("normal", f**-0.5)),
    }
    if config["gated_mlp"]:
        s["stack/pos0/mlp/w_gate"] = ((L, d, f), ("normal", d**-0.5))
    s.update(norm("stack/pos0/pre_norm"))
    s.update(norm("stack/pos0/mlp_pre_norm"))
    s.update(norm("final_norm"))
    if not config["tie_embeddings"]:
        s["unembed/embedding"] = ((V, d), ("normal", d**-0.5))
    return s


def _norm(config, p, x):
    eps = float(config["norm_eps"])
    if config["norm_type"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + p["scale"])


def _rotary(x, theta):
    """Full rotary embedding on (B, T, heads, hd): the two halves rotate."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _block(config, mm, p, x):
    B, T, _ = x.shape
    hq, hkv, hd = config["num_heads"], config["num_kv_heads"], config["head_dim"]
    theta = float(config["rope_theta"])
    h = _norm(config, p["pre_norm"], x)
    q = _rotary(mm("btd,dnh->btnh", h, p["attn"]["wq"]), theta) / math.sqrt(hd)
    k = _rotary(mm("btd,dnh->btnh", h, p["attn"]["wk"]), theta)
    v = mm("btd,dnh->btnh", h, p["attn"]["wv"])
    q = q.reshape(B, T, hkv, hq // hkv, hd)
    s = mm("bqngh,bknh->bngqk", q, k)
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm("bngqk,bknh->bqngh", a, v).reshape(B, T, hq, hd)
    x = x + mm("btnh,nhd->btd", o, p["attn"]["wo"])
    h = _norm(config, p["mlp_pre_norm"], x)
    up = mm("btd,df->btf", h, p["mlp"]["w_up"])
    if "w_gate" in p["mlp"]:
        up = jax.nn.silu(mm("btd,df->btf", h, p["mlp"]["w_gate"])) * up
    else:
        up = jax.nn.silu(up)
    return x + mm("btf,fd->btd", up, p["mlp"]["w_down"])


def loss(config, mm, params, batch):
    """Mean cross-entropy of the labelled text positions."""
    x = params["embed"]["embedding"][batch["tokens"]]
    n_prefix = 0
    if "prefix_embeds" in batch:
        n_prefix = batch["prefix_embeds"].shape[1]
        x = jnp.concatenate([batch["prefix_embeds"], x], axis=1)
    stack = params["stack"]["pos0"]

    def layer(x, p):
        return _block(config, mm, p, x), None

    # one layer at a time, recomputed in the backward pass, so the f32
    # activations of a whole stack are never held at once
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stack)
    x = _norm(config, params["final_norm"], x)[:, n_prefix:]
    head = params.get("unembed", params["embed"])["embedding"]
    logits = mm("btd,vd->btv", x, head)
    labels = batch["labels"]
    mask = labels >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(mask, lse - ll, 0.0)) / jnp.maximum(jnp.sum(mask), 1)
