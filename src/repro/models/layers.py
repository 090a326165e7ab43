"""Shared neural-net building blocks (pure-function style, explicit pytrees).

Parameters are nested dicts of ``jnp.ndarray``; every constructor returns
``(params, apply_fn)``-style pairs via module-level ``init_*`` / ``apply_*``
functions so the whole model stays a transparent pytree (no framework dep).
Sharding is applied *by name* through :mod:`repro.sharding.specs`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.ctx import shard_activation

Params = dict[str, Any]


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[name]


def truncated_normal(key, shape, scale, dtype=jnp.float32):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.zeros((d,), dtype)}  # gemma-style (1 + scale) parameterization


def apply_rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(dt)


def init_layernorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def apply_layernorm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype=jnp.float32) -> Params:
    return {"embedding": truncated_normal(key, (vocab, d), 1.0 / np.sqrt(d), dtype)}


def apply_embedding(p: Params, tokens: jnp.ndarray, *, scale: bool, act_dtype) -> jnp.ndarray:
    emb = p["embedding"].astype(act_dtype)
    x = jnp.take(emb, tokens, axis=0)
    if scale:
        x = x * jnp.asarray(np.sqrt(emb.shape[-1]), act_dtype)
    return shard_activation(x, ("batch", "seq", None))


def apply_unembed(p: Params, x: jnp.ndarray, *, softcap: float | None) -> jnp.ndarray:
    logits = jnp.einsum("...d,vd->...v", x, p["embedding"].astype(x.dtype))
    logits = shard_activation(logits, ("batch", "seq", "vocab"))
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True)}[name]


def init_mlp(key, d: int, f: int, gated: bool, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {
        "w_up": truncated_normal(k1, (d, f), 1.0 / np.sqrt(d), dtype),
        "w_down": truncated_normal(k2, (f, d), 1.0 / np.sqrt(f), dtype),
    }
    if gated:
        p["w_gate"] = truncated_normal(k3, (d, f), 1.0 / np.sqrt(d), dtype)
    return p


def apply_mlp(p: Params, x: jnp.ndarray, act: str) -> jnp.ndarray:
    dt = x.dtype
    up = jnp.einsum("...d,df->...f", x, p["w_up"].astype(dt))
    if "w_gate" in p:
        gate = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(dt))
        h = _act(act)(gate) * up
    else:
        h = _act(act)(up)
    h = shard_activation(h, ("batch", "seq", "ff"))
    return jnp.einsum("...f,fd->...d", h, p["w_down"].astype(dt))


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN's inverse frequencies (deepseek-v2's ``DeepseekV2YarnRotaryEmbedding``):
    the plain ones above the correction range of ``beta_fast`` rotations,
    divided by ``factor`` below that of ``beta_slow``, a linear ramp between."""
    def correction_dim(rotations):
        return head_dim * math.log(yarn.original_max_position_embeddings / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    extra = rope_frequencies(head_dim, theta)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float, yarn=None) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    ``yarn`` (a ``configs.Yarn``) scales the frequencies and cos/sin."""
    hd = x.shape[-1]
    inv = rope_frequencies(hd, theta) if yarn is None else yarn_frequencies(hd, theta, yarn)
    freqs = jnp.asarray(inv, jnp.float32)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    if yarn is not None:
        m = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> np.ndarray:
    """Whisper-style fixed sinusoidal position embedding table."""
    pos = np.arange(n)[:, None].astype(np.float64)
    dim = np.arange(0, d, 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, dim / d)
    out = np.zeros((n, d), dtype=np.float32)
    out[:, 0::2] = np.sin(angle)
    out[:, 1::2] = np.cos(angle)
    return out


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

def softcap(x: jnp.ndarray, cap: float | None) -> jnp.ndarray:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


@dataclasses.dataclass(frozen=True)
class LayerIO:
    """What a mixing layer needs to know about the token geometry."""

    positions: jnp.ndarray  # (batch, seq) absolute positions
    causal: bool = True
    window: int | None = None  # sliding-window size for "local" layers
