"""Top-level model API — one entry point for every assigned architecture.

``init_model`` / ``forward`` / ``loss_fn`` / ``decode_step`` dispatch on the
config family:

* decoder-only (dense / moe / ssm / hybrid): embedding -> heterogeneous block
  stack (:mod:`repro.models.transformer`) -> final norm -> (tied) unembed.
* vlm: identical trunk; ``prefix_embeds`` (the vision-projector stub output,
  shape ``(B, P, D)``) are concatenated ahead of the token embeddings and
  excluded from the loss.
* audio (whisper): encoder-decoder in :mod:`repro.models.whisper`; the conv
  frontend stub supplies ``enc_embeds`` ``(B, T_enc, D)``.

A *batch* is a dict of arrays:
  ``tokens``        (B, S) int32   — always present
  ``labels``        (B, S) int32   — training only; ``-1`` masks a position
  ``prefix_embeds`` (B, P, D)      — vlm only
  ``enc_embeds``    (B, T_enc, D)  — audio only
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models import whisper as W
from repro.models.layers import (
    LayerIO,
    Params,
    apply_embedding,
    apply_layernorm,
    apply_rmsnorm,
    apply_unembed,
    dtype_of,
    init_embedding,
    init_layernorm,
    init_rmsnorm,
)

__all__ = [
    "init_model",
    "forward",
    "loss_fn",
    "init_decode_state",
    "decode_step",
    "prefill",
]


def _final_norm_init(cfg):
    return init_layernorm(cfg.d_model) if cfg.norm_type == "layernorm" else init_rmsnorm(cfg.d_model)


def _final_norm(cfg, p, x):
    fn = apply_layernorm if cfg.norm_type == "layernorm" else apply_rmsnorm
    return fn(p, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Init / forward
# ---------------------------------------------------------------------------

def init_model(key, cfg) -> Params:
    if cfg.is_encoder_decoder:
        return W.init_whisper(key, cfg)
    k1, k2 = jax.random.split(key)
    params: Params = {
        "embed": init_embedding(k1, cfg.vocab_size, cfg.d_model),
        "stack": T.init_stack(k2, cfg),
        "final_norm": _final_norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = init_embedding(jax.random.fold_in(key, 7), cfg.vocab_size, cfg.d_model)
    return params


def _embed_with_prefix(params, batch, cfg, act_dt):
    """Token embeddings, with optional vlm prefix; returns (x, positions, n_prefix)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = apply_embedding(params["embed"], tokens, scale=cfg.embed_scale, act_dtype=act_dt)
    n_prefix = 0
    if cfg.frontend == "vision" and "prefix_embeds" in batch:
        pre = batch["prefix_embeds"].astype(act_dt)
        n_prefix = pre.shape[1]
        x = jnp.concatenate([pre, x], axis=1)
    total = n_prefix + S
    positions = jnp.broadcast_to(jnp.arange(total)[None], (B, total))
    return x, positions, n_prefix


def forward(params: Params, batch: dict[str, Any], cfg) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence pass -> (logits (B, S, V), aux_loss scalar).

    For vlm configs the prefix positions are dropped from the logits so the
    output always aligns with ``batch["tokens"]``.
    """
    logits, stats = _forward(params, batch, cfg)
    return logits, stats["aux"]


def _forward(params, batch, cfg):
    """:func:`forward` with all the stack's stats (``transformer.zero_stats``)."""
    act_dt = dtype_of(cfg.activation_dtype)
    if cfg.is_encoder_decoder:
        memory = W.encode(params, batch["enc_embeds"].astype(act_dt), cfg)
        logits = W.decode_train(params, batch["tokens"], memory, cfg)
        return logits, {"aux": jnp.zeros((), jnp.float32)}

    x, positions, n_prefix = _embed_with_prefix(params, batch, cfg, act_dt)
    io = LayerIO(positions=positions, causal=True)
    x, stats = T.apply_stack(params["stack"], x, io, cfg)
    x = _final_norm(cfg, params["final_norm"], x)
    if n_prefix:
        x = x[:, n_prefix:]
    unembed = params.get("unembed", params["embed"])
    logits = apply_unembed(unembed, x, softcap=cfg.final_logit_softcap)
    return logits, stats


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Masked token-mean CE in float32. labels < 0 are ignored."""
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    return nll.sum() / denom, denom


def loss_fn(params: Params, batch: dict[str, Any], cfg) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """-> (loss, metrics): ``ce``, ``aux``, ``n_tokens``, and for a chip's
    share of experts the tick's counters ``moe_held_choices`` (token-choices
    routed to held experts, all layers) and ``moe_max_expert_load``."""
    logits, stats = _forward(params, batch, cfg)
    aux = stats["aux"]
    labels = batch.get("labels")
    if labels is None:  # next-token objective derived from tokens
        labels = jnp.concatenate(
            [batch["tokens"][:, 1:], -jnp.ones_like(batch["tokens"][:, :1])], axis=1
        )
    ce, n_tok = cross_entropy(logits, labels)
    loss = ce + cfg.router_aux_coef * aux if cfg.num_experts else ce
    return loss, {"ce": ce, "n_tokens": n_tok, **stats}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_decode_state(params: Params, cfg, batch_size: int, capacity: int, *,
                      cache_dtype=jnp.bfloat16, batch: dict[str, Any] | None = None) -> Params:
    """Fresh decode cache sized for ``capacity`` positions.

    Whisper needs the encoder memory (projected cross-KV), so ``batch`` with
    ``enc_embeds`` must be supplied for encoder-decoder configs.
    """
    if cfg.is_encoder_decoder:
        assert batch is not None and "enc_embeds" in batch
        act_dt = dtype_of(cfg.activation_dtype)
        memory = W.encode(params, batch["enc_embeds"].astype(act_dt), cfg)
        return W.init_whisper_cache(params, memory, cfg, capacity, cache_dtype)
    return T.init_stack_cache(cfg, batch_size, capacity, cache_dtype)


def decode_step(params: Params, cache: Params, token: jnp.ndarray, pos, cfg):
    """One decode step. token: (B,) int32; pos: scalar int (absolute position).

    Returns (logits (B, V), new_cache).
    """
    if cfg.is_encoder_decoder:
        return W.whisper_decode_step(params, cache, token, pos, cfg)
    act_dt = dtype_of(cfg.activation_dtype)
    x = apply_embedding(params["embed"], token[:, None], scale=cfg.embed_scale, act_dtype=act_dt)
    x, new_cache = T.apply_stack_step(params["stack"], x, cache, jnp.asarray(pos, jnp.int32), cfg)
    x = _final_norm(cfg, params["final_norm"], x)
    unembed = params.get("unembed", params["embed"])
    logits = apply_unembed(unembed, x[:, 0], softcap=cfg.final_logit_softcap)
    return logits, new_cache


def prefill(params: Params, batch: dict[str, Any], cfg, capacity: int, *,
            cache_dtype=jnp.bfloat16):
    """Process a prompt, returning (last-position logits, decode cache)."""
    act_dt = dtype_of(cfg.activation_dtype)
    if cfg.is_encoder_decoder:
        memory = W.encode(params, batch["enc_embeds"].astype(act_dt), cfg)
        logits = W.decode_train(params, batch["tokens"], memory, cfg)
        cache = W.init_whisper_cache(params, memory, cfg, capacity, cache_dtype)
        return logits[:, -1], cache

    x, positions, n_prefix = _embed_with_prefix(params, batch, cfg, act_dt)
    io = LayerIO(positions=positions, causal=True)
    x, cache = T.prefill_stack(params["stack"], x, io, cfg, capacity, cache_dtype)
    x = _final_norm(cfg, params["final_norm"], x)
    unembed = params.get("unembed", params["embed"])
    logits = apply_unembed(unembed, x[:, -1], softcap=cfg.final_logit_softcap)
    return logits, cache
