"""Decoder trunk: heterogeneous blocks + scan-over-pattern-periods.

A config's ``block_pattern`` (e.g. gemma3's 5×local + 1×global, or
recurrentgemma's recurrent/recurrent/local) defines one *period*; the stack is
``num_periods`` scanned repetitions of the period (params stacked on a leading
axis, MaxText-style, for O(period) compile time) plus unrolled remainder
layers.  A config with ``first_k_dense_replace`` puts that many dense-MLP
layers in front (deepseek-v2), stacked and scanned the same way.  Every
block is pre-norm residual; gemma2/3 add post-norms.

Each layer type owns its decode cache:
  global     -> full KV cache (capacity = max sequence)
  local      -> ring KV cache (capacity = window)
  ssm        -> (conv ring, ssm state)
  recurrent  -> (conv ring, lru state)
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import rglru as R
from repro.models import ssm as S
from repro.models.layers import (
    LayerIO,
    Params,
    apply_layernorm,
    apply_mlp,
    apply_rmsnorm,
    init_layernorm,
    init_mlp,
    init_rmsnorm,
)

# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------

def _norm_init(cfg):
    return init_layernorm(cfg.d_model) if cfg.norm_type == "layernorm" else init_rmsnorm(cfg.d_model)


def _norm(cfg, p, x):
    fn = apply_layernorm if cfg.norm_type == "layernorm" else apply_rmsnorm
    return fn(p, x, cfg.norm_eps)


def init_block(key, layer_type: str, cfg, *, dense: bool = False) -> Params:
    """``dense``: a dense MLP even where the config has experts (the leading
    layers of ``first_k_dense_replace``)."""
    ks = jax.random.split(key, 4)
    p: Params = {"pre_norm": _norm_init(cfg)}
    if layer_type in ("global", "local") and cfg.kv_lora_rank:
        p["attn"] = A.init_mla(ks[0], cfg)
    elif layer_type in ("global", "local"):
        p["attn"] = A.init_attention(ks[0], cfg)
    elif layer_type == "ssm":
        p["ssm"] = S.init_ssm(ks[0], cfg)
        return p  # mamba block has no separate MLP
    elif layer_type == "recurrent":
        p["rglru"] = R.init_rglru(ks[0], cfg)
    else:
        raise ValueError(f"unknown layer type {layer_type!r}")
    if cfg.use_post_norms:
        p["post_norm"] = _norm_init(cfg)
    p["mlp_pre_norm"] = _norm_init(cfg)
    if cfg.num_experts and not dense:
        p["moe"] = MOE.init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp)
    if cfg.use_post_norms:
        p["mlp_post_norm"] = _norm_init(cfg)
    return p


def _window_for(layer_type: str, cfg) -> int | None:
    return cfg.window_size if layer_type == "local" else None


def zero_stats(cfg) -> dict:
    """What a stack's layers add up besides the residual stream: the router
    load-balance loss (``aux``), and for a chip's share of experts its
    counters (token-choices routed to held experts, summed over layers;
    the largest held expert's load, the max over layers)."""
    stats = {"aux": jnp.zeros((), jnp.float32)}
    if cfg.experts_held:
        stats["moe_held_choices"] = jnp.zeros((), jnp.int32)
        stats["moe_max_expert_load"] = jnp.zeros((), jnp.int32)
    return stats


def add_stats(total: dict, new: dict) -> dict:
    out = dict(total)
    for k, v in new.items():
        out[k] = jnp.maximum(total[k], v) if k == "moe_max_expert_load" else total[k] + v
    return out


def apply_block(p: Params, x: jnp.ndarray, layer_type: str, io: LayerIO, cfg, *, dense: bool = False):
    """Full-sequence (train/prefill-without-cache) path -> (x, stats), the
    stats a dict of :func:`zero_stats`' keys (``aux`` alone unless the
    block holds a share of experts)."""
    from repro.sharding.ctx import shard_activation

    stats = {"aux": jnp.zeros((), jnp.float32)}
    if cfg.sequence_parallel:
        # residual stream seq-sharded over `model` between mixers (Megatron
        # SP): norms/elementwise run on 1/|model| of the tokens, XLA places
        # all-gather before q/k/v and reduce-scatter after wo / w_down.
        x = shard_activation(x, ("batch", "seq_sp", None))
    pre = _norm(cfg, p["pre_norm"], x)
    if layer_type in ("global", "local") and cfg.kv_lora_rank:
        with jax.named_scope("mla"):
            h = A.mla_layer(p["attn"], pre, io, cfg)
    elif layer_type in ("global", "local"):
        h = A.attention_layer(p["attn"], pre, io, cfg, window=_window_for(layer_type, cfg),
                              use_rope=cfg.use_rope)
    elif layer_type == "ssm":
        h = S.apply_ssm(p["ssm"], pre, cfg)
        return x + h, stats
    elif layer_type == "recurrent":
        h = R.apply_rglru(p["rglru"], pre, cfg)
    if cfg.use_post_norms:
        h = _norm(cfg, p["post_norm"], h)

    if cfg.parallel_residual:
        m_in = pre
    else:
        x = x + h
        m_in = _norm(cfg, p["mlp_pre_norm"], x)
    if cfg.num_experts and not dense and cfg.experts_held:
        with jax.named_scope("moe"):
            m, aux, counts = MOE.apply_held_moe(p["moe"], m_in, cfg)
        stats = {"aux": aux, **counts}
    elif cfg.num_experts and not dense:
        m, stats["aux"] = MOE.apply_moe(p["moe"], m_in, cfg)
    else:
        m = apply_mlp(p["mlp"], m_in, cfg.act)
    if cfg.use_post_norms:
        m = _norm(cfg, p["mlp_post_norm"], m)
    x = (x + h + m) if cfg.parallel_residual else (x + m)
    if cfg.sequence_parallel:
        x = shard_activation(x, ("batch", "seq_sp", None))
    return x, stats


# ---------------------------------------------------------------------------
# Decode-step block (single token, threaded cache)
# ---------------------------------------------------------------------------

def init_block_cache(layer_type: str, batch: int, capacity: int, cfg, dtype) -> Params:
    if layer_type == "global":
        return A.init_kv_cache(batch, capacity, cfg.num_kv_heads, cfg.head_dim, dtype)
    if layer_type == "local":
        cap = min(cfg.window_size, capacity)
        return A.init_kv_cache(batch, cap, cfg.num_kv_heads, cfg.head_dim, dtype)
    if layer_type == "ssm":
        return S.init_ssm_cache(batch, cfg, dtype)
    if layer_type == "recurrent":
        return R.init_rglru_cache(batch, cfg, dtype)
    raise ValueError(layer_type)


def _attn_decode(p, x, cache, layer_type, pos, cfg):
    """Project one token, update cache, attend."""
    dt = x.dtype
    B = x.shape[0]
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"].astype(dt))
    qpos = jnp.broadcast_to(pos[None, None], (B, 1))
    if cfg.use_rope:
        q = A.apply_rope(q, qpos, cfg.rope_theta)
        k = A.apply_rope(k, qpos, cfg.rope_theta)
    scale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5
    q = q * jnp.asarray(scale, dt)
    ring = layer_type == "local"
    cache = (A.update_cache_ring if ring else A.update_cache_full)(cache, k, v, pos)
    cap = cache["k"].shape[1]
    cpos_fn = A.cache_positions_ring if ring else A.cache_positions_full
    cpos = cpos_fn(cap, pos + 1, B)
    out = A.decode_attention(
        q, cache["k"], cache["v"], cpos, qpos,
        window=_window_for(layer_type, cfg), softcap=cfg.attn_logit_softcap,
    )
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(dt)), cache


def apply_block_step(p: Params, x: jnp.ndarray, cache, layer_type: str, pos, cfg):
    """x: (B, 1, D), pos: scalar absolute position -> (x, new_cache)."""
    pre = _norm(cfg, p["pre_norm"], x)
    if layer_type in ("global", "local"):
        h, cache = _attn_decode(p["attn"], pre, cache, layer_type, pos, cfg)
    elif layer_type == "ssm":
        h, cache = S.apply_ssm_step(p["ssm"], pre, cache, cfg)
        return x + h, cache
    elif layer_type == "recurrent":
        h, cache = R.apply_rglru_step(p["rglru"], pre, cache, cfg)
    if cfg.use_post_norms:
        h = _norm(cfg, p["post_norm"], h)

    if cfg.parallel_residual:
        m_in = pre
    else:
        x = x + h
        m_in = _norm(cfg, p["mlp_pre_norm"], x)
    if cfg.num_experts:
        m, _ = MOE.apply_moe(p["moe"], m_in, cfg)
    else:
        m = apply_mlp(p["mlp"], m_in, cfg.act)
    if cfg.use_post_norms:
        m = _norm(cfg, p["mlp_post_norm"], m)
    x = (x + h + m) if cfg.parallel_residual else (x + m)
    return x, cache


def prefill_block_cache(p: Params, x: jnp.ndarray, layer_type: str, io: LayerIO, cfg, capacity: int, cache_dtype):
    """Full-sequence pass that also emits the decode cache."""
    aux_x, _ = apply_block(p, x, layer_type, io, cfg)
    if layer_type in ("global", "local"):
        dt = x.dtype
        pre = _norm(cfg, p["pre_norm"], x)
        k = jnp.einsum("btd,dnh->btnh", pre, p["attn"]["wk"].astype(dt))
        v = jnp.einsum("btd,dnh->btnh", pre, p["attn"]["wv"].astype(dt))
        if cfg.use_rope:
            k = A.apply_rope(k, io.positions, cfg.rope_theta)
        ring = layer_type == "local"
        cap = min(cfg.window_size, capacity) if ring else capacity
        cache = A.fill_cache_from_prefill(k.astype(cache_dtype), v.astype(cache_dtype), cap, ring)
        return aux_x, cache
    if layer_type == "ssm":
        pre = _norm(cfg, p["pre_norm"], x)
        _, cache = S.ssm_prefill_cache(p["ssm"], pre, cfg, cache_dtype)
        return aux_x, cache
    if layer_type == "recurrent":
        pre = _norm(cfg, p["pre_norm"], x)
        _, cache = R.rglru_prefill_cache(p["rglru"], pre, cfg, cache_dtype)
        return aux_x, cache
    raise ValueError(layer_type)


# ---------------------------------------------------------------------------
# Stack: scan over periods + unrolled remainder
# ---------------------------------------------------------------------------

def _require_decodable(cfg):
    if cfg.kv_lora_rank or cfg.first_k_dense_replace:
        raise NotImplementedError(
            f"{cfg.name}: latent attention and leading dense layers run on the "
            "full-sequence (training) path only; there is no decode cache for them"
        )


def init_stack(key, cfg) -> Params:
    """Params of the stack: ``lead`` (the ``first_k_dense_replace`` dense
    layers, stacked), ``pos{j}`` (the pattern's periods, stacked) or
    ``layer{i}``, and ``rem{i}`` (remainder layers)."""
    pattern = cfg.block_pattern
    n_per = cfg.num_periods
    params: Params = {}
    if cfg.first_k_dense_replace:
        keys = jax.random.split(jax.random.fold_in(key, 30_000), cfg.first_k_dense_replace)
        layers = [init_block(k, "global", cfg, dense=True) for k in keys]
        params["lead"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    if cfg.scan_layers and n_per > 0:
        for j, t in enumerate(pattern):
            keys = jax.random.split(jax.random.fold_in(key, j), n_per)
            layers = [init_block(k, t, cfg) for k in keys]
            params[f"pos{j}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    else:
        for i, t in enumerate(pattern * n_per):
            params[f"layer{i}"] = init_block(jax.random.fold_in(key, 10_000 + i), t, cfg)
    for i, t in enumerate(cfg.remainder_layers):
        params[f"rem{i}"] = init_block(jax.random.fold_in(key, 20_000 + i), t, cfg)
    return params


def apply_stack(params: Params, x: jnp.ndarray, io: LayerIO, cfg):
    """-> (x, stats): the summed stats of :func:`zero_stats`."""
    pattern = cfg.block_pattern
    stats = zero_stats(cfg)

    if cfg.first_k_dense_replace:
        def lead(carry, layer_params):
            x, st = carry
            x, a = apply_block(layer_params, x, "global", io, cfg, dense=True)
            return (x, add_stats(st, a)), None

        body = jax.checkpoint(lead) if cfg.remat else lead
        (x, stats), _ = jax.lax.scan(body, (x, stats), params["lead"])

    if cfg.scan_layers and cfg.num_periods > 0:
        stacked = {f"pos{j}": params[f"pos{j}"] for j in range(len(pattern))}

        def period(carry, period_params):
            x, st = carry
            for j, t in enumerate(pattern):
                x, a = apply_block(period_params[f"pos{j}"], x, t, io, cfg)
                st = add_stats(st, a)
            return (x, st), None

        body = jax.checkpoint(period) if cfg.remat else period
        (x, stats), _ = jax.lax.scan(body, (x, stats), stacked)
    else:
        for i, t in enumerate(pattern * cfg.num_periods):
            x, a = apply_block(params[f"layer{i}"], x, t, io, cfg)
            stats = add_stats(stats, a)
    for i, t in enumerate(cfg.remainder_layers):
        x, a = apply_block(params[f"rem{i}"], x, t, io, cfg)
        stats = add_stats(stats, a)
    return x, stats


def init_stack_cache(cfg, batch: int, capacity: int, dtype) -> Params:
    _require_decodable(cfg)
    pattern = cfg.block_pattern
    cache: Params = {}
    if cfg.scan_layers and cfg.num_periods > 0:
        for j, t in enumerate(pattern):
            one = init_block_cache(t, batch, capacity, cfg, dtype)
            cache[f"pos{j}"] = jax.tree.map(
                lambda l: jnp.broadcast_to(l[None], (cfg.num_periods,) + l.shape), one
            )
    else:
        for i, t in enumerate(pattern * cfg.num_periods):
            cache[f"layer{i}"] = init_block_cache(t, batch, capacity, cfg, dtype)
    for i, t in enumerate(cfg.remainder_layers):
        cache[f"rem{i}"] = init_block_cache(t, batch, capacity, cfg, dtype)
    return cache


def apply_stack_step(params: Params, x: jnp.ndarray, cache, pos, cfg):
    _require_decodable(cfg)
    pattern = cfg.block_pattern
    if cfg.scan_layers and cfg.num_periods > 0:
        stacked_p = {f"pos{j}": params[f"pos{j}"] for j in range(len(pattern))}
        stacked_c = {f"pos{j}": cache[f"pos{j}"] for j in range(len(pattern))}

        def period(x, xs):
            pp, cc = xs
            new_c = {}
            for j, t in enumerate(pattern):
                x, nc = apply_block_step(pp[f"pos{j}"], x, cc[f"pos{j}"], t, pos, cfg)
                new_c[f"pos{j}"] = nc
            return x, new_c

        x, new_cache = jax.lax.scan(period, x, (stacked_p, stacked_c))
    else:
        new_cache = {}
        for i, t in enumerate(pattern * cfg.num_periods):
            x, nc = apply_block_step(params[f"layer{i}"], x, cache[f"layer{i}"], t, pos, cfg)
            new_cache[f"layer{i}"] = nc
    for i, t in enumerate(cfg.remainder_layers):
        x, nc = apply_block_step(params[f"rem{i}"], x, cache[f"rem{i}"], t, pos, cfg)
        new_cache[f"rem{i}"] = nc
    return x, new_cache


def prefill_stack(params: Params, x: jnp.ndarray, io: LayerIO, cfg, capacity: int, cache_dtype):
    """Prefill the whole stack, returning hidden states and the decode cache.

    The scanned path threads the cache as scan outputs (stacked per period).
    """
    _require_decodable(cfg)
    pattern = cfg.block_pattern

    if cfg.scan_layers and cfg.num_periods > 0:
        stacked = {f"pos{j}": params[f"pos{j}"] for j in range(len(pattern))}

        def period(x, pp):
            caches = {}
            for j, t in enumerate(pattern):
                x, c = prefill_block_cache(pp[f"pos{j}"], x, t, io, cfg, capacity, cache_dtype)
                caches[f"pos{j}"] = c
            return x, caches

        x, cache = jax.lax.scan(period, x, stacked)
    else:
        cache = {}
        for i, t in enumerate(pattern * cfg.num_periods):
            x, c = prefill_block_cache(params[f"layer{i}"], x, t, io, cfg, capacity, cache_dtype)
            cache[f"layer{i}"] = c
    for i, t in enumerate(cfg.remainder_layers):
        x, c = prefill_block_cache(params[f"rem{i}"], x, t, io, cfg, capacity, cache_dtype)
        cache[f"rem{i}"] = c
    return x, cache
