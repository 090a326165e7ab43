"""Attention: blockwise (online-softmax) prefill/train path + cached decode.

Three compute paths, all numerically equivalent where they overlap:

* ``blockwise_attention`` — lax.map over query blocks, lax.scan over KV blocks
  with running (max, sum, acc) online softmax.  The S×S score matrix never
  materializes, so 32k×32k prefill lowers within HBM budgets.
* banded path (``window is not None``) — each query block only *gathers* a
  static-width KV band of ``window + block_q`` positions, making sliding-window
  layers O(S·W) in FLOPs and bytes (this is what legitimizes ``long_500k``).
* ``decode_attention`` — one query token against a full or ring-buffer cache.

``mla_layer`` is deepseek-v2's multi-head latent attention on the
full-sequence path: keys and values come up from a normed low-rank latent,
one rotary key is shared by every head, and values are narrower than keys;
it mixes through ``blockwise_attention`` like every other layer.

GQA is handled by grouping query heads over KV heads; logit softcapping
(gemma2) is applied pre-softmax.  The Pallas flash kernel
(:mod:`repro.kernels.flash_attention`) is a drop-in for the inner block loop
when ``config.use_pallas`` is set (TPU target; validated in interpret mode).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import (
    LayerIO,
    Params,
    apply_rmsnorm,
    apply_rope,
    init_rmsnorm,
    truncated_normal,
    yarn_mscale,
)
from repro.sharding.ctx import shard_activation

NEG_INF = -2.0e38
# Above this many bytes of f32 scores a call (batch x heads x queries x
# keys), the backward pass recomputes each query block, and within it each
# key block's scores, instead of keeping the scores: at 2 x 4096 positions
# and 16 heads they alone would be 2 GiB a layer, several times over.
REMAT_SCORES_BYTES = 1 << 30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_attention(key, cfg, *, cross: bool = False) -> Params:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if cross:
        hkv = hq  # whisper cross-attention is plain MHA
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d)
    dt = jnp.float32
    return {
        "wq": truncated_normal(kq, (d, hq, hd), s, dt),
        "wk": truncated_normal(kk, (d, hkv, hd), s, dt),
        "wv": truncated_normal(kv, (d, hkv, hd), s, dt),
        "wo": truncated_normal(ko, (hq, hd, d), 1.0 / np.sqrt(hq * hd), dt),
    }


# ---------------------------------------------------------------------------
# Core blockwise attention (jnp oracle; the Pallas kernel mirrors this)
# ---------------------------------------------------------------------------

def _block_attend(q, k, v, qpos, kpos, *, causal, window, softcap, q_per_kv):
    """Attend one query block to one KV block.

    q: (B, Qb, Nkv, G, H); k/v: (B, Kb, Nkv, H); positions: (B, Qb)/(B, Kb).
    Returns unnormalized (scores_max, exp_sum, acc) pieces for online softmax.
    """
    scores = jnp.einsum("bqngh,bknh->bngqk", q.astype(jnp.float32), k.astype(jnp.float32))
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    mask = jnp.ones(scores.shape[-2:], bool)[None, None, None]
    dpos = qpos[:, None, None, :, None] - kpos[:, None, None, None, :]  # (B,1,1,Qb,Kb)
    valid = kpos[:, None, None, None, :] >= 0
    if causal:
        valid &= dpos >= 0
    if window is not None:
        valid &= dpos < window
    scores = jnp.where(mask & valid, scores, NEG_INF)
    return scores


def _online_softmax_step(carry, scores, v):
    m_prev, l_prev, acc_prev = carry
    m_cur = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows: keep m finite so exp() stays 0, not NaN
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(scores <= NEG_INF / 2, 0.0, p)
    corr = jnp.exp(jnp.where(m_prev <= NEG_INF / 2, NEG_INF, m_prev) - m_safe)
    corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bngqk,bknh->bqngh", p, v.astype(jnp.float32))
    acc_new = acc_prev * corr.transpose(0, 3, 1, 2)[..., None] + pv
    return (m_new, l_new, acc_new)


def blockwise_attention(
    q: jnp.ndarray,  # (B, S, Nq, H)
    k: jnp.ndarray,  # (B, T, Nkv, H)
    v: jnp.ndarray,
    qpos: jnp.ndarray,  # (B, S)
    kpos: jnp.ndarray,  # (B, T)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
) -> jnp.ndarray:
    B, S, Nq, H = q.shape
    T, Nkv, Hv = k.shape[1], k.shape[2], v.shape[-1]  # v may be narrower (MLA)
    G = Nq // Nkv
    q = q.reshape(B, S, Nkv, G, H)

    bq = min(block_q, S)
    bk = min(block_k, T)
    pad_q = (-S) % bq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad_q)), constant_values=-(10**9))
    nqb = (S + pad_q) // bq

    if window is not None and T > window + bq:
        out = _banded_attention(q, k, v, qpos, kpos, bq, window, softcap, causal)
    else:
        pad_k = (-T) % bk
        if pad_k:
            k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            kpos = jnp.pad(kpos, ((0, 0), (0, pad_k)), constant_values=-1)
        nkb = (T + pad_k) // bk
        kb = k.reshape(B, nkb, bk, Nkv, H)
        vb = v.reshape(B, nkb, bk, Nkv, Hv)
        kposb = kpos.reshape(B, nkb, bk)
        remat = 4 * B * Nq * S * T > REMAT_SCORES_BYTES

        def per_q_block(args):
            qblk, qposblk = args  # (B, bq, Nkv, G, H), (B, bq)

            def kv_step(carry, xs):
                kblk, vblk, kposblk = xs
                scores = _block_attend(
                    qblk, kblk, vblk, qposblk, kposblk,
                    causal=causal, window=window, softcap=softcap, q_per_kv=G,
                )
                return _online_softmax_step(carry, scores, vblk), None

            m0 = jnp.full((B, Nkv, G, bq), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, Nkv, G, bq), jnp.float32)
            a0 = jnp.zeros((B, bq, Nkv, G, Hv), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(
                jax.checkpoint(kv_step) if remat else kv_step,
                (m0, l0, a0),
                (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4), kposb.transpose(1, 0, 2)),
            )
            l = jnp.maximum(l, 1e-30)
            return acc / l.transpose(0, 3, 1, 2)[..., None]

        qblocks = q.reshape(B, nqb, bq, Nkv, G, H).transpose(1, 0, 2, 3, 4, 5)
        qposblocks = qpos.reshape(B, nqb, bq).transpose(1, 0, 2)
        if remat:
            per_q_block = jax.checkpoint(per_q_block)
        out = jax.lax.map(per_q_block, (qblocks, qposblocks))  # (nqb, B, bq, Nkv, G, Hv)
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nqb * bq, Nkv, G, Hv)

    out = out[:, :S].reshape(B, S, Nq, Hv)
    return out.astype(v.dtype)


def _banded_attention(q, k, v, qpos, kpos, bq, window, softcap, causal):
    """Sliding-window path: each query block gathers a static KV band of width
    ``window + bq`` — O(S·W) instead of O(S·T)."""
    B, Spad, Nkv, G, H = q.shape
    T = k.shape[1]
    nqb = Spad // bq
    band = window + bq

    def per_q_block(i):
        qblk = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        qposblk = jax.lax.dynamic_slice_in_dim(qpos, i * bq, bq, axis=1)
        start = jnp.clip(i * bq + bq - band, 0, max(T - band, 0))
        kblk = jax.lax.dynamic_slice_in_dim(k, start, min(band, T), axis=1)
        vblk = jax.lax.dynamic_slice_in_dim(v, start, min(band, T), axis=1)
        kposblk = jax.lax.dynamic_slice_in_dim(kpos, start, min(band, T), axis=1)
        scores = _block_attend(
            qblk, kblk, vblk, qposblk, kposblk,
            causal=causal, window=window, softcap=softcap, q_per_kv=G,
        )
        m = jnp.max(scores, axis=-1, keepdims=True)
        m = jnp.where(m <= NEG_INF / 2, 0.0, m)
        p = jnp.exp(scores - m)
        p = jnp.where(scores <= NEG_INF / 2, 0.0, p)
        l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
        pv = jnp.einsum("bngqk,bknh->bqngh", p, vblk.astype(jnp.float32))
        return pv / l.transpose(0, 3, 1, 2)[..., None]

    out = jax.lax.map(per_q_block, jnp.arange(nqb))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Spad, Nkv, G, v.shape[-1])


# ---------------------------------------------------------------------------
# Decode (single new token against a cache)
# ---------------------------------------------------------------------------

def decode_attention(
    q: jnp.ndarray,  # (B, 1, Nq, H)
    k_cache: jnp.ndarray,  # (B, C, Nkv, H)
    v_cache: jnp.ndarray,
    cache_positions: jnp.ndarray,  # (B, C) absolute positions; -1 = empty slot
    qpos: jnp.ndarray,  # (B, 1)
    *,
    window: int | None = None,
    softcap: float | None = None,
) -> jnp.ndarray:
    B, _, Nq, H = q.shape
    Nkv = k_cache.shape[2]
    G = Nq // Nkv
    qg = q.reshape(B, 1, Nkv, G, H)
    scores = _block_attend(
        qg, k_cache, v_cache, qpos, cache_positions,
        causal=True, window=window, softcap=softcap, q_per_kv=G,
    )  # (B, Nkv, G, 1, C)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(scores - m)
    p = jnp.where(scores <= NEG_INF / 2, 0.0, p)
    l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
    pv = jnp.einsum("bngqk,bknh->bqngh", p, v_cache.astype(jnp.float32))
    out = pv / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(B, 1, Nq, H).astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# KV cache helpers (full + ring)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, capacity: int, nkv: int, hd: int, dtype) -> dict[str, jnp.ndarray]:
    return {
        "k": jnp.zeros((batch, capacity, nkv, hd), dtype),
        "v": jnp.zeros((batch, capacity, nkv, hd), dtype),
    }


def cache_positions_full(capacity: int, length: jnp.ndarray, batch: int) -> jnp.ndarray:
    """Positions of slots [0..capacity) when ``length`` tokens are stored."""
    slots = jnp.arange(capacity)
    pos = jnp.where(slots < length, slots, -1)
    return jnp.broadcast_to(pos[None, :], (batch, capacity))


def cache_positions_ring(capacity: int, length: jnp.ndarray, batch: int) -> jnp.ndarray:
    """Ring buffer: slot j holds absolute position p ≡ j (mod capacity),
    the largest such p < length; empty slots report -1."""
    slots = jnp.arange(capacity)
    p = length - 1 - ((length - 1 - slots) % capacity)
    pos = jnp.where((p >= 0) & (length > 0), p, -1)
    return jnp.broadcast_to(pos[None, :], (batch, capacity))


def update_cache_full(cache, k_new, v_new, pos: jnp.ndarray):
    """Insert one token at absolute position ``pos`` (scalar int)."""
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
    return {"k": k, "v": v}


def update_cache_ring(cache, k_new, v_new, pos: jnp.ndarray):
    cap = cache["k"].shape[1]
    slot = pos % cap
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    return {"k": k, "v": v}


def fill_cache_from_prefill(k, v, capacity: int, ring: bool):
    """Build a decode cache from prefill K/V of length S (static)."""
    B, S = k.shape[0], k.shape[1]
    if not ring:
        pad = capacity - S
        assert pad >= 0, f"cache capacity {capacity} < prefill length {S}"
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return {"k": kc, "v": vc}
    # ring: keep the last `capacity` positions at slot = pos % capacity
    n = min(S, capacity)
    k_last, v_last = k[:, S - n :], v[:, S - n :]
    slots = (np.arange(S - n, S) % capacity).astype(np.int32)
    kc = jnp.zeros((B, capacity) + k.shape[2:], k.dtype).at[:, slots].set(k_last)
    vc = jnp.zeros((B, capacity) + v.shape[2:], v.dtype).at[:, slots].set(v_last)
    return {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# Full attention layer (projections + rope + mix)
# ---------------------------------------------------------------------------

def attention_layer(
    p: Params,
    x: jnp.ndarray,
    io: LayerIO,
    cfg,
    *,
    window: int | None,
    kv_source: jnp.ndarray | None = None,  # cross-attention memory
    use_rope: bool = True,
) -> jnp.ndarray:
    dt = x.dtype
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
    src = x if kv_source is None else kv_source
    k = jnp.einsum("btd,dnh->btnh", src, p["wk"].astype(dt))
    v = jnp.einsum("btd,dnh->btnh", src, p["wv"].astype(dt))
    q = shard_activation(q, ("batch", "seq", "heads", None))
    k = shard_activation(k, ("batch", "seq", "kv_heads", None))
    v = shard_activation(v, ("batch", "seq", "kv_heads", None))
    if use_rope and kv_source is None:
        q = apply_rope(q, io.positions, cfg.rope_theta)
        k = apply_rope(k, io.positions, cfg.rope_theta)
    scale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5
    q = q * jnp.asarray(scale, dt)
    kpos = io.positions if kv_source is None else jnp.broadcast_to(
        jnp.arange(src.shape[1])[None], (src.shape[0], src.shape[1])
    )
    if cfg.use_pallas and kv_source is None:
        # TPU fast path: the Pallas flash kernel (contiguous positions).
        from repro.kernels import ON_TPU
        from repro.kernels.flash_attention.ops import flash_attention

        out = flash_attention(
            q, k, v,
            causal=io.causal, window=window, softcap=cfg.attn_logit_softcap,
            scale=1.0,  # q is pre-scaled above
            block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
            interpret=not ON_TPU,
        )
        out = shard_activation(out, ("batch", "seq", "heads", None))
        return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(dt))
    out = blockwise_attention(
        q, k, v, io.positions, kpos,
        causal=io.causal and kv_source is None,
        window=window,
        softcap=cfg.attn_logit_softcap,
        block_q=cfg.attn_block_q,
        block_k=cfg.attn_block_k,
    )
    out = shard_activation(out, ("batch", "seq", "heads", None))
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Multi-head latent attention (deepseek-v2, no q low-rank)
# ---------------------------------------------------------------------------

def init_mla(key, cfg) -> Params:
    d, h, dn, dr = cfg.d_model, cfg.num_heads, cfg.head_dim, cfg.qk_rope_head_dim
    r, dv = cfg.kv_lora_rank, cfg.v_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    dt = jnp.float32
    return {
        "wq": truncated_normal(kq, (d, h, dn + dr), d**-0.5, dt),
        "wkv_a": truncated_normal(ka, (d, r + dr), d**-0.5, dt),
        "kv_norm": init_rmsnorm(r),
        "wkv_b": truncated_normal(kb, (r, h, dn + dv), r**-0.5, dt),
        "wo": truncated_normal(ko, (h, dv, d), (h * dv) ** -0.5, dt),
    }


def mla_softmax_scale(cfg) -> float:
    """(no-rope + rope dims)^-0.5, times YaRN's ``mscale_all_dim`` factor squared."""
    scale = (cfg.head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return scale


def mla_layer(p: Params, x: jnp.ndarray, io: LayerIO, cfg) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D).  Rope runs on split halves of the rope dims
    (the published code first permutes interleaved pairs into halves)."""
    dt = x.dtype
    dn, r = cfg.head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
    latent = apply_rmsnorm(p["kv_norm"], kv_a[..., :r], cfg.norm_eps)
    kv = jnp.einsum("bsr,rnh->bsnh", latent, p["wkv_b"].astype(dt))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_rope = apply_rope(q[..., dn:], io.positions, cfg.rope_theta, cfg.yarn)
    k_rope = apply_rope(kv_a[..., None, r:], io.positions, cfg.rope_theta, cfg.yarn)
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1) * jnp.asarray(mla_softmax_scale(cfg), dt)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + k_rope.shape[-1:])], axis=-1)
    out = blockwise_attention(
        q, k, v, io.positions, io.positions,
        causal=io.causal, window=None, softcap=None,
        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
    )
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(dt))
