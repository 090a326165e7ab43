"""A DeepSeek-V2 decoder on one chip's share of expert parallelism, in plain
float32.

Nothing here imports the program.  It follows the published
``modeling_deepseek.py`` (DeepseekV2Attention without a q low-rank,
DeepseekV2MoE, MoEGate with ``scoring_func`` softmax, ``topk_method``
greedy and ``seq_aux``), with these as the configuration states them:

* pre-norm blocks, RMSNorm as ``x / rms(x) * (1 + scale)``; the first
  ``first_k_dense_replace`` layers have a dense SwiGLU MLP (``d_ff``), the
  others a routed-expert layer;
* latent attention: ``q = x W_q`` (heads of ``head_dim`` no-rope and
  ``qk_rope_head_dim`` rope dims); ``[c ‖ k_rope] = x W_kva``; ``c`` normed;
  ``[k_nope ‖ v] = c W_kvb``; YaRN rope on ``q_rope`` and on the one
  ``k_rope`` every head shares, on split halves; causal softmax over
  ``[nope ‖ rope]`` scaled by ``(head_dim + qk_rope_head_dim)^-0.5 ·
  mscale_all_dim``-factor squared; values ``v_head_dim`` wide;
* the expert layer holds experts ``[expert_offset, expert_offset +
  experts_held)`` of the router's ``num_experts``: softmax over all of
  them, greedy top ``top_k``, renormalised only under ``norm_topk_prob``,
  times ``routed_scaling_factor``.  Each held expert's SwiGLU runs on every
  token as a dense mask, weighted by its gate where the token chose it and
  by 0 elsewhere: no sort and no dispatch.  Absent experts add nothing.
  The shared experts are one SwiGLU of ``shared_expert_ff`` on every token;
* the loss is the mean cross-entropy plus ``router_aux_coef`` times the
  sum over expert layers of the expert-level balance loss per sequence,
  ``mean_b sum_e f_be P_be``, over all ``num_experts`` router outputs.

Layers are recomputed in the backward pass and attention runs a block of
queries at a time, so the f32 activations of a 2 x 4096 batch fit.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512  # queries scored at once


def weight_shapes(config: dict) -> dict:
    """``{path: (shape, init)}``: the leading dense layers stacked under
    ``stack/lead``, the expert layers under ``stack/pos0``."""
    L, lead = config["num_layers"], config["first_k_dense_replace"]
    d, h, dn = config["d_model"], config["num_heads"], config["head_dim"]
    dr, dv, r = config["qk_rope_head_dim"], config["v_head_dim"], config["kv_lora_rank"]
    f, fe, fs = config["d_ff"], config["d_ff_expert"], config["shared_expert_ff"]
    E, held, V = config["num_experts"], config["experts_held"], config["vocab_size"]

    def normal(fan_in, *shape):
        return (shape, ("normal", fan_in**-0.5))

    def block(prefix, n):
        s = {
            f"{prefix}/pre_norm/scale": ((n, d), ("zeros",)),
            f"{prefix}/mlp_pre_norm/scale": ((n, d), ("zeros",)),
            f"{prefix}/attn/wq": normal(d, n, d, h, dn + dr),
            f"{prefix}/attn/wkv_a": normal(d, n, d, r + dr),
            f"{prefix}/attn/kv_norm/scale": ((n, r), ("zeros",)),
            f"{prefix}/attn/wkv_b": normal(r, n, r, h, dn + dv),
            f"{prefix}/attn/wo": normal(h * dv, n, h, dv, d),
        }
        return s

    s = {
        "embed/embedding": normal(d, V, d),
        "unembed/embedding": normal(d, V, d),
        "final_norm/scale": ((d,), ("zeros",)),
    }
    s.update(block("stack/lead", lead))
    s.update({
        "stack/lead/mlp/w_gate": normal(d, lead, d, f),
        "stack/lead/mlp/w_up": normal(d, lead, d, f),
        "stack/lead/mlp/w_down": normal(f, lead, f, d),
    })
    n = L - lead
    s.update(block("stack/pos0", n))
    s.update({
        "stack/pos0/moe/router": normal(d, n, d, E),
        "stack/pos0/moe/w_gate_e": normal(d, n, held, d, fe),
        "stack/pos0/moe/w_up_e": normal(d, n, held, d, fe),
        "stack/pos0/moe/w_down_e": normal(fe, n, held, fe, d),
        "stack/pos0/moe/shared/w_gate": normal(d, n, d, fs),
        "stack/pos0/moe/shared/w_up": normal(d, n, d, fs),
        "stack/pos0/moe/shared/w_down": normal(fs, n, fs, d),
    })
    return s


def _rms(config, scale, x):
    eps = float(config["norm_eps"])
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + scale)


def _yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn(config, T):
    """cos, sin (T, dr/2) of DeepseekV2YarnRotaryEmbedding, and the softmax
    scale of DeepseekV2Attention."""
    rs, dim, base = config["rope_scaling"], config["qk_rope_head_dim"], float(config["rope_theta"])
    factor, orig = float(rs["factor"]), float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    keep = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    inv_freq = inter * (1.0 - keep) + extra * keep
    m = _yarn_get_mscale(factor, rs["mscale"]) / _yarn_get_mscale(factor, rs["mscale_all_dim"])
    ang = np.arange(T, dtype=np.float64)[:, None] * inv_freq[None, :]
    scale = (config["head_dim"] + dim) ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= _yarn_get_mscale(factor, rs["mscale_all_dim"]) ** 2
    return np.cos(ang) * m, np.sin(ang) * m, scale


def _rotate(x, cos, sin):
    """Rotary on split halves; x (B, T, ..., dr), cos/sin (T, dr/2)."""
    shape = (1, cos.shape[0]) + (1,) * (x.ndim - 3) + (cos.shape[1],)
    cos = jnp.asarray(cos, jnp.float32).reshape(shape)
    sin = jnp.asarray(sin, jnp.float32).reshape(shape)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(config, mm, p, x, rope):
    B, T, _ = x.shape
    dn, r = config["head_dim"], config["kv_lora_rank"]
    cos, sin, scale = rope
    q = mm("btd,dnh->btnh", x, p["wq"])
    kv_a = mm("btd,dr->btr", x, p["wkv_a"])
    kv = mm("btr,rnh->btnh", _rms(config, p["kv_norm"]["scale"], kv_a[..., :r]), p["wkv_b"])
    q_nope, q_rope = q[..., :dn], _rotate(q[..., dn:], cos, sin)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k_rope = _rotate(kv_a[..., r:], cos, sin)  # (B, T, dr), one for every head
    nb = T // Q_BLOCK if T % Q_BLOCK == 0 and T > Q_BLOCK else 1
    bq = T // nb

    @jax.checkpoint
    def query_block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * bq, bq, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * bq, bq, axis=1)
        s = (mm("bqnh,bknh->bnqk", qn, k_nope) + mm("bqnh,bkh->bnqk", qr, k_rope)) * scale
        causal = (i * bq + jnp.arange(bq))[:, None] >= jnp.arange(T)[None, :]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm("bnqk,bknh->bqnh", a, v)

    o = jax.lax.map(query_block, jnp.arange(nb))  # (nb, B, bq, H, dv)
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, *o.shape[3:])
    return mm("btnh,nhd->btd", o, p["wo"])


def _swiglu(mm, x, w_gate, w_up, w_down):
    return mm("btf,fd->btd", jax.nn.silu(mm("btd,df->btf", x, w_gate)) * mm("btd,df->btf", x, w_up), w_down)


def _experts(config, mm, p, x):
    """The held experts' part of the layer, the shared experts, and the
    per-sequence balance loss."""
    B, T, _ = x.shape
    E, K = config["num_experts"], config["top_k"]
    lo, held = config["expert_offset"], config["experts_held"]
    probs = jax.nn.softmax(mm("btd,de->bte", x, p["router"]), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, K)
    chosen = jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32), axis=-2)  # (B, T, E)
    gate = probs * chosen
    if config["norm_topk_prob"]:
        gate = gate / jnp.sum(top_p, axis=-1, keepdims=True)
    gate = gate * float(config["routed_scaling_factor"])
    h = jax.nn.silu(mm("btd,edf->betf", x, p["w_gate_e"])) * mm("btd,edf->betf", x, p["w_up_e"])
    y = mm("betf,efd->betd", h, p["w_down_e"])
    out = mm("bte,betd->btd", gate[..., lo:lo + held], y)
    sh = p["shared"]
    out = out + _swiglu(mm, x, sh["w_gate"], sh["w_up"], sh["w_down"])
    f = jnp.sum(chosen, axis=1) / (T * K / E)  # (B, E)
    aux = jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))
    return out, aux


def _block(config, mm, p, x, rope):
    x = x + _attention(config, mm, p["attn"], _rms(config, p["pre_norm"]["scale"], x), rope)
    h = _rms(config, p["mlp_pre_norm"]["scale"], x)
    if "moe" in p:
        m, aux = _experts(config, mm, p["moe"], h)
    else:
        m, aux = _swiglu(mm, h, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"]), 0.0
    return x + m, aux


def loss(config, mm, params, batch):
    """Mean cross-entropy of the labelled positions plus the weighted
    balance losses of the expert layers."""
    x = params["embed"]["embedding"][batch["tokens"]]
    rope = _yarn(config, x.shape[1])

    def layer(carry, p):
        x, aux = carry
        x, a = _block(config, mm, p, x, rope)
        return (x, aux + a), None

    # one layer at a time, recomputed in the backward pass
    carry = (x, jnp.zeros((), jnp.float32))
    for stack in ("lead", "pos0"):
        carry, _ = jax.lax.scan(jax.checkpoint(layer), carry, params["stack"][stack])
    x, aux = carry
    x = _rms(config, params["final_norm"]["scale"], x)
    logits = mm("btd,vd->btv", x, params["unembed"]["embedding"])
    labels = batch["labels"]
    mask = labels >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    ce = jnp.sum(jnp.where(mask, lse - ll, 0.0)) / jnp.maximum(jnp.sum(mask), 1)
    return ce + float(config["router_aux_coef"]) * aux
