"""Mixture-of-Experts layer (qwen2-moe / qwen3-moe style).

Design (TPU-native, FLOPs-honest):

* **Routing**: top-k softmax with renormalized selected probabilities
  (qwen convention); auxiliary Switch-style load-balance loss.
* **Slot assignment**: capacity ``C = ceil(top_k·T·cf/E)`` per expert; token→slot
  positions computed with a stable sort over expert ids (O(T·K log) — *no*
  (T,E,C) one-hot tensors, which would double the MoE FLOPs and blow memory).
* **Dispatch/combine**: scatter rows into an ``(E_local·C, D)`` buffer and gather
  back.  Under ``shard_map`` over the ``model`` axis the dispatch is
  *communication-free*: activations are replicated across ``model``, so each
  expert shard scatters exactly the tokens routed to its local experts; the
  combine is one ``psum`` over ``model`` — identical collective cost to a
  tensor-parallel dense FFN.
* **Shared experts** (qwen2-moe): gated dense MLP + sigmoid gate, applied to
  every token outside the routed path.
* **Expert padding**: qwen2-moe's 60 routed experts pad to 64 so the expert
  axis shards over model=16; padded experts are masked to -inf in the router.

Without an active mesh (CPU unit tests) the same math runs single-shard —
that path is the oracle the sharded path is tested against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.layers import Params, _act, truncated_normal
from repro.sharding.ctx import current_rules


def init_moe(key, cfg) -> Params:
    d, e, fe = cfg.d_model, cfg.experts_padded, cfg.d_ff_expert
    ks = jax.random.split(key, 6)
    s = 1.0 / np.sqrt(d)
    p: Params = {
        "router": truncated_normal(ks[0], (d, e), s, jnp.float32),
        "w_gate_e": truncated_normal(ks[1], (e, d, fe), s, jnp.float32),
        "w_up_e": truncated_normal(ks[2], (e, d, fe), s, jnp.float32),
        "w_down_e": truncated_normal(ks[3], (e, fe, d), 1.0 / np.sqrt(fe), jnp.float32),
    }
    if cfg.shared_expert_ff:
        fs = cfg.shared_expert_ff
        p["shared"] = {
            "w_gate": truncated_normal(ks[4], (d, fs), s, jnp.float32),
            "w_up": truncated_normal(jax.random.fold_in(ks[4], 1), (d, fs), s, jnp.float32),
            "w_down": truncated_normal(
                jax.random.fold_in(ks[4], 2), (fs, d), 1.0 / np.sqrt(fs), jnp.float32
            ),
            "gate_proj": truncated_normal(ks[5], (d, 1), s, jnp.float32),
        }
    return p


def capacity_for(tokens: int, num_experts: int, top_k: int, capacity_factor: float) -> int:
    # reprolint: disable=RL001 — pure python ints: static capacity at trace time
    cap = int(np.ceil(top_k * tokens * capacity_factor / num_experts))
    return max(-(-cap // 4) * 4, 4)  # lane-friendly multiple of 4


def _slot_assignment(topk_idx: jnp.ndarray, num_experts: int):
    """Position of each (token, choice) within its expert's capacity queue.

    topk_idx: (T, K) int32 -> pos: (T, K) int32.  Earlier (token-major) entries
    win slots, matching the usual Switch priority rule.
    """
    T, K = topk_idx.shape
    flat = topk_idx.reshape(T * K)
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    starts = jnp.cumsum(counts) - counts
    ranks = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros((T * K,), jnp.int32).at[order].set(ranks)
    return pos.reshape(T, K), counts


def _expert_ffn(xin: jnp.ndarray, p: Params, act: str, e_slice) -> jnp.ndarray:
    """xin: (E_loc, C, D) -> (E_loc, C, D) with weight stacks (E, D, F)/(E, F, D)."""
    dt = xin.dtype
    wg = e_slice(p["w_gate_e"]).astype(dt)
    wu = e_slice(p["w_up_e"]).astype(dt)
    wd = e_slice(p["w_down_e"]).astype(dt)
    gate = jnp.einsum("ecd,edf->ecf", xin, wg)
    up = jnp.einsum("ecd,edf->ecf", xin, wu)
    return jnp.einsum("ecf,efd->ecd", _act(act)(gate) * up, wd)


def _routed_local(xt, p, cfg, C: int, e_start, e_local: int, e_presliced: bool):
    """Dispatch -> expert FFN -> weighted combine for experts
    [e_start, e_start + e_local).  xt: (T, D).  Returns the *partial* output
    (zero rows for tokens whose experts live elsewhere) plus the aux loss.

    ``e_presliced``: the expert weight stacks already hold only the local
    experts (shard_map path); otherwise they hold all E and are sliced here.
    """
    dt = xt.dtype
    T, D = xt.shape
    E, K = cfg.experts_padded, cfg.top_k

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    if cfg.experts_padded != cfg.num_experts:
        pad_mask = np.zeros((E,), np.float32)
        pad_mask[cfg.num_experts :] = -1e30
        logits = logits + pad_mask
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_idx = jax.lax.top_k(probs, K)
    topk_p = (topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)).astype(dt)

    pos, counts = _slot_assignment(topk_idx, E)
    local = (topk_idx >= e_start) & (topk_idx < e_start + e_local)
    keep = local & (pos < C)
    e_rel = topk_idx - e_start
    dest = jnp.where(keep, e_rel * C + pos, e_local * C)  # overflow -> trash row

    buf = jnp.zeros((e_local * C + 1, D), dt)
    for kk in range(K):  # K unique-destination scatters; avoids a (T·K, D) copy
        buf = buf.at[dest[:, kk]].set(xt, mode="drop")
    e_slice = (lambda w: w) if e_presliced else (
        lambda w: jax.lax.dynamic_slice_in_dim(w, e_start, e_local, axis=0)
    )
    eout = _expert_ffn(
        buf[: e_local * C].reshape(e_local, C, D), p, cfg.act, e_slice=e_slice
    ).reshape(e_local * C, D)
    eout = jnp.concatenate([eout, jnp.zeros((1, D), dt)], axis=0)

    out = jnp.zeros((T, D), dt)
    for kk in range(K):
        w = jnp.where(keep[:, kk], topk_p[:, kk], 0.0)[:, None]
        out = out + w * eout[dest[:, kk]]

    # Switch-style load-balance loss: fraction routed x mean router prob.
    me = counts[: cfg.num_experts].astype(jnp.float32) / (T * K)
    pe = jnp.mean(probs, axis=0)[: cfg.num_experts]
    aux = (cfg.num_experts * cfg.num_experts * jnp.sum(me * pe) / cfg.top_k).astype(jnp.float32)
    return out, aux


def apply_moe(p: Params, x: jnp.ndarray, cfg) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss)."""
    B, S, D = x.shape
    E = cfg.experts_padded
    rules = current_rules()

    if rules is not None and "model" in rules.mesh.axis_names:
        mesh = rules.mesh
        n_model = mesh.shape["model"]
        assert E % n_model == 0, f"experts {E} must divide model axis {n_model}"
        e_local = E // n_model
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        n_data = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
        bspec = P(batch_axes if batch_axes else None, None, None)

        fe = cfg.d_ff_expert
        stationary = (
            cfg.moe_weights_stationary and batch_axes and fe % n_data == 0 and (B * S) % n_data == 0
        )

        if stationary:
            # Weights-stationary: experts over `model` x d_ff over `data`.
            # Tokens (tiny at decode) are all-gathered over `data`; each shard
            # computes its f-slice of every local expert; outputs psum over
            # (`model`, `data`).  Expert weights never move.
            C = capacity_for(B * S, E, cfg.top_k, cfg.capacity_factor)
            wspec = {
                k: (
                    P("model", None, batch_axes) if k in ("w_gate_e", "w_up_e")
                    else P("model", batch_axes, None) if k == "w_down_e"
                    else jax.tree.map(lambda _: P(), v)
                )
                for k, v in p.items()
            }

            @functools.partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=(wspec, bspec),
                out_specs=(bspec, P()),
                check_vma=False,
            )
            def sharded(pp, xs):
                Bl, Sl, Dl = xs.shape
                xg = xs
                for ax in batch_axes:
                    xg = jax.lax.all_gather(xg, ax, axis=0, tiled=True)
                xt = xg.reshape(B * S, Dl)
                m_idx = jax.lax.axis_index("model")
                out, aux = _routed_local(
                    xt, pp, cfg, C, m_idx * e_local, e_local, e_presliced=True
                )
                out = jax.lax.psum(out, ("model",) + batch_axes)
                aux = jax.lax.pmean(aux, ("model",) + batch_axes)
                # slice this shard's batch rows back out
                d_idx = jax.lax.axis_index(batch_axes[0]) if len(batch_axes) == 1 else (
                    jax.lax.axis_index(batch_axes[0]) * mesh.shape[batch_axes[1]]
                    + jax.lax.axis_index(batch_axes[1])
                )
                out = jax.lax.dynamic_slice_in_dim(
                    out.reshape(n_data, Bl * Sl, Dl), d_idx, 1, axis=0
                )[0]
                return out.reshape(Bl, Sl, Dl), aux

            out, aux = sharded(p, x)
        else:
            T_loc = (B * S) // n_data
            C = capacity_for(T_loc, E, cfg.top_k, cfg.capacity_factor)
            # expert stacks arrive pre-sliced over `model` (their at-rest
            # sharding); router / shared MLP are small and enter replicated.
            wspec = {
                k: (P("model", None, None) if k.endswith("_e") else jax.tree.map(lambda _: P(), v))
                for k, v in p.items()
            }

            @functools.partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=(wspec, bspec),
                out_specs=(bspec, P()),
                check_vma=False,
            )
            def sharded(pp, xs):
                Bl, Sl, Dl = xs.shape
                xt = xs.reshape(Bl * Sl, Dl)
                m_idx = jax.lax.axis_index("model")
                out, aux = _routed_local(xt, pp, cfg, C, m_idx * e_local, e_local, e_presliced=True)
                out = jax.lax.psum(out, "model")
                aux = jax.lax.pmean(aux, ("model",) + batch_axes)
                return out.reshape(Bl, Sl, Dl), aux

            out, aux = sharded(p, x)
    else:
        xt = x.reshape(B * S, D)
        C = capacity_for(B * S, E, cfg.top_k, cfg.capacity_factor)
        out, aux = _routed_local(xt, p, cfg, C, 0, E, e_presliced=False)
        out = out.reshape(B, S, D)

    if "shared" in p:
        dt = x.dtype
        sp = p["shared"]
        g = _act(cfg.act)(jnp.einsum("bsd,df->bsf", x, sp["w_gate"].astype(dt)))
        u = jnp.einsum("bsd,df->bsf", x, sp["w_up"].astype(dt))
        sh = jnp.einsum("bsf,fd->bsd", g * u, sp["w_down"].astype(dt))
        sgate = jax.nn.sigmoid(jnp.einsum("bsd,dk->bsk", x, sp["gate_proj"].astype(dt)))
        out = out + sgate * sh
    return out, aux
