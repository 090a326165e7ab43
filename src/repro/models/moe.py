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

:func:`apply_held_moe` is one chip's share of expert parallelism
(deepseek-v2): the layer holds ``experts_held`` of ``num_experts`` experts,
routes over all of them, and runs every token-choice that went to a held
expert (no capacity, no drops) through grouped matmuls; experts held
elsewhere add nothing here.
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
    d, fe = cfg.d_model, cfg.d_ff_expert
    e = cfg.experts_held or cfg.experts_padded  # expert stacks hold this chip's share
    ks = jax.random.split(key, 6)
    s = 1.0 / np.sqrt(d)
    p: Params = {
        "router": truncated_normal(ks[0], (d, cfg.experts_padded), s, jnp.float32),
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
        }
        if cfg.shared_expert_gate:
            p["shared"]["gate_proj"] = truncated_normal(ks[5], (d, 1), s, jnp.float32)
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
        out = out + _shared(p["shared"], x, cfg)
    return out, aux


def _shared(sp: Params, x: jnp.ndarray, cfg) -> jnp.ndarray:
    """The shared experts, one gated MLP on every token, with qwen2-moe's
    sigmoid output gate where the params hold one."""
    dt = x.dtype
    g = _act(cfg.act)(jnp.einsum("bsd,df->bsf", x, sp["w_gate"].astype(dt)))
    u = jnp.einsum("bsd,df->bsf", x, sp["w_up"].astype(dt))
    sh = jnp.einsum("bsf,fd->bsd", g * u, sp["w_down"].astype(dt))
    if "gate_proj" in sp:
        sh = jax.nn.sigmoid(jnp.einsum("bsd,dk->bsk", x, sp["gate_proj"].astype(dt))) * sh
    return sh


# ---------------------------------------------------------------------------
# This chip's share of expert parallelism (deepseek-v2 routing, no drops)
# ---------------------------------------------------------------------------

def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Tiles of the Pallas grouped matmul: 512 rows where they divide the
    rows, else 128; 512 deep; the whole width up to 1408 (an expert's
    d_ff), else 1024 (d_model 2048 in two)."""
    return (512 if m % 512 == 0 else 128, 512, n if n <= 1408 else 1024)


def _grouped_matmul(lhs: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray) -> jnp.ndarray:
    """``lhs`` rows grouped by held expert, ``sizes`` rows each, then the rows
    of absent experts (``sizes[-1]``), times each group's ``w[g]``; rows of
    absent experts come out zero and cost nothing.

    On the TPU this is the Pallas megablox ``gmm``, whose grid visits only
    the held groups' row tiles; elsewhere ``lax.ragged_dot``.
    """
    from repro.kernels import ON_TPU

    w = w.astype(lhs.dtype)
    if ON_TPU:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        return megablox.gmm(lhs, w, sizes, lhs.dtype, _gmm_tiling)
    return jax.lax.ragged_dot(lhs, w, sizes[:-1], preferred_element_type=lhs.dtype)


def _sequence_balance_loss(probs: jnp.ndarray, topk_idx: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """deepseek-v2's expert-level balance loss per sequence (``seq_aux``):
    ``mean_b sum_e f_be P_be`` with ``f_be`` the share of row b's token-choices
    that chose expert e over its uniform share ``1 / E``, and ``P_be`` the
    mean router probability of e over row b.  probs: (B, S, E); topk_idx:
    (B, S, K)."""
    B, S, E = probs.shape
    counts = jnp.zeros((B, E), jnp.float32).at[
        jnp.arange(B)[:, None], topk_idx.reshape(B, S * top_k)
    ].add(1.0)
    f = counts / (S * top_k / E)
    return jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))


# Tokens dispatched to the held experts at a time, at most.  The expert
# layer's backward holds its dispatch buffers for one block of tokens
# (recomputed block by block), so their memory does not grow with the tokens
# a tick: 2 x 4096 tokens at top-6 would otherwise hold ~1.4 GB of (token,
# choice) rows beside the async state.  A block's tokens are a multiple of
# ROW_TILE, so that its token-choices fill whole row tiles of the grouped
# matmul (``_gmm_tiling``).
DISPATCH_TOKENS = 2048
ROW_TILE = 128


def _held_part(p: Params, xt, topk_p, topk_idx, cfg):
    """The held experts' part of the layer for tokens xt (T, D) whose
    top-k gates and expert ids are (T, K); and each held expert's load,
    with the choices of absent experts last (held + 1,)."""
    T, D = xt.shape
    K, held = cfg.top_k, cfg.experts_held
    with jax.named_scope("dispatch"):
        e = topk_idx.reshape(T * K) - cfg.expert_offset
        slot = jnp.where((e >= 0) & (e < held), e, held)  # absent experts sort last
        order = jnp.argsort(slot, stable=True)
        sizes = jnp.bincount(slot, length=held + 1).astype(jnp.int32)
        token = order // K  # the token of each (token, choice) row, in expert order
        xs = xt[token]
    with jax.named_scope("experts"):
        h = _act(cfg.act)(_grouped_matmul(xs, p["w_gate_e"], sizes))
        h = h * _grouped_matmul(xs, p["w_up_e"], sizes)
        ys = _grouped_matmul(h, p["w_down_e"], sizes)
    with jax.named_scope("combine"):
        w = jnp.where(slot < held, topk_p.reshape(T * K), 0.0)[order].astype(xt.dtype)
        out = jnp.zeros((T, D), xt.dtype).at[token].add(ys * w[:, None])
    return out, sizes


def _in_blocks(a: jnp.ndarray, blocks: int, rows: int, fill=0) -> jnp.ndarray:
    """Rows of ``a`` as ``(blocks, rows, ...)``, the last block filled out
    with ``fill`` rows."""
    extra = blocks * rows - a.shape[0]
    if extra:
        a = jnp.pad(a, ((0, extra),) + ((0, 0),) * (a.ndim - 1), constant_values=fill)
    return a.reshape(blocks, rows, *a.shape[1:])


def apply_held_moe(p: Params, x: jnp.ndarray, cfg):
    """x: (B, S, D) -> (out, aux_loss, counts) for this chip's experts
    ``[expert_offset, expert_offset + experts_held)`` of ``num_experts``.

    The router scores every expert (softmax, greedy top-k, renormalised only
    under ``norm_topk_prob``); the token-choices that went to a held expert
    are sorted into one group per expert and run through grouped matmuls,
    every one of them (no capacity, no drops), in equal blocks of at most
    ``DISPATCH_TOKENS`` tokens; padding rows fill the last block and choose
    no held expert.  The others add nothing here.  ``counts`` holds this
    layer's token-choices routed to held experts and the largest held
    expert's load.
    """
    B, S, D = x.shape
    T, K, held = B * S, cfg.top_k, cfg.experts_held
    xt = x.reshape(T, D)
    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_idx = jax.lax.top_k(probs, K)
        if cfg.norm_topk_prob:
            topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)
        aux = _sequence_balance_loss(probs.reshape(B, S, -1), topk_idx.reshape(B, S, K), K)
    blocks = -(-T // DISPATCH_TOKENS)
    rows = -(-T // (blocks * ROW_TILE)) * ROW_TILE
    experts = {k: p[k] for k in ("w_gate_e", "w_up_e", "w_down_e")}
    part = jax.checkpoint(lambda args: _held_part(experts, *args, cfg))
    out, sizes = jax.lax.map(part, (
        _in_blocks(xt, blocks, rows), _in_blocks(topk_p, blocks, rows),
        _in_blocks(topk_idx, blocks, rows, fill=-1)))  # padding chooses no held expert
    out, sizes = out.reshape(blocks * rows, D)[:T].reshape(B, S, D), jnp.sum(sizes, axis=0)
    if "shared" in p:
        with jax.named_scope("shared"):
            out = out + _shared(p["shared"], x, cfg)
    counts = {"moe_held_choices": jnp.sum(sizes[:held]), "moe_max_expert_load": jnp.max(sizes[:held])}
    return out, aux, counts
