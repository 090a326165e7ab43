"""Training FLOPs of one tick of a dense decoder (matrix products only).

Counted once per tick: the forward pass's products, and twice that for the
backward pass; no recomputation (remat), no embedding lookup.  Per row of
``T`` positions (image prefix and text) of which ``S`` are text:

* each layer, per position: the q, k, v, o projections and the feed-forward
  (three matrices when gated, else two), 2 FLOPs per multiply-add;
* each layer, per row: causal attention, ``q k^T`` and ``p v`` over the
  ``T (T + 1) / 2`` pairs a causal mask keeps;
* the head, per text position: ``2 d V``.
"""

from __future__ import annotations


def train_flops(config: dict, batch: int, positions: int) -> float:
    L, d, f = config["num_layers"], config["d_model"], config["d_ff"]
    hq, hkv, hd = config["num_heads"], config["num_kv_heads"], config["head_dim"]
    V = config["vocab_size"]
    T = positions
    S = T - int(config.get("num_prefix_embeddings") or 0)
    proj = 2 * (d * hq * hd + 2 * d * hkv * hd + hq * hd * d)
    mlp = 2 * (3 if config["gated_mlp"] else 2) * d * f
    attn = 2 * 2 * hq * hd * T * (T + 1) / 2
    forward = batch * (L * (T * (proj + mlp) + attn) + S * 2 * d * V)
    return 3.0 * forward
