"""Training FLOPs of one tick of the DeepSeek-V2 decoder on a chip's share
of experts (matrix products only), and the routed experts' work alone.

Counted once per tick: the forward pass's products, and twice that for the
backward pass; no recomputation (remat), no embedding lookup, 2 FLOPs per
multiply-add.  Per row of ``T`` positions:

* each layer, per position: latent attention's projections, ``W_q``
  (``d -> H (dn + dr)``), ``W_kva`` (``d -> r + dr``), ``W_kvb``
  (``r -> H (dn + dv)``) and ``W_o`` (``H dv -> d``);
* each layer, per row: causal scores over ``dn + dr`` dims and the value
  mix over ``dv`` dims, on the ``T (T + 1) / 2`` pairs a causal mask keeps;
* a leading dense layer, per position: the SwiGLU, ``3 d d_ff``;
* an expert layer, per position: the router (``d E``) and the shared
  SwiGLU (``3 d f_shared``); per tick: the routed experts at their expected
  load, ``T K held / E`` token-choices a row, ``3 d f_expert`` each;
* the head, per position: ``d V``.
"""

from __future__ import annotations


def _expert_rows(config: dict, batch: int, positions: int) -> float:
    """Token-choices a tick that the held experts take, at the expected
    (uniform) load: ``batch T top_k held / E``."""
    return batch * positions * config["top_k"] * config["experts_held"] / config["num_experts"]


def train_flops(config: dict, batch: int, positions: int) -> float:
    L, lead = config["num_layers"], config["first_k_dense_replace"]
    d, h, dn = config["d_model"], config["num_heads"], config["head_dim"]
    dr, dv, r = config["qk_rope_head_dim"], config["v_head_dim"], config["kv_lora_rank"]
    T, V = positions, config["vocab_size"]
    proj = 2 * (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d)
    attn = 2 * h * (dn + dr + dv) * T * (T + 1) / 2
    dense = 2 * 3 * d * config["d_ff"]
    moe = 2 * (d * config["num_experts"] + 3 * d * config["shared_expert_ff"])
    routed = 2 * 3 * d * config["d_ff_expert"] * _expert_rows(config, batch, positions)
    forward = (batch * (L * (T * proj + attn) + lead * T * dense + (L - lead) * T * moe + T * 2 * d * V)
               + (L - lead) * routed)
    return 3.0 * forward


def expert_cost(config: dict, batch: int, positions: int) -> tuple[float, float]:
    """(FLOPs, bytes) a tick of the routed experts' grouped matmuls at the
    expected load, forward and backward, no recomputation.  Each of the three
    forward products (gate, up: ``rows x d -> rows x f``; down: ``rows x f ->
    rows x d``) reads its bf16 operands, the held experts' weights once, and
    writes its bf16 result; the backward has two products for each, the
    input gradient and the weight gradient, with the same reads and writes."""
    n = config["num_layers"] - config["first_k_dense_replace"]
    d, f, held = config["d_model"], config["d_ff_expert"], config["experts_held"]
    rows = _expert_rows(config, batch, positions)
    flops = 3 * 2 * 3 * rows * d * f
    # forward, elements a layer: gate and up read rows x d and weights, write
    # rows x f; down reads rows x f and weights, writes rows x d
    fwd = 2 * (rows * d + held * d * f + rows * f) + (rows * f + held * f * d + rows * d)
    # backward: input gradients read the output gradient and weights and
    # write rows x (input width); weight gradients read the input and the
    # output gradient and write the weights' shape
    bwd = 2 * ((rows * f + held * d * f + rows * d) + (rows * d + rows * f + held * d * f))
    bwd += (rows * d + held * f * d + rows * f) + (rows * f + rows * d + held * f * d)
    return float(n * flops), float(n * 2 * (fwd + bwd))
