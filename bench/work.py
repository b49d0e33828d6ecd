"""Operations and bytes that served tokens require, from the configuration
and the context lengths alone: never from the padded shapes a program
happens to run, so a share reads the same work whatever implements it.

Per token and layer: the projection and MLP matrix products (2 FLOPs per
weight), and attention over ``c`` cached positions (QK^T and PV, 4 * H * dh
* c FLOPs; the K and V of those positions read once, 2 * G * dh * c
elements). The output head costs 2 * D * V per token whose logits are
needed: the last prompt token and every decode input. Weights are counted
in ``step_weight_bytes``: once per device step, whatever the batch.
"""
from __future__ import annotations

from typing import Dict

BYTES = 2   # bfloat16 weights and KV


def _dims(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


def layer_matmul_flops(cfg: Dict) -> float:
    """Matrix-product FLOPs of one token through one layer."""
    D, H, G, dh, F, _, _ = _dims(cfg)
    return 2.0 * (D * H * dh * 2 + D * G * dh * 2 + 3 * D * F)


def head_flops(cfg: Dict) -> float:
    D, *_, V = _dims(cfg)
    return 2.0 * D * V


def attn_flops(cfg: Dict, ctx: float) -> float:
    """Attention FLOPs of one query over ``ctx`` positions, all layers."""
    _, H, _, dh, _, L, _ = _dims(cfg)
    return 4.0 * H * dh * ctx * L


def attn_bytes(cfg: Dict, ctx: float) -> float:
    """K and V bytes one query reads over ``ctx`` positions, all layers."""
    _, _, G, dh, _, L, _ = _dims(cfg)
    return 2.0 * G * dh * ctx * L * BYTES


def decode_flops(cfg: Dict, ctx: int) -> float:
    """One decode token whose input sits at position ``ctx - 1``."""
    L = cfg["num_hidden_layers"]
    return L * layer_matmul_flops(cfg) + attn_flops(cfg, ctx) \
        + head_flops(cfg)


def prefill_attn_flops(cfg: Dict, start: int, n: int) -> float:
    """Attention FLOPs of prompt positions start..start+n-1, each over
    itself and every earlier position."""
    pairs = n * start + n * (n + 1) / 2.0
    return attn_flops(cfg, pairs)


def prefill_flops(cfg: Dict, n: int, start: int = 0,
                  head: bool = True) -> float:
    """Prompt positions start..start+n-1; with ``head``, the output head
    for the last one (the chunk that ends the prompt)."""
    L = cfg["num_hidden_layers"]
    return n * L * layer_matmul_flops(cfg) \
        + prefill_attn_flops(cfg, start, n) + (head_flops(cfg) if head
                                               else 0.0)


def step_weight_bytes(cfg: Dict) -> float:
    """Weight bytes one device step reads: every layer plus the head."""
    D, *_, V = _dims(cfg)
    L = cfg["num_hidden_layers"]
    return (L * layer_matmul_flops(cfg) / 2.0 + D * V) * BYTES
