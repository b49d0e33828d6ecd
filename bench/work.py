"""Operations and bytes that served tokens require, from the configuration
and the context lengths alone: never from the padded shapes a program
happens to run, so a share reads the same work whatever implements it.

What one token needs in each part of the model is the architecture's to
say (``bench/arch/<model_type>.py``: ``matmul_flops``, ``attn_flops``,
``attn_bytes``, ``head_flops``, ``step_weight_bytes``). Here they are
summed for a decode token and a prompt chunk. The output head runs for
each token whose logits are needed: the last prompt token and every decode
input.
"""
from __future__ import annotations

from typing import Dict

BYTES = 2   # bfloat16 weights and KV


def decode_flops(arch, cfg: Dict, ctx: int) -> float:
    """One decode token whose input sits at position ``ctx - 1``."""
    return arch.matmul_flops(cfg) + arch.attn_flops(cfg, ctx) \
        + arch.head_flops(cfg)


def prefill_attn_flops(arch, cfg: Dict, start: int, n: int) -> float:
    """Attention FLOPs of prompt positions start..start+n-1, each over
    itself and every earlier position."""
    pairs = n * start + n * (n + 1) / 2.0
    return arch.attn_flops(cfg, pairs)


def prefill_flops(arch, cfg: Dict, n: int, start: int = 0,
                  head: bool = True) -> float:
    """Prompt positions start..start+n-1; with ``head``, the output head
    for the last one (the chunk that ends the prompt)."""
    return n * arch.matmul_flops(cfg) \
        + prefill_attn_flops(arch, cfg, start, n) \
        + (arch.head_flops(cfg) if head else 0.0)
