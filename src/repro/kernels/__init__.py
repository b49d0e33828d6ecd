"""Pallas TPU kernels for the serving hot spots, each with a pure-jnp oracle
in ref.py and a jit wrapper in ops.py (interpret=True on the CPU backend):

  flash_prefill        — causal GQA flash attention (chunk-offset aware)
  paged_decode         — decode attention over paged KV (block tables via
                         scalar prefetch)
  paged_decode_splitkv — flash-decoding variant: the page chain splits over
                         a second grid axis, per-split (m, l, acc) partials
                         combine in a log-sum-exp epilogue
  duet_attention       — fused mixed-phase attention with grid interleaving
                         (the paper's SM partition mapped to the TPU grid),
                         over the slab cache or the paged pool
                         (duet_attention_paged)

``paged_decode_auto`` dispatches between the plain, split-KV and
shard_map-wrapped (TP>1) decode kernels from static mesh/threshold inputs.
"""
from repro.kernels.ops import (DuetSchedule, build_duet_schedule,
                               duet_attention, duet_attention_paged,
                               flash_prefill, pack_duet_queries,
                               paged_decode, paged_decode_auto,
                               paged_decode_sharded, paged_decode_splitkv,
                               unpack_duet_output)

__all__ = [
    "DuetSchedule", "build_duet_schedule", "duet_attention",
    "duet_attention_paged", "flash_prefill", "pack_duet_queries",
    "paged_decode", "paged_decode_auto", "paged_decode_sharded",
    "paged_decode_splitkv", "unpack_duet_output",
]
