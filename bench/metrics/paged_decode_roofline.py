"""Roofline share of the paged-decode kernel (op families ``paged_decode``
and ``paged_decode_splitkv``), in percent: the least time the chip could
take for the decode attention the window's tokens need, over the kernel's
device time. The least time is the larger of ``decode_attn_flops`` over
the bf16 peak and ``decode_attn_bytes`` over the HBM peak
(``bench/work.py`` through ``readings.window_work``, from context lengths,
never from padded shapes). Bytes set it: per cached position a query
does 4 * H * dh FLOPs and reads 2 * G * dh bfloat16 elements of K and V,
H / G FLOPs per byte (4 for Qwen3's 32 query heads over 8 KV heads),
against the v5e's 240. Layer: kernels (``kernels/paged_decode``). Read
only where every decode row goes through this kernel, so the counted work
is the kernel's: None where the window ran a duet iteration (its decode
rows go through the duet grid), the trace has no such kernel, or the
window no decode token."""

KERNELS = ("paged_decode", "paged_decode_splitkv")


def read(r):
    if r.trace is None or r.peak is None or \
            r.counters.get("duet_iterations", 0) > 0:
        return None
    t = sum(r.trace.by_name.get(k, 0.0) for k in KERNELS)
    if t <= 0 or r.work.get("decode_tokens", 0) <= 0:
        return None
    least = max(r.work["decode_attn_flops"] / r.peak.bf16_flops,
                r.work["decode_attn_bytes"] / r.peak.hbm_bytes_per_s)
    return 100.0 * least / t
