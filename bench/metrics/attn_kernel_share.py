"""Device time of the attention kernels over device busy time, in percent.
A kernel's op family in the trace is the ``name`` its ``pallas_call``
gives it (``bench/devtrace.op_family``). Layer: kernels
(``kernels/paged_decode``, ``duet_attention``, ``flash_prefill``). None
where the trace holds none of them: a program whose kernels carry no name
shows them under the name of the scope around them."""

ATTN_KERNELS = ("paged_decode", "paged_decode_splitkv", "duet_attention",
                "duet_attention_paged", "flash_prefill")


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    s = sum(r.trace.by_name.get(k, 0.0) for k in ATTN_KERNELS)
    if s <= 0:
        return None
    return 100.0 * s / r.trace.busy_s
