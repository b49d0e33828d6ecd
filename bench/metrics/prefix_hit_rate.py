"""Prompt tokens served from the prefix cache over prompt tokens looked up
in the window (``kv_mgr.prefix_stats()`` deltas), in percent. Layer: KV
manager (``serving/kvcache``)."""


def read(r):
    n = r.counters["lookup_tokens"]
    if n <= 0:
        return None
    return 100.0 * r.counters["hit_tokens"] / n
