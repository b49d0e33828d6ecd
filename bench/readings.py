"""What a run measured, and the arithmetic from client events to metrics.

Every time here is the host's ``perf_counter`` at the moment an event
reached the client: a request's submit, each of its tokens, its finish.
The engine's own clock is a roofline prediction and is never read.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Record:
    """One request as its client saw it."""
    rid: int
    prompt: np.ndarray
    output_len: int
    submit_t: float
    tok_t: List[float] = field(default_factory=list)
    toks: List[int] = field(default_factory=list)
    finish_t: Optional[float] = None
    reason: Optional[str] = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (0-100, linear interpolation); None if empty."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def ttfts(recs: List[Record], t0: float, t1: float) -> List[float]:
    """Submit-to-first-token of every request whose first token came in
    [t0, t1]."""
    return [r.tok_t[0] - r.submit_t for r in recs
            if r.tok_t and t0 <= r.tok_t[0] <= t1]


def gaps(recs: List[Record], t0: float, t1: float) -> List[float]:
    """Every inter-token gap of every request with both tokens in [t0, t1],
    pooled. Tokens of one look-ahead burst arrive together, so gaps near 0
    are kept: they are what the user sees."""
    out = []
    for r in recs:
        t = [x for x in r.tok_t if t0 <= x <= t1]
        out.extend(b - a for a, b in zip(t, t[1:]))
    return out


def tokens_in(recs: List[Record], t0: float, t1: float) -> int:
    return sum(1 for r in recs for x in r.tok_t if t0 <= x <= t1)


def end_to_end(recs: List[Record], t0: float, t1: float,
               setup_s: float) -> Dict[str, Optional[float]]:
    g = gaps(recs, t0, t1)
    tt = ttfts(recs, t0, t1)
    return {
        "ttft_mean_s": float(np.mean(tt)) if tt else None,
        "tbt_mean_ms": 1e3 * float(np.mean(g)) if g else None,
        "tbt_p99_ms": (1e3 * percentile(g, 99)) if g else None,
        "output_tokens_per_s": tokens_in(recs, t0, t1) / (t1 - t0),
        "setup_s": setup_s,
    }


def window_work(recs: List[Record], arch, cfg: Dict, t0: float, t1: float,
                pre0: Dict[int, int], pre1: Dict[int, int]
                ) -> Dict[str, float]:
    """Work the window's tokens require under ``arch``, the configuration's
    architecture module (``bench/work.py``): the prompt
    positions dispatched between t0 and t1 (``pre0``, ``pre1``: prompt
    tokens dispatched by then, per request), and each decode token
    delivered in [t0, t1] (token j >= 1 attends prompt_len + j positions).
    """
    from bench import work
    flops = attn_f = attn_b = 0.0
    decode_tokens = prompt_tokens = 0
    for r in recs:
        a, b = pre0.get(r.rid, 0), pre1.get(r.rid, 0)
        if b > a:
            flops += work.prefill_flops(arch, cfg, b - a, start=a,
                                        head=b == r.prompt_len)
            prompt_tokens += b - a
        for j, t in enumerate(r.tok_t):
            if j == 0 or not t0 <= t <= t1:
                continue
            ctx = r.prompt_len + j
            flops += work.decode_flops(arch, cfg, ctx)
            attn_f += arch.attn_flops(cfg, ctx)
            attn_b += arch.attn_bytes(cfg, ctx)
            decode_tokens += 1
    return {"flops": flops, "decode_attn_flops": attn_f,
            "decode_attn_bytes": attn_b, "decode_tokens": decode_tokens,
            "prompt_tokens": prompt_tokens}


@dataclass
class Readings:
    """Everything a per-layer reader may use."""
    window_s: float
    counters: Dict[str, float]
    work: Dict[str, float]
    peak: object = None
    trace: object = None            # devtrace.Reduction of device ops
    step_device_s: float = 0.0      # device time of the serving programs
