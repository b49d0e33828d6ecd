"""Attention-aware roofline model unit tests (paper §4.1)."""

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import RequestLoad, RooflineModel, TPU_V5E, H100_LIKE
from repro.core.roofline import _linear


CFG = get_config("qwen3-4b")


def test_linear_operator_matches_paper_formula():
    n, di, do, b = 1024, 4096, 11008, 2
    c = _linear(n, di, do, b)
    assert c.flops == 2 * n * di * do
    assert c.bytes == n * di * b + di * do * b + n * do * b


def test_attention_request_formula_prefill():
    m = RooflineModel(CFG, TPU_V5E)
    q, c = 512, 0
    F, B = m._block_seq_cost_vec("attn", np.array([q]), np.array([c]))
    H, dh, G = CFG.num_heads, CFG.head_dim, CFG.num_kv_heads
    assert F[0] == 4 * H * q * (q + c) * dh + 2 * H * q * (q + c)
    assert B[0] == 2 * H * q * dh * 2 + 2 * G * (q + c) * dh * 2


def test_attention_captures_decode_context_growth():
    """Paper Obs. 2 / Fig. 1c: decode latency grows with context under a
    fixed token budget."""
    m = RooflineModel(CFG, TPU_V5E)
    short = m.decode_latency(8, 1024, units=1)
    long = m.decode_latency(8, 65536, units=1)
    assert long > 3 * short


def test_prefill_latency_quadratic_component():
    m = RooflineModel(CFG, TPU_V5E)
    t1 = m.prefill_latency(4096, units=1)
    t2 = m.prefill_latency(8192, units=1)
    assert t2 > 1.9 * t1  # superlinear (linear layers + quadratic attention)


def test_units_monotonicity():
    m = RooflineModel(CFG, TPU_V5E)
    reqs = [RequestLoad(q=2048, c=0, phase="prefill")] + \
        [RequestLoad(q=1, c=4096) for _ in range(16)]
    lat = [m.iteration_latency(reqs, units=u) for u in (1, 2, 4, 8)]
    assert all(a > b for a, b in zip(lat, lat[1:]))


def test_chunked_prefill_modelled():
    """(q>1, c>0) chunked-prefill attention costs more than a fresh chunk of
    the same size (it rereads the cached context)."""
    m = RooflineModel(CFG, TPU_V5E)
    fresh = m.iteration_latency([RequestLoad(q=1024, c=0)], units=1)
    chunk = m.iteration_latency([RequestLoad(q=1024, c=8192)], units=1)
    assert chunk > fresh


def test_allreduce_term_grows_with_tp():
    m1 = RooflineModel(CFG, TPU_V5E, tp=1)
    m8 = RooflineModel(CFG, TPU_V5E, tp=8)
    reqs = [RequestLoad(q=4096, c=0, phase="prefill")]
    # same units: tp=8 adds communication on top
    t1 = m1.iteration_latency(reqs, units=8)
    t8 = m8.iteration_latency(reqs, units=8)
    assert t8 > t1


def test_gpu_bandwidth_curve_superlinear():
    """Paper Fig. 3a: 20% of SMs reach well over 20% of bandwidth."""
    frac_bw = H100_LIKE.bw(0.2 * H100_LIKE.num_units) / H100_LIKE.bw(
        H100_LIKE.num_units)
    assert frac_bw > 0.35
    # TPU chips own their HBM: linear
    assert TPU_V5E.bw(51) / TPU_V5E.bw(256) == pytest.approx(51 / 256)


def test_recurrent_family_operators():
    zcfg = get_config("zamba2-1.2b")
    m = RooflineModel(zcfg, TPU_V5E)
    # decode cost is O(1) in context for SSM blocks: latency flat vs context
    t1 = m.decode_latency(4, 1024, units=1)
    t2 = m.decode_latency(4, 262144, units=1)
    assert t2 < 1.5 * t1 * 40  # grows only via the shared-attn blocks
    xcfg = get_config("xlstm-350m")
    mx = RooflineModel(xcfg, TPU_V5E)
    ta = mx.decode_latency(4, 1024, units=1)
    tb = mx.decode_latency(4, 262144, units=1)
    assert tb == pytest.approx(ta)  # pure recurrent: no context dependence


def test_sliding_window_caps_attention():
    m_full = RooflineModel(CFG, TPU_V5E)
    m_win = RooflineModel(CFG, TPU_V5E, sliding_window=8192)
    t_full = m_full.decode_latency(1, 500_000, units=1)
    t_win = m_win.decode_latency(1, 500_000, units=1)
    assert t_win < t_full / 5


def test_hardware_spec_by_device_kind():
    """The engine prices the device it runs on: v5e and the CPU test
    target map to the v5e spec; an unknown TPU kind is an error, never a
    silent v5e."""
    from repro.core import hardware_spec
    assert hardware_spec("TPU v5 lite") is TPU_V5E
    assert hardware_spec("cpu") is TPU_V5E
    with pytest.raises(ValueError, match="TPU v7x"):
        hardware_spec("TPU v7x")
