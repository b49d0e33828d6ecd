"""The kernel layer's readers on synthetic readings: the attention
kernels' share of device busy time, and paged decode's roofline share."""
import pytest

from bench import devtrace, peaks
from bench.readings import Readings
from bench.run import Cell
from bench.tests.tiny import ROOT

V5E = peaks.peak("TPU v5 lite")


def reader(name):
    return Cell.load(ROOT, "qwen3-4b.decode2k-c32").reader(name)


def readings(by_name, busy_s=10.0, work=None, peak=V5E, counters=None):
    trace = devtrace.Reduction(window_s=10.5, busy_s=busy_s,
                               by_name=dict(by_name))
    return Readings(window_s=10.5, counters=counters or {}, work=work or {},
                    peak=peak, trace=trace)


def test_attn_kernel_share_sums_the_named_kernels_over_busy_time():
    read = reader("attn_kernel_share")
    r = readings({"paged_decode": 4.0, "duet_attention_paged": 3.0,
                  "paged_decode_splitkv": 1.0, "fusion": 2.0})
    assert read(r) == pytest.approx(80.0)
    assert read(readings({"duet_attention": 2.5}, busy_s=2.5)) == 100.0


@pytest.mark.parametrize("r", [
    readings({"closed_call": 8.9, "fusion": 1.0}),   # kernels unnamed
    readings({"paged_decode": 1.0}, busy_s=0.0),
    Readings(window_s=1.0, counters={}, work={})])    # no trace
def test_attn_kernel_share_is_absent_without_named_kernels(r):
    assert reader("attn_kernel_share")(r) is None


def test_paged_decode_roofline_is_bound_by_bytes():
    read = reader("paged_decode_roofline")
    work = {"decode_tokens": 3250, "decode_attn_bytes": 335e9,
            "decode_attn_flops": 4 * 335e9}
    r = readings({"paged_decode": 8.0, "paged_decode_splitkv": 0.93,
                  "fusion": 1.0}, work=work,
                 counters={"mux_iterations": 100, "duet_iterations": 0})
    want = 100.0 * (335e9 / V5E.hbm_bytes_per_s) / 8.93
    assert read(r) == pytest.approx(want)
    # the FLOPs would need a hundredth of the time the bytes need
    flops_bound = 100.0 * work["decode_attn_flops"] / V5E.bf16_flops / 8.93
    assert flops_bound < read(r) / 50
    # the kernel cannot take less than the least time: at that time, 100
    least = 335e9 / V5E.hbm_bytes_per_s
    assert read(readings({"paged_decode": least}, work=work)) == \
        pytest.approx(100.0)


@pytest.mark.parametrize("r", [
    readings({"closed_call": 8.9}, work={"decode_tokens": 10,
                                         "decode_attn_bytes": 1e9,
                                         "decode_attn_flops": 4e9}),
    readings({"paged_decode": 8.9}, work={"decode_tokens": 0,
                                          "decode_attn_bytes": 0.0,
                                          "decode_attn_flops": 0.0}),
    readings({"paged_decode": 8.9}, work={"decode_tokens": 10,
                                          "decode_attn_bytes": 1e9,
                                          "decode_attn_flops": 4e9},
             peak=None),
    # a duet iteration's decode rows ran in the duet grid, not this kernel
    readings({"paged_decode": 8.9}, work={"decode_tokens": 10,
                                          "decode_attn_bytes": 1e9,
                                          "decode_attn_flops": 4e9},
             counters={"mux_iterations": 100, "duet_iterations": 1}),
    Readings(window_s=1.0, counters={}, work={})])
def test_paged_decode_roofline_is_absent_without_its_inputs(r):
    assert reader("paged_decode_roofline")(r) is None
