"""Client-side arithmetic on synthetic event streams."""
import numpy as np
import pytest

from bench import readings
from bench.readings import Record


def rec(rid, submit, toks):
    r = Record(rid=rid, prompt=np.zeros(10, np.int32), output_len=len(toks),
               submit_t=submit)
    r.tok_t = list(toks)
    r.toks = [1] * len(toks)
    return r


def test_percentile_matches_linear_interpolation():
    assert readings.percentile([], 90) is None
    assert readings.percentile([5.0], 99) == 5.0
    v = list(range(1, 101))
    assert readings.percentile(v, 90) == pytest.approx(90.1)
    assert readings.percentile(v, 50) == pytest.approx(50.5)


def test_ttft_counts_first_tokens_inside_the_window_only():
    recs = [rec(0, 0.0, [1.0, 2.0]), rec(1, 0.5, [3.0, 3.5]),
            rec(2, 9.0, [11.0])]
    assert readings.ttfts(recs, 0.5, 10.0) == [1.0, 2.5]


def test_gaps_are_pooled_and_keep_burst_gaps():
    # a look-ahead burst delivers tokens together: its near-zero gaps count
    recs = [rec(0, 0.0, [1.0, 1.0, 1.0, 2.0]), rec(1, 0.0, [1.5, 4.0])]
    g = sorted(readings.gaps(recs, 0.0, 10.0))
    assert g == pytest.approx([0.0, 0.0, 1.0, 2.5])


def test_gaps_need_both_tokens_inside_the_window():
    recs = [rec(0, 0.0, [0.5, 1.5, 2.5, 3.5])]
    assert readings.gaps(recs, 1.0, 3.0) == pytest.approx([1.0])


def test_end_to_end_rates_over_the_window():
    recs = [rec(0, 0.0, [0.5, 1.0, 1.5, 2.0]), rec(1, 0.2, [1.2, 1.4])]
    m = readings.end_to_end(recs, 1.0, 3.0, setup_s=7.0)
    assert m["output_tokens_per_s"] == pytest.approx(5 / 2.0)
    assert m["setup_s"] == 7.0
    g = [0.5, 0.5, 0.2]
    assert m["tbt_mean_ms"] == pytest.approx(1e3 * np.mean(g))
    assert m["tbt_p99_ms"] == pytest.approx(1e3 * np.percentile(g, 99))
    assert m["ttft_mean_s"] == pytest.approx(1.0)


def test_window_work_counts_prompts_and_decode_tokens():
    from bench import work
    from bench.tests.tiny import tiny_arch, tiny_config
    cfg, arch = tiny_config(), tiny_arch()
    r = rec(0, 0.0, [1.0, 2.0, 3.0])
    w = readings.window_work([r], arch, cfg, 0.0, 10.0, {}, {0: 10})
    want = work.prefill_flops(arch, cfg, 10) \
        + work.decode_flops(arch, cfg, 11) + work.decode_flops(arch, cfg, 12)
    assert w["flops"] == pytest.approx(want)
    assert w["decode_tokens"] == 2 and w["prompt_tokens"] == 10


def test_window_work_charges_only_chunks_dispatched_in_the_window():
    from bench import work
    from bench.tests.tiny import tiny_arch, tiny_config
    cfg, arch = tiny_config(), tiny_arch()
    # 4 prompt positions ran before the window, 6 in it; the first token
    # came in the window and is no decode token
    r = rec(0, 0.0, [5.0])
    w = readings.window_work([r], arch, cfg, 1.0, 10.0, {0: 4}, {0: 10})
    assert w["flops"] == pytest.approx(
        work.prefill_flops(arch, cfg, 6, start=4))
    assert w["prompt_tokens"] == 6 and w["decode_tokens"] == 0
    # a prompt whose chunks all ran before the window costs it nothing
    w = readings.window_work([r], arch, cfg, 1.0, 10.0, {0: 10}, {0: 10})
    assert w["flops"] == 0.0
    # a chunk that does not end the prompt needs no output head
    assert work.prefill_flops(arch, cfg, 4, head=False) == pytest.approx(
        work.prefill_flops(arch, cfg, 4) - arch.head_flops(cfg))
