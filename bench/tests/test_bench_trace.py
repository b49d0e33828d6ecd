"""Trace reduction: busy union, idle share, time per op family, gaps."""
import time

import pytest

from bench import devtrace


def test_union_and_reduce_on_synthetic_events():
    ev = [("fusion.1", 0, 10), ("fusion.2", 5, 10), ("custom-call.3", 30, 5),
          ("copy", 90, 20)]
    assert devtrace.union(ev) == [(0, 15), (30, 35), (90, 110)]
    r = devtrace.reduce(ev, 0, 100)
    assert r.busy_s == pytest.approx(30e-9)   # copy clipped at 100
    assert r.window_s == pytest.approx(100e-9)
    assert r.idle_share == pytest.approx(0.7)
    assert r.by_name["fusion"] == pytest.approx(20e-9)
    assert r.by_name["custom-call"] == pytest.approx(5e-9)
    assert r.by_name["copy"] == pytest.approx(10e-9)
    assert devtrace.op_family(
        "%duet_attention_paged.35 = bf16[8,4]{1,0} custom-call(s32[8] "
        "%copy-done.264), custom_call_target=\"tpu_custom_call\"") \
        == "duet_attention_paged"
    assert sorted(r.gaps) == [(15, 30), (35, 90)]
    named = devtrace.name_gaps(r.gaps, [("plan", 36, 50), ("get", 10, 10)])
    assert named[0] == ("plan", pytest.approx(55e-9))
    assert named[1] == ("get", pytest.approx(15e-9))


def test_reduction_of_a_recorded_trace(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tracer = devtrace.Tracer(str(tmp_path))
    tracer.start()
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        for _ in range(3):
            f(x).block_until_ready()
            time.sleep(0.02)
    prof = tracer.stop()
    t0, t1 = devtrace.window_bounds(prof)
    assert t1 - t0 >= 0.06e9
    # the CPU has no device plane: its executor threads stand in for one
    ops = devtrace.line_events(prof, lambda n: n.startswith("/host:"),
                               lambda n: "PjRtCpuClient" in n)
    ops = [e for e in ops if e[2] > 0]
    r = devtrace.reduce(ops, t0, t1)
    assert 0.0 < r.busy_s < r.window_s
    assert 0.0 < r.idle_share < 1.0
    assert sum(r.by_name.values()) >= r.busy_s * (1 - 1e-9)
    idle = sum(b - a for a, b in r.gaps) / 1e9
    assert idle + r.busy_s == pytest.approx(r.window_s)
    assert devtrace.describe(prof)
