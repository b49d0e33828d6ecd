"""Jitted programs lowered (first met) by JAX between window start and end, from
its compile events (``bench/clock.py``). Layer: engine
(``serving/async_engine``), whose dispatch cache misses are the source.
Should be 0: the warm-up replays the window's own schedule."""


def read(r):
    return float(r.counters["lowerings"])
