"""Share of the window's planned iterations that the multiplexer ran in
duet mode (``mux.stats`` deltas), in percent. Layer: scheduler
(``serving/scheduler`` + ``core/multiplexer``)."""


def read(r):
    n = r.counters["mux_iterations"]
    if n <= 0:
        return None
    return 100.0 * r.counters["duet_iterations"] / n
