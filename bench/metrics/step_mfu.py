"""Model FLOPs the window's tokens require (``bench/work.py``: prompt
positions dispatched in the window, and every decode token delivered in
it) over the device time of the serving programs in
the trace times the chip's bf16 peak, in percent. Layer: model step
(``core/lookahead`` super-iteration programs)."""


def read(r):
    if r.trace is None or r.step_device_s <= 0:
        return None
    return 100.0 * r.work["flops"] / (r.step_device_s * r.peak.bf16_flops)
