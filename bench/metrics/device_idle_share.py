"""1 - (union of device op intervals) / traced window, in percent. Layer:
device."""


def read(r):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * r.trace.idle_share
