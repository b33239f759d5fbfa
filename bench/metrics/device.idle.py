"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / (window), from the
profiler's trace.  Reads ``device.idle.<cell family>``."""

from bench import tracefile


def read(w):
    if w.rec.trace is None or not w.rec.trace.devices:
        return None
    return 100.0 * tracefile.idle_share(w.rec.trace)
