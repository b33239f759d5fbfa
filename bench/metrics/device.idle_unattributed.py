"""Share of the traced window in which the device was idle while no span
of the program was open: the idle gaps that ``tracefile.idle_gaps`` names
by one of the benchmark's own annotations (``bench.*``) or by none,
over the window.  What the program's spans do not cover, the program's
tracing cannot explain.  Reads ``device.idle_unattributed.<cell family>``."""

from bench import tracefile


def read(w):
    t = w.rec.trace
    if t is None or not t.devices:
        return None
    gaps = tracefile.idle_gaps(t, k=len(t.host) + 1)
    idle = sum(s for label, s in gaps
               if label.startswith("bench.") or label == "(no annotation)")
    return 100.0 * idle / tracefile.window_s(t)
