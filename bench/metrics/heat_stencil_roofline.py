"""The stencil's share of its roofline: the least time the window's
iterations could take on this chip, their required HBM bytes
(``counts.jacobi_bytes``: read the grid once, write its interior once)
over the HBM bandwidth, divided by the device's busy time in the traced
window.  Every operation on the device counts, so relayout copies around
the kernel count against it, whatever implements them.  Memory bounds it:
4 FLOPs per 8 bytes lie far under the chip's ridge point."""

from bench import tracefile


def read(w):
    peak = w.peaks.get("hbm_byte_s")
    if not peak or w.rec.trace is None or not w.rec.trace.devices:
        return None
    busy = tracefile.busy_s(w.rec.trace)
    if busy <= 0:
        return None
    return 100.0 * w.measured.work["bytes"] / peak / busy
