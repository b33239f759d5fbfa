"""Megabytes (1e6 B) per unit of work (a prefill) that the program copied
from the host to the device in ``Runtime.adopt``: the ``bytes`` of its
``adopt`` spans over the traced window.  A program without the span reads
nothing.  Reads ``adopt.mb.<cell family>``."""


def read(w):
    adopted = [ev["args"]["bytes"] for ev in w.rec.spans
               if ev.get("ph") == "X" and ev["name"] == "adopt"]
    if not adopted or not w.measured.units:
        return None
    return sum(adopted) / 1e6 / w.measured.units
