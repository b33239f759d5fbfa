"""Megabytes (1e6 B) per unit of work (a prefill) that the program's
blocks read from its buffer store as they are stored, with no relayout:
the ``stored_bytes`` args of its ``block`` spans over the traced window
(the bytes of each block's input views that are the whole base in its
buffer's shape).  A program whose ``block`` spans lack the arg reads
nothing.  Reads ``view.stored_mb.<cell family>``."""


def read(w):
    stored = [ev["args"]["stored_bytes"] for ev in w.rec.spans
              if ev.get("ph") == "X" and ev["name"] == "block"
              and "stored_bytes" in ev.get("args", {})]
    if not stored or not w.measured.units:
        return None
    return sum(stored) / 1e6 / w.measured.units
