"""Host milliseconds per unit of work (a prefill) in the program's
``sync.read`` spans: from the end of the flush that computed an array the
host asked for until that array is on the host, which is mostly the wait
for the device to drain its queue.  From the program's own spans over the
traced window; a program without the span reads nothing.  Reads
``sync.wait_ms.<cell family>``."""


def read(w):
    if not w.measured.units or not any(
            ev["name"] == "sync.read" for ev in w.rec.spans):
        return None
    return w.rec.span_ms({"sync.read"}) / w.measured.units
