"""Host milliseconds per unit of work (a prefill) in the program's
``adopt`` spans: host arrays copied to the device by ``Runtime.adopt``
(a prefill's zeroed KV caches, its tokens), from the program's own spans
over the traced window.  A program without the span reads nothing.
Reads ``adopt.host_ms.<cell family>``."""


def read(w):
    if not w.measured.units or not any(
            ev["name"] == "adopt" for ev in w.rec.spans):
        return None
    return w.rec.span_ms({"adopt"}) / w.measured.units
