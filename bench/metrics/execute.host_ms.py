"""Host milliseconds per unit of work (an iteration, a prefill) in the
program's execute stage (``stage.execute``: dispatching and enqueueing the
blocks, which does not wait for the device), from its own spans over the
traced window.  Reads ``execute.host_ms.<cell family>``."""


def read(w):
    if not w.rec.spans or not w.measured.units:
        return None
    return w.rec.span_ms({"stage.execute"}) / w.measured.units
