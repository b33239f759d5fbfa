"""Host milliseconds per unit of work (an iteration, a prefill) in the
program's trace and plan stages (``stage.trace`` while the tape is
recorded, then graph, partition, schedule and lower), from its own spans
over the traced window.  Reads ``plan.host_ms.<cell family>``."""

from bench.harness import PLAN_SPANS


def read(w):
    if not w.rec.spans or not w.measured.units:
        return None
    return w.rec.span_ms(PLAN_SPANS) / w.measured.units
