"""Model FLOP utilization of the whole prefill step: the model FLOPs the
window's prefills require (``counts.prefill_flops``) over the window's
wall time and the chip's bfloat16 peak.  The configuration computes in
float32 at highest precision, which takes several bfloat16 passes, so the
bfloat16 peak is what a change of precision would be measured against."""


def read(w):
    peak = w.peaks.get("bf16_flop_s")
    if not peak or not w.rec.seconds or "flops" not in w.measured.work:
        return None
    return 100.0 * w.measured.work["flops"] / w.rec.seconds / peak
