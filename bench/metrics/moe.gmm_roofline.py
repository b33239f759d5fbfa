"""The grouped expert product's share of its roofline: the least time its
work could take on this chip over the device time its operations took in
the traced window.

* FLOPs: the rows routed to the held experts, summed over the program's
  ``moe.rows`` spans (one a prefill, every expert layer), times
  ``2 * 3 * d * f`` (gate, up and down of one row: ``gmm_row_flops``);
* bytes: the held experts' weights once per ``ragged_matmul`` pass (the
  executor's ``ragged_matmul_blocks`` over the window, times
  ``gmm_weight_bytes``), plus each routed row's reads and writes through
  the layer's three passes (``gmm_row_bytes``);
* least time: the larger of FLOPs over the bfloat16 peak and bytes over
  the HBM bandwidth (``counts_mla_moe``; the configuration's float32 at
  ``highest`` takes several bfloat16 passes, as ``lm.step_mfu`` says);
* device time: the self time, inside the window, of the device operations
  named ``ragged-dot*`` (the TPU's grouped-product kernel and the metadata
  it computes from the group sizes).

A program without the spans or the counter, or a trace without those
operations, reads nothing.  Reads ``moe.gmm_roofline.<cell family>``."""

from bench import tracefile

#: what the grouped product's device operations are named by
OP_NAME = "ragged-dot"


def gmm_seconds(trace) -> float:
    """Self seconds of the grouped product's operations inside the
    window, averaged over the trace's devices."""
    t0, t1 = trace.window()
    ns = sum(v for ops in trace.devices.values()
             for label, v in tracefile.self_times(ops, t0, t1).items()
             if label.startswith(OP_NAME))
    return ns / max(1, len(trace.devices)) / 1e9


def read(w):
    flop_s, byte_s = w.peaks.get("bf16_flop_s"), w.peaks.get("hbm_byte_s")
    work = w.measured.work
    rows = sum(ev["args"]["rows"] for ev in w.rec.spans
               if ev.get("ph") == "X" and ev["name"] == "moe.rows")
    passes = w.rec.counters.get("ragged_matmul_blocks")
    if (not flop_s or not byte_s or not rows or not passes
            or "gmm_row_flops" not in work or w.rec.trace is None
            or not w.rec.trace.devices):
        return None
    seconds = gmm_seconds(w.rec.trace)
    if seconds <= 0:
        return None
    flops = rows * work["gmm_row_flops"]
    nbytes = passes * work["gmm_weight_bytes"] + rows * work["gmm_row_bytes"]
    return 100.0 * max(flops / flop_s, nbytes / byte_s) / seconds
