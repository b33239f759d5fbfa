"""The reduction from a profiler trace to the device metrics, checked on a
trace recorded on the chip, and the reading of a trace file.

``data/heat_slice.json`` holds the events that ``tracefile.load`` kept from
a traced run of ``heat2d.iterate`` on one v5e (seed 12), cut to 60 ms: from
the middle of one fused loop of 32 iterations to the middle of the next,
so that the slice holds 32 iterations' work.  Each number is worked out a
second way here (busy time by marking 10 ns bins, the idle gap and its
label by hand), and the literals are those second computations, made when
the slice was cut.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, spec, tracefile
from bench.tracefile import Event

DATA = Path(__file__).resolve().parent / "data"
N = 4098
ITERATIONS = 32


@pytest.fixture(scope="module")
def trace():
    d = json.loads((DATA / "heat_slice.json").read_text())
    t0, t1 = d["window"]
    host = [Event(*e) for e in d["host"]]
    host.append(Event(tracefile.WINDOW, t0, t1 - t0))
    return tracefile.Trace(
        devices={k: [Event(*e) for e in v] for k, v in d["devices"].items()},
        host=sorted(host, key=lambda e: e.start_ns))


def test_busy_time_and_idle_share_by_hand(trace):
    t0, t1 = trace.window()
    (ops,) = trace.devices.values()
    width = 10.0
    mark = np.zeros(int(np.ceil((t1 - t0) / width)), bool)
    for e in ops:
        a, b = max(e.start_ns, t0), min(e.end_ns, t1)
        if b > a:
            mark[int((a - t0) // width):int(np.ceil((b - t0) / width))] = True
    assert mark.sum() * width / 1e9 == pytest.approx(0.06014779, abs=1e-8)
    assert tracefile.busy_s(trace) == pytest.approx(0.0601477805, abs=1e-12)
    assert tracefile.window_s(trace) == pytest.approx(0.0601525735,
                                                      abs=1e-12)
    assert 100 * tracefile.idle_share(trace) == pytest.approx(
        100 * 4.793e-6 / 0.0601525735, rel=1e-6)


def test_idle_gap_is_named_by_the_innermost_annotation(trace):
    # one gap between the two fused loops, 4.793 us, while the host was in
    # the program's execute stage (inside a flush, inside an iteration)
    gaps = tracefile.idle_gaps(trace)
    assert gaps == [["repro.stage.execute", pytest.approx(4.793e-6)]]


def test_device_ops_are_self_times(trace):
    """A ``while`` spans its body's ops on the same line: its self time is
    its own span less theirs, so the self times add up to the busy time."""
    t0, t1 = trace.window()
    (ops,) = trace.devices.values()
    per = tracefile.self_times(ops, t0, t1)
    assert sum(per.values()) / 1e9 == pytest.approx(
        tracefile.busy_s(trace), rel=1e-9)
    top = tracefile.device_ops(trace)
    assert [name for name, _ in top] == [
        "body.18 custom-call:tpu_custom_call f32[4096,4096]",
        "fusion.2 fusion (f32[4096,4096], f32[4096,4096], f32[4096,4096], "
        "f32[4096,4096])",
        "dynamic-update-slice.9 dynamic-update-slice f32[4098,4098]",
        "body.19 custom-call:tpu_custom_call f32[4096,4096]",
        "reshape.65 reshape f32[4098,4098]",
        "reshape.66 reshape f32[16793604]",
        "while.9 while (s32[], f32[16793604], s32[], s32[])"]
    # the loop's own time is what its body's ops leave of its span
    assert top[-1][1] == pytest.approx(2.9377e-5, rel=1e-6)


def test_op_label():
    assert tracefile.op_label(
        "%body.18 = f32[4096,4096]{1,0:T(8,128)} custom-call(f32[4096,4096]"
        "{1,0:T(8,128)} %a), custom_call_target=\"tpu_custom_call\"") == \
        "body.18 custom-call:tpu_custom_call f32[4096,4096]"
    assert tracefile.op_label(
        "%while.9 = (s32[]{:T(128)}, f32[16]{0:T(1024)}) while((s32[], "
        "f32[16]) %t), condition=%c, body=%b") == \
        "while.9 while (s32[], f32[16])"


def test_heat_roofline_of_the_slice(trace):
    """32 iterations' required bytes at 819 GB/s over the busy time."""
    prog = spec.load_module("programs", "heat2d")
    cfg = {"n": N, "dtype": "float32"}
    work = {k: v * ITERATIONS for k, v in prog.work(cfg).items()}
    rec = harness.Recorder(traced=True)
    rec.trace = trace
    w = harness.Window(rec=rec,
                       measured=harness.Measured(ITERATIONS, {}, work),
                       peaks={"hbm_byte_s": 819e9})
    share = spec.load_module("metrics", "heat_stencil_roofline").read(w)
    by_hand = 4 * (N * N + (N - 2) ** 2) * ITERATIONS / 819e9 / 0.0601477805
    assert share == pytest.approx(100 * by_hand, rel=1e-9)
    assert 8.7 < share < 8.8


def test_load_reads_host_annotations_from_a_trace_file(tmp_path):
    """A trace recorded here on the CPU: the benchmark's annotations are
    read, and with no TPU plane the device readers find nothing."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((64, 64), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracefile.WINDOW):
        with jax.profiler.TraceAnnotation("bench.iteration"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracefile.load(tmp_path)
    assert [e.name for e in tr.host] == [tracefile.WINDOW, "bench.iteration"]
    assert tr.window()[1] > tr.window()[0]
    assert tr.devices == {}
    rec = harness.Recorder(traced=True)
    rec.trace = tr
    w = harness.Window(rec=rec, measured=harness.Measured(1, {}),
                       peaks={"hbm_byte_s": 819e9})
    idle = spec.load_module("metrics", spec.reader_name("device.idle.heat"))
    assert idle.read(w) is None
