"""The readers of the program's host-edge and merge-cache spans, and of
the device's idle time that no program span covers, each on a window made
by hand: each reads what its docstring says, and a window without its
spans or its trace (a program that lacks them) reads nothing."""

from __future__ import annotations

import pytest

from bench import harness, spec, tracefile
from bench.tracefile import Event

#: a traced window of 4 prefills: two adopts and a read per prefill, two
#: merge-cache probes that missed and two that hit
SPANS = (
    [{"name": "adopt", "ph": "X", "dur": 1500.0,
      "args": {"bytes": 3_000_000, "flush": i}} for i in range(4)]
    + [{"name": "adopt", "ph": "X", "dur": 500.0,
        "args": {"bytes": 1_000_000, "flush": i}} for i in range(4)]
    + [{"name": "sync.read", "ph": "X", "dur": 20_000.0,
        "args": {"bytes": 4096, "flush": i}} for i in range(4)]
    + [{"name": "plan.lookup", "ph": "X", "dur": 10.0,
        "args": {"hit": h, "key": "0123456789abcdef", "flush": i}}
       for i, h in enumerate(("miss", "memory", "miss", "disk"))]
    + [{"name": "cache.exec", "ph": "i", "args": {"hit": True}},
       {"name": "stage.execute", "ph": "X", "dur": 7000.0, "args": {}}])
#: what a program without the new spans records
OLD_SPANS = [ev for ev in SPANS if ev["name"] in ("cache.exec",
                                                  "stage.execute")]


def _window(spans=(), trace=None, units=4):
    rec = harness.Recorder(traced=True)
    rec.spans = list(spans)
    rec.trace = trace
    return harness.Window(rec=rec, measured=harness.Measured(units, {}),
                          peaks={})


def _reader(metric):
    return spec.load_module("metrics", spec.reader_name(metric))


def _trace(host):
    """Device busy over [0, 100] and [200, 500] and [600, 1000] ns of a
    1000 ns window: idle gaps [100, 200] (middle 150) and [500, 600]
    (middle 550)."""
    ops = [Event("op", 0, 100), Event("op", 200, 300), Event("op", 600, 400)]
    host = sorted(host + [Event(tracefile.WINDOW, 0, 1000)],
                  key=lambda e: e.start_ns)
    return tracefile.Trace(devices={"/device:TPU:0": ops}, host=host)


@pytest.mark.parametrize("metric, want", [
    ("adopt.host_ms.lm", (4 * 1.5 + 4 * 0.5) / 4),
    ("adopt.mb.lm", (4 * 3.0 + 4 * 1.0) / 4),
    ("sync.wait_ms.lm", 20.0),
    ("plan.merge_hit.lm", 50.0),
])
def test_span_reader_by_hand(metric, want):
    assert _reader(metric).read(_window(SPANS)) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["adopt.host_ms.lm", "adopt.mb.lm",
                                    "sync.wait_ms.lm", "plan.merge_hit.lm"])
def test_span_reader_without_its_spans_reads_nothing(metric):
    assert _reader(metric).read(_window(OLD_SPANS)) is None
    assert _reader(metric).read(_window()) is None


def test_unattributed_idle_by_hand():
    """The first gap lies under a program span inside the benchmark's
    request, so it is the program's; the second lies under the window
    alone: 100 ns of 1000."""
    read = _reader("device.idle_unattributed.lm").read
    covered = _trace([Event("bench.prefill", 0, 400),
                      Event("repro.stage.trace", 120, 60)])
    assert read(_window(trace=covered)) == pytest.approx(10.0)
    # the same request without the program's span: both gaps are the
    # benchmark's, 200 ns of 1000
    bare = _trace([Event("bench.prefill", 0, 400)])
    assert read(_window(trace=bare)) == pytest.approx(20.0)


def test_unattributed_idle_without_a_device_reads_nothing():
    read = _reader("device.idle_unattributed.lm").read
    assert read(_window()) is None
    host_only = tracefile.Trace(host=[Event(tracefile.WINDOW, 0, 1000)])
    assert read(_window(trace=host_only)) is None
