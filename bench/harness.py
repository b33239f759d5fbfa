"""What every cell's run shares: the measured window's recorder, the
checks that decide ``correct`` and the per-layer readings.

A system module (``systems/<name>.py``) sets a cell up, drives its window
through a :class:`Recorder` and verifies what the window produced against
the configuration's plain reference; this module does the rest.
"""

from __future__ import annotations

import contextlib
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Mapping, Optional

from . import tracefile

#: how long a traced run measures: the profiler's trace of a longer window
#: takes long to write and read back, and a few seconds of steady state
#: hold thousands of iterations or tens of requests
TRACE_WINDOW_S = 4.0
#: the program's spans of its trace and plan stages: recording the tape,
#: then graph, partition, schedule and lower
PLAN_SPANS = frozenset({"stage.trace", "stage.graph", "stage.partition",
                        "stage.schedule", "stage.lower"})


def log(msg: str) -> None:
    """A line of progress on stderr, with the process's clock."""
    print(f"bench: [{time.perf_counter():.1f}] {msg}", file=sys.stderr,
          flush=True)


class Compiles:
    """What JAX compiled in this process: requests served from the
    persistent compilation cache, requests that missed it and were
    compiled, and the seconds spent in the backend's compiler (a hit's
    load included).  A window should add nothing."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def __str__(self) -> str:
        return (f"{self.hits} cache hits, {self.misses} compiled, "
                f"{self.seconds:.1f} s in the compiler")


@dataclass
class Check:
    """One number compared for ``correct``: it passes at or under its
    limit.  A number that is not finite fails."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Measured:
    """What a system's window hands back."""
    #: iterations or requests completed in the window
    units: int
    #: end-to-end metrics by name (``setup_s`` is the harness's own)
    end_to_end: Dict[str, float]
    #: quantities the per-layer readers divide by: required FLOPs and bytes
    #: of the whole window, tokens, and the like
    work: Dict[str, float] = field(default_factory=dict)


def counter_delta(before: Mapping, after: Mapping) -> Dict:
    """``after - before`` over nested counter mappings."""
    out: Dict = {}
    for k, v in after.items():
        if isinstance(v, Mapping):
            out[k] = counter_delta(before.get(k, {}), v)
        else:
            out[k] = v - before.get(k, 0)
    return out


class _Mirrored:
    """A program span that also opens a profiler annotation, so that the
    device trace can tell which stage the host was in."""
    __slots__ = ("_span", "_ann")

    def __init__(self, span, name: str):
        import jax
        self._span = span
        self._ann = jax.profiler.TraceAnnotation("repro." + name)

    def __enter__(self):
        self._ann.__enter__()
        return self._span.__enter__()

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._ann.__exit__(*exc)


def _mirroring_tracer():
    """The program's span tracer, with each span mirrored into the
    profiler's trace as ``repro.<span name>``."""
    from repro.core.obs.trace import Tracer

    class MirroringTracer(Tracer):
        def span(self, name, args=None):
            return _Mirrored(super().span(name, args), name)

    return MirroringTracer()


class Recorder:
    """Times the measured window and, in a traced run, records the
    program's spans and counters and the profiler's trace over it."""

    def __init__(self, traced: bool, keep_trace: Optional[Path] = None):
        self.traced = traced
        self.keep_trace = keep_trace
        self.seconds = 0.0
        self.spans: List[Dict[str, Any]] = []
        self.counters: Dict = {}
        self.trace: Optional[tracefile.Trace] = None

    def annotate(self, name: str):
        """A host annotation in the profiler's trace (a no-op untraced)."""
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self, executor):
        """Wrap the measured window.  The body must end on a host read of
        what the window computed, so the time covers all of its work."""
        if not self.traced:
            t0 = time.perf_counter()
            yield self
            self.seconds = time.perf_counter() - t0
            return
        import jax
        from repro.core.obs import trace as program_trace

        tmp = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        before = executor.stats.snapshot()
        program_trace.enable(_mirroring_tracer())
        jax.profiler.start_trace(str(tmp))
        try:
            t0 = time.perf_counter()
            with self.annotate(tracefile.WINDOW):
                yield self
            self.seconds = time.perf_counter() - t0
        finally:
            jax.profiler.stop_trace()
            tracer = program_trace.disable()
        self.spans = list(tracer.events)
        self.counters = counter_delta(before, executor.stats.snapshot())
        try:
            path = tracefile.find_xplane(tmp)
            if self.keep_trace is not None:
                self.keep_trace.parent.mkdir(parents=True, exist_ok=True)
                shutil.copyfile(path, self.keep_trace)
            self.trace = tracefile.load(path)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def span_ms(self, names) -> float:
        """Milliseconds the program spent in spans of these names."""
        return sum(ev.get("dur", 0.0) for ev in self.spans
                   if ev.get("ph") == "X" and ev["name"] in names) / 1e3


@dataclass
class Window:
    """What a per-layer metric's reader reads: the traced window."""
    rec: Recorder
    measured: Measured
    peaks: Dict[str, float]


def read_per_layer(cell, window: Window) -> Dict[str, Dict[str, Any]]:
    """Each of the cell's per-layer metrics, from its reader
    (``metrics/<name>.py`` or its family's, ``read(window) -> float |
    None``).  A reader that finds nothing to read returns None and the
    metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"]).read(window)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def peak_memory_bytes(devices) -> Optional[int]:
    """The peak on the fullest chip, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
