"""From a profiler trace to device busy time, idle gaps and op times.

The JAX profiler writes an ``.xplane.pb`` file.  :func:`load` keeps what the
benchmark reads from it: the operations each TPU ran (its ``XLA Ops``
line), and the host annotations the benchmark opened (names starting with
``bench.``, and the program's own spans mirrored as ``repro.<span>``).
Everything else here reduces those events, on the clock the profiler put
them on, to the numbers the harness reports.  The reduction is plain
interval arithmetic so that a reader can check it by hand
(``tests/test_bench_tracefile.py`` does, on a trace recorded on the chip).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

#: a TPU's own plane; other planes are host threads or metadata
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the line of a device plane that holds one event per operation run
OPS_LINE = "XLA Ops"
#: host annotations the benchmark reads
HOST_PREFIXES = ("bench.", "repro.")
#: the annotation that spans the measured window
WINDOW = "bench.window"


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    #: per device plane, its operations in start order
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    #: the benchmark's host annotations, in start order
    host: List[Event] = field(default_factory=list)

    def window(self) -> Tuple[float, float]:
        """Start and end of the one ``bench.window`` annotation."""
        w = [e for e in self.host if e.name == WINDOW]
        if len(w) != 1:
            raise ValueError(f"trace holds {len(w)} {WINDOW!r} annotations,"
                             " want exactly one")
        return w[0].start_ns, w[0].end_ns


def find_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {directory}, "
                                f"found {[str(p) for p in found]}")
    return found[0]


def load(path: Path) -> Trace:
    """Read an ``.xplane.pb`` file (or the one under a directory)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    data = ProfileData.from_file(str(path))
    out = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [Event(e.name, float(e.start_ns), float(e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            out.devices[plane.name] = sorted(ops, key=lambda e: e.start_ns)
        elif plane.name.startswith("/host:"):
            out.host.extend(
                Event(e.name, float(e.start_ns), float(e.duration_ns))
                for line in plane.lines for e in line.events
                if e.name.startswith(HOST_PREFIXES))
    out.host.sort(key=lambda e: e.start_ns)
    return out


def clip(events: Sequence[Event], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """``(start, end)`` of each event's part inside ``[t0, t1]``."""
    out = []
    for e in events:
        a, b = max(e.start_ns, t0), min(e.end_ns, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge overlapping ``(start, end)`` intervals."""
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which some operation ran, averaged over
    the devices in the trace."""
    t0, t1 = trace.window()
    if not trace.devices:
        raise ValueError("the trace holds no TPU plane")
    total = sum(b - a for ops in trace.devices.values()
                for a, b in union(clip(ops, t0, t1)))
    return total / len(trace.devices) / 1e9


def window_s(trace: Trace) -> float:
    t0, t1 = trace.window()
    return (t1 - t0) / 1e9


def idle_share(trace: Trace) -> float:
    """1 - busy / window."""
    return 1.0 - busy_s(trace) / window_s(trace)


def op_label(hlo: str) -> str:
    """A short name for an ``XLA Ops`` event, whose name is the op's HLO
    text: ``<name> <opcode> <result shape>``, layouts dropped, with a
    custom call's target (``custom-call:tpu_custom_call`` is a Pallas
    kernel)."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    opcode = rest.split("(", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    if target:
        opcode += ":" + target.group(1)
    shape = re.sub(r"\{[^{}]*\}", "", shape)
    if len(shape) > 80:
        shape = shape[:77] + "..."
    return f"{name.lstrip('%')} {opcode} {shape}"


def self_times(ops: Sequence[Event], t0: float, t1: float
               ) -> Dict[str, float]:
    """Nanoseconds inside ``[t0, t1]`` that each op label ran outside the
    ops nested in it: a ``while`` spans its body's ops on the same line,
    and counting both would count the body twice."""
    per: Dict[str, float] = {}
    stack: List[List] = []          # [end, label, clipped duration]

    def close(entry):
        per[entry[1]] = per.get(entry[1], 0.0) + entry[2]

    for e in sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0] <= e.start_ns:
            close(stack.pop())
        a, b = max(e.start_ns, t0), min(e.end_ns, t1)
        d = max(0.0, b - a)
        if stack and e.end_ns <= stack[-1][0]:
            stack[-1][2] -= d
        stack.append([e.end_ns, op_label(e.name), d])
    while stack:
        close(stack.pop())
    return per


def device_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` operations that took most device time inside the window,
    by self time, as ``[label, seconds]`` averaged over devices."""
    t0, t1 = trace.window()
    per: Dict[str, float] = {}
    for ops in trace.devices.values():
        for label, ns in self_times(ops, t0, t1).items():
            per[label] = per.get(label, 0.0) + ns
    n = max(1, len(trace.devices))
    top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in top]


def _host_label(host: Sequence[Event], t: float) -> str:
    """The innermost annotation open at ``t``: the shortest of those that
    cover it, since spans nest inside the spans that opened them."""
    best: Optional[Event] = None
    for e in host:
        if e.start_ns > t:
            break
        if e.end_ns >= t and (best is None or e.dur_ns < best.dur_ns):
            best = e
    return best.name if best is not None else "(no annotation)"


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """Device idle time inside the window, by what the host was doing:
    every gap between busy intervals is named by the innermost host
    annotation open at its midpoint, and the ``k`` names with the most idle
    seconds (averaged over devices) are returned as ``[name, seconds]``."""
    t0, t1 = trace.window()
    per: Dict[str, float] = {}
    for ops in trace.devices.values():
        edge = t0
        for a, b in union(clip(ops, t0, t1)) + [(t1, t1)]:
            if a > edge:
                label = _host_label(trace.host, (edge + a) / 2)
                per[label] = per.get(label, 0.0) + (a - edge)
            edge = max(edge, b)
    n = max(1, len(trace.devices))
    top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in top]
