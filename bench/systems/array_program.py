"""An iterative array program on the lazy runtime, as its user runs it.

The configuration names the program (``programs/<program>.py``), its sizes
and the ``Runtime`` options; the traffic mix says how the one client drives
it (``kind: "iterate"``):

* ``flush_every``: iterations recorded per ``flush`` (1: one tape each, as
  the paper's Benchpress programs record them);
* ``read_every``: read the whole state to the host every this many
  iterations (0: only when the window closes).

The window ends on a host read of the state, so every iteration counted
has completed.  ``iter_ms`` is the window's wall time over its iterations.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass
from typing import Any, List

import numpy as np

from bench.harness import Check, Measured, Recorder, log

#: iterations run twice in set-up, each time ending on a read: enough for
#: the loop fuser to arm (3 flushes by default), fill its queue to capacity
#: twice (32 iterations by default) and drain a part-full queue, so that
#: every program the window runs is compiled before it opens
WARM_ITERATIONS = 80
TRAFFIC_KEYS = {"kind", "clients", "flush_every", "read_every"}


@dataclass
class State:
    cell: Any
    seed: int
    program: Any
    rt: Any
    data: Any
    initial: np.ndarray
    flush_every: int
    read_every: int
    #: iterations the state has been through, set-up included
    iterations: int = 0
    result: Any = None


def _traffic(cell):
    tr = cell.traffic
    unknown = set(tr) - TRAFFIC_KEYS
    if tr.get("kind") != "iterate" or unknown:
        raise ValueError(f"{cell.traffic_name}: not an iterate mix "
                         f"(kind {tr.get('kind')!r}, unknown keys "
                         f"{sorted(unknown)})")
    if tr.get("clients", 1) != 1:
        raise ValueError(f"{cell.traffic_name}: one client drives an "
                         "array program")
    return int(tr.get("flush_every", 1)), int(tr.get("read_every", 0))


def setup(cell, seed: int) -> State:
    from repro.core import lazy as bh
    from repro.core.lazy import Runtime

    cfg = cell.config
    program = cell.module("programs", cfg["program"])
    flush_every, read_every = _traffic(cell)
    initial = program.initial(cfg, seed)
    rt = Runtime(**cfg.get("runtime", {}))
    with rt.activate():
        data = program.adopt(bh, initial)
    st = State(cell=cell, seed=seed, program=program, rt=rt, data=data,
               initial=initial, flush_every=flush_every,
               read_every=read_every)
    log("initial state adopted by the program")
    for _ in range(2):
        _drive(st, Recorder(traced=False), iterations=WARM_ITERATIONS)
        log(f"warm-up of {WARM_ITERATIONS} iterations")
    return st


def _drive(st: State, rec: Recorder, *, iterations: int = 0,
           seconds: float = 0.0) -> int:
    """Iterate until ``iterations`` are done, or until ``seconds`` have
    passed, then read the state; returns the iterations run."""
    from repro.core import lazy as bh

    prog, data = st.program, st.data
    t0 = time.perf_counter()
    i = 0
    with st.rt.activate():
        while True:
            with rec.annotate("bench.iteration"):
                prog.step(bh, data)
                i += 1
                if i % st.flush_every == 0:
                    bh.flush()
            if st.read_every and i % st.read_every == 0:
                with rec.annotate("bench.read"):
                    prog.read(data)
            if (iterations and i >= iterations) or \
                    (seconds and time.perf_counter() - t0 >= seconds):
                break
        with rec.annotate("bench.read"):
            st.result = prog.read(data)
    st.iterations += i
    return i


def window(st: State, seconds: float, rec: Recorder) -> Measured:
    with rec.window(st.rt.executor):
        n = _drive(st, rec, seconds=seconds)
    per_iteration = st.program.work(st.cell.config)
    return Measured(units=n,
                    end_to_end={"iter_ms": rec.seconds * 1e3 / n},
                    work={k: v * n for k, v in per_iteration.items()})


def verify(st: State) -> List[Check]:
    """Free the program's state, then run the plain reference for as many
    iterations from the same initial state and compare."""
    got, initial, n = st.result, st.initial, st.iterations
    cfg = st.cell.config
    st.data = st.rt = st.result = None
    gc.collect()
    ref = st.cell.module("references", cfg["reference"])
    return ref.compare(got, ref.reference(initial, n, cfg["dtype"]))


def control(cell, seed: int, units: int) -> dict:
    """The plain reference in the program's place, computed in bfloat16
    (one step below the configuration's float32): ``units`` iterations
    from the seed's initial state, compared as a run compares its state."""
    cfg = cell.config
    prog = cell.module("programs", cfg["program"])
    ref = cell.module("references", cfg["reference"])
    initial = prog.initial(cfg, seed)
    want = ref.reference(initial, units, cfg["dtype"])
    got = ref.reference(initial, units, "bfloat16")
    return {c.name: c.value for c in ref.compare(got, want)}
