#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets the cell up from ``--seed`` (data or weights made on the device, every
shape the traffic uses warmed and compiled, or loaded from JAX's persistent
compilation cache in ``<checkout>/.jax_cache``), measures for ``--seconds``,
then checks what the measured window produced against the configuration's
plain reference.  The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from the program's spans and
counters and a profiler trace), ``device``, with ``--trace 1`` a
``breakdown`` of device time and idle gaps, and last the numbers compared
for ``correct``, each beside its limit (also the last lines of stderr).

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result: no number here comes from a CPU.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def configure_jax_environment() -> None:
    """Before JAX starts: keep every compiled program in the checkout's
    persistent cache (or in ``JAX_COMPILATION_CACHE_DIR`` where that is
    set, which JAX reads itself), with no limit on its size, and keep the
    TPU runtime's logs out of fixed paths outside the checkout."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    # every program, however fast it compiles or large it is: a run after
    # the first compiles nothing.  A size limit on the cache (such as
    # JAX_COMPILATION_CACHE_MAX_SIZE) smaller than all of a cell's programs
    # together would evict each run's programs before the next run reads
    # them, so the cache is kept whole
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def fail(msg: str, code: int = 1) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def run(args, require_tpu: bool = True, root: Path = ROOT) -> dict:
    """One run of one cell of ``<root>/BENCHMARK.json``; returns the result
    line as a dict.  Raises ``SystemExit`` with a message where the machine
    cannot run the cell; ``require_tpu=False`` lets a test drive the rest
    of a run on the CPU."""
    from bench import harness, spec
    from bench.peaks import peaks

    cell = spec.resolve(args.workload, root=root)
    import jax

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise SystemExit(fail(f"JAX found no TPU (devices are "
                                  f"{devices[0].platform}); the benchmark "
                                  "does not fall back to the CPU"))
        if len(devices) < cell.chips:
            raise SystemExit(fail(f"{cell.name} needs {cell.chips} chips, "
                                  f"JAX sees {len(devices)}"))
    kind = devices[0].device_kind
    chip_peaks = peaks(kind) if require_tpu else {}
    system = cell.module("systems", cell.config["system"])

    compiles = harness.Compiles()
    state = system.setup(cell, args.seed)
    setup_s = time.perf_counter() - T_PROCESS
    harness.log(f"set-up: {compiles}")

    seconds = (min(args.seconds, harness.TRACE_WINDOW_S) if args.trace
               else args.seconds)
    rec = harness.Recorder(traced=bool(args.trace),
                           keep_trace=args.keep_trace)
    harness.log(f"set-up done in {setup_s:.1f} s; window of {seconds} s")
    measured = system.window(state, seconds, rec)
    memory_peak = harness.peak_memory_bytes(devices[:cell.chips])
    harness.log(f"window done: {measured.units} completed; so far "
                f"{compiles}")

    checks = system.verify(state)
    del state
    gc.collect()
    harness.log("verified")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(checks) and all(c.ok for c in checks),
              "attempted": measured.units, "failed": 0}
    if args.trace:
        window = harness.Window(rec=rec, measured=measured,
                                peaks=chip_peaks)
        result["metrics"] = harness.read_per_layer(cell, window)
        if rec.trace is not None and rec.trace.devices:
            from bench import tracefile
            device["busy_s"] = tracefile.busy_s(rec.trace)
            device["window_s"] = tracefile.window_s(rec.trace)
            result["breakdown"] = {
                "device_ops": tracefile.device_ops(rec.trace),
                "idle_gaps": tracefile.idle_gaps(rec.trace)}
    else:
        values = dict(measured.end_to_end, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True,
                    help="a cell's name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the cell's data, weights and traffic")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: report the per-layer metrics from a traced run")
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="copy the profiler's .xplane.pb of a traced run here")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        import repro.core.lazy  # noqa: F401
    except ImportError as e:
        return fail(f"the program is not in this checkout ({e})", 2)
    configure_jax_environment()
    result = run(args)
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
