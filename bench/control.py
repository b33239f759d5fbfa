#!/usr/bin/env python3
"""Readings of a cell's control on the chip: the plain reference put in the
program's place and computed in the next lower precision than the
configuration states.  The benchmark's own runs never run it; its readings
set the upper end of each limit in ``references/`` (``PERF.md`` gives them).

    python3 bench/control.py --workload <name> --seeds 1,2,3 --units N

The cell's system module computes it (``systems/<system>.py``,
``control(cell, seed, units) -> {number: reading}``), over ``N`` units of
the seed's work: iterations of an array program, prompts of a model's
traffic.  Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--units", type=int, required=True,
                    help="iterations (heat) or prompts (LM) to compare")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run as bench_run
    from bench import spec
    bench_run.configure_jax_environment()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 1
    import repro.core.lazy  # noqa: F401  (the program's float64 mode, as in a run)

    cell = spec.resolve(args.workload)
    system = cell.module("systems", cell.config["system"])
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "units": args.units,
                          **system.control(cell, seed, args.units)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
