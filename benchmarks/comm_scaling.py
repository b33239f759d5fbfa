"""Interconnect-byte scaling of distributed fusion (ISSUE 2 acceptance).

Runs benchmark programs whose inputs are block-sharded over 1/2/4/8
simulated host devices (``--xla_force_host_platform_device_count``, set in a
subprocess per device count) and reports the fabric bytes moved by COMM ops
under the ``comm`` cost model with fusion (``greedy``) vs the unfused
singleton baseline.  The resharding pass inserts one collective per
consuming read site; fusion merges identical reshards into one collective
per block, so the fused schedule moves strictly fewer interconnect bytes.

Every run also cross-checks that ``DistBlockExecutor`` results are
bit-identical to the single-device ``BlockExecutor`` on the same program.

Usage:
    python -m benchmarks.comm_scaling                 # table over 1/2/4/8
    python -m benchmarks.comm_scaling --ci            # assert the criterion
    python -m benchmarks.comm_scaling --single 8      # one child (JSON out)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def _window_pipeline(bh, dist, n_dev, n=4096, k=4, dtype="float64"):
    """k shifted windows of one sharded vector, combined: every window read
    is misaligned with the shard grid -> one allgather per read site."""
    import numpy as np
    x = bh.asarray(np.linspace(0.0, 1.0, n, dtype=dtype))
    dist.shard(x, n=n_dev)
    w = n - k
    acc = x[0:w] * 0.0
    for i in range(k):
        acc = acc + x[i:w + i] * float(i + 1)
    return acc.numpy()


def _stencil(bh, dist, n_dev, n=256, iters=2, dtype="float64"):
    """Row-sharded 2-D Jacobi sweep: the four shifted reads are halo-
    crossing window reads of the sharded grid."""
    import numpy as np
    g = bh.asarray((np.arange(n * n, dtype=np.float64).reshape(n, n)
                    / (n * n)).astype(dtype))
    dist.shard(g, n=n_dev)
    for _ in range(iters):
        inner = (g[1:-1, :-2] + g[1:-1, 2:]
                 + g[:-2, 1:-1] + g[2:, 1:-1]) * 0.25
        g[1:n - 1, 1:n - 1] = inner
        inner.delete()
        bh.flush()
    return g.numpy()


PROGRAMS = {"window_pipeline": _window_pipeline, "stencil": _stencil}


def _run_one(name, n_dev):
    import numpy as np
    from repro.core import dist
    from repro.core import lazy as bh
    from repro.core.dist import host_mesh
    from repro.core.lazy import fresh_runtime

    fn = PROGRAMS[name]
    out = {"program": name, "devices": n_dev}
    identical = True
    for alg in ("singleton", "greedy"):
        with fresh_runtime(cost_model="comm", algorithm=alg,
                           mesh=host_mesh(n_dev)) as rt:
            got = fn(bh, dist, n_dev)
            st = rt.executor.stats
            out[f"bytes_{alg}"] = st["interconnect_bytes"]
            out[f"collectives_{alg}"] = st["collectives"]
            out[f"shard_map_blocks_{alg}"] = st["shard_map_blocks"]
        # bit-identity: DistBlockExecutor vs the plain single-device
        # BlockExecutor under the SAME partition (the executor swap must
        # not change a single bit; different partitions may legitimately
        # differ by FMA contraction, so we compare per-algorithm)
        with fresh_runtime(cost_model="comm", algorithm=alg) as rt:
            identical = identical and bool(
                np.array_equal(got, fn(bh, dist, n_dev)))
    out["bit_identical"] = identical
    return out


def _child(n_dev):
    rows = [_run_one(name, n_dev) for name in PROGRAMS]
    print(json.dumps(rows))


def _spawn(n_dev):
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if not f.startswith("--xla_force_host_platform"))
    env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={n_dev}").strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.comm_scaling", "--single",
         str(n_dev)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if res.returncode != 0:
        raise RuntimeError(f"child ({n_dev} devices) failed:\n{res.stderr}")
    return json.loads(res.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--single", type=int, default=None,
                    help="(internal) run in-process for one device count")
    ap.add_argument("--ci", action="store_true",
                    help="8-device smoke: assert fused < unfused on >= 2 "
                         "programs and bit-identical executor results")
    args = ap.parse_args()

    if args.single is not None:
        _child(args.single)
        return

    devices = [8] if args.ci else args.devices
    rows = []
    for n in devices:
        rows.extend(_spawn(n))

    hdr = (f"{'program':<18} {'dev':>4} {'unfused B':>12} {'fused B':>12} "
           f"{'saving':>8} {'coll u/f':>9} {'ident':>6}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        bu, bf = r["bytes_singleton"], r["bytes_greedy"]
        sv = f"{(1 - bf / bu) * 100:.0f}%" if bu else "-"
        print(f"{r['program']:<18} {r['devices']:>4} {bu:>12.0f} {bf:>12.0f} "
              f"{sv:>8} {r['collectives_singleton']:>4}/{r['collectives_greedy']:<4} "
              f"{str(r['bit_identical']):>6}")

    if args.ci:
        assert all(r["bit_identical"] for r in rows), \
            "DistBlockExecutor diverged from BlockExecutor"
        assert all(r["shard_map_blocks_greedy"] > 0 for r in rows), \
            "shard_map lowering never ran — every block fell back"
        improved = [r for r in rows
                    if r["devices"] == 8 and r["bytes_greedy"] < r["bytes_singleton"]]
        assert len(improved) >= 2, \
            f"fusion reduced interconnect bytes on only {len(improved)} programs"
        print("CI criterion met: fused < unfused on "
              f"{len(improved)} programs via shard_map, results bit-identical")


if __name__ == "__main__":
    main()
