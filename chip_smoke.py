#!/usr/bin/env python3
"""Smoke run of the lazy fusion runtime on a TPU.

Drives the runtime's main path once, through the entry points a user
calls, on one TPU chip:

1. ``programs``: three Benchpress programs (``benchmarks/programs.py``) in
   float32 through ``Runtime(algorithm="greedy", backend="pallas")`` — the
   heat-equation stencil on a 1026^2 grid, lattice-Boltzmann D3Q19 on
   128^3 = 2.1e6 cells and Black-Scholes over 2^23 options — each checked
   against the same program run unfused on XLA (``algorithm="singleton",
   backend="xla"``) on the same chip;
2. ``server``: two tenants send requests of 2**22 elements concurrently
   through ``repro.core.serve.Server``, checked against a
   ``batching=False`` server;
3. ``lm``: ``LazyTransformer`` prefill (batch 2, 512 tokens) and 3 decode
   steps at qwen1.5-4b's published widths (d_model 2560, 20 MHA heads x
   128, d_ff 6912, vocab 151936) with random weights, checked against the
   jitted direct model under ``highest`` matmul precision.

``--chips 4`` runs only the sharded path instead: the two programs of
``benchmarks/comm_scaling.py`` at 2**24 elements on a 4-chip mesh through
the ``shard_map`` backend, checked against a one-device run.

Every phase asserts that kernels compiled to Mosaic (no interpret mode),
that Pallas or an LM claimant ran blocks, and that no backend failed.
Detail goes to earlier lines; the last line of stdout is one JSON object
naming the device.  The script exits non-zero on the first failure, and
when JAX finds no TPU: it never falls back to the CPU.  The seconds it
prints come from one cold and one warm pass: a smoke run, not a
measurement.

Usage:  python3 chip_smoke.py [--chips 4] [--seed 0]
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

#: results are compared as  max|got - ref| <= RTOL * max|ref|  (float32
#: blocks reassociate sums and may contract mul+add differently from the
#: unfused reference; a few iterations keep that within 1e-4 of scale)
PROGRAM_RTOL = 1e-4
#: both servers run the same plan on the same chip: only the window differs
SERVER_RTOL = 1e-6
#: logits vs the jitted direct model, both at ``highest`` precision: 4
#: layers of float32 matmuls and reductions in another order
LM_RTOL = 2e-3
SHARDED_RTOL = 1e-6

#: (program, keyword arguments).  Lattice-Boltzmann is cut from the
#: paper's 150^3 cells to 128^3: its streaming windows have a minor dim
#: that is not a multiple of 128, and XLA's TPU compile time for those
#: relayouts grows faster than their size — about 100 s for the program and
#: its reference at 128^3, 490 s at 150^3 (compiled for v5e without a chip)
PROGRAMS = (
    ("heat_equation", dict(iters=10, n=1026)),
    ("lattice_boltzmann", dict(iters=3, n=128)),
    ("black_scholes", dict(iters=5, n=1 << 23)),
)


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check_close(what: str, got, ref, rtol: float) -> float:
    """Fail unless ``got`` is finite, shaped like ``ref`` and within
    ``rtol * max|ref|`` of it everywhere; returns the max abs error."""
    got, ref = np.asarray(got), np.asarray(ref)
    if got.shape != ref.shape or got.dtype != ref.dtype:
        raise SmokeFailure(f"{what}: got {got.shape} {got.dtype}, "
                           f"reference {ref.shape} {ref.dtype}")
    if not np.all(np.isfinite(got)):
        raise SmokeFailure(f"{what}: non-finite values")
    err = float(np.max(np.abs(got.astype(np.float64) - ref)))
    bound = rtol * float(np.max(np.abs(ref)))
    log(f"  {what}: max|got-ref| = {err!r}  (bound {bound!r})")
    if not err <= bound:
        raise SmokeFailure(f"{what}: max|got-ref| = {err} > {bound}")
    return err


def lowering_report(rt, what: str) -> dict:
    """Blocks per backend and decline slugs of one runtime's executor;
    fails on interpret mode and on any ``(backend, "error")`` slug."""
    ex = rt.executor
    if ex.lowering_context().interpret:
        raise SmokeFailure(f"{what}: Pallas kernels run in interpret mode")
    st = ex.stats.snapshot()
    blocks = {k: v for k, v in st["backend_blocks"].items() if v}
    declines = {f"{b}:{r}": n for b, rs in st["backend_fallbacks"].items()
                for r, n in rs.items() if n}
    errors = [k for k in declines if k.endswith(":error")]
    if errors:
        raise SmokeFailure(f"{what}: backend errors {errors}")
    log(f"  {what}: blocks {blocks}  declines {declines}")
    return st


def phase_programs(programs=PROGRAMS) -> None:
    import benchmarks.programs as P
    from repro.core.lazy import fresh_runtime

    for name, kw in programs:
        fn = getattr(P, name)
        with fresh_runtime(algorithm="singleton", backend="xla"):
            ref = np.asarray(fn(**kw, dtype=np.float32))
        with fresh_runtime(algorithm="greedy", backend="pallas") as rt:
            t0 = time.perf_counter()
            got = np.asarray(fn(**kw, dtype=np.float32))
            cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            fn(**kw, dtype=np.float32)       # same executables, warm
            warm = time.perf_counter() - t0
            st = lowering_report(rt, name)
        if st["pallas_blocks"] <= 0:
            raise SmokeFailure(f"{name}: no block ran as a Pallas kernel")
        log(f"  {name}: compile_s (cold - warm) = {cold - warm!r}  "
            f"run_s (warm) = {warm!r}")
        check_close(name, got, ref, PROGRAM_RTOL)


def _shared_request(data: np.ndarray):
    """Identical structure for every tenant: coalescable."""
    from repro.core import lazy as bh

    def fn():
        a = bh.asarray(data)
        b = bh.floor((a * 2.0 + 3.0) % 1021.0)
        return bh.maximum(b, a) + b.sum().broadcast_to(a.shape)
    return fn


def _tenant_request(data: np.ndarray, tenant: int):
    """A literal per tenant: never coalesces across tenants."""
    from repro.core import lazy as bh
    scale = float(tenant + 2)

    def fn():
        a = bh.asarray(data)
        return bh.floor((a * scale) % 1021.0) + a
    return fn


def phase_server(size: int = 1 << 22, tenants: int = 2, requests: int = 3,
                 seed: int = 0) -> None:
    from repro.core.serve import Server

    rng = np.random.default_rng(seed)
    load = []
    for t in range(tenants):
        row = []
        for r in range(requests):
            data = np.floor(rng.random(size, dtype=np.float32) * 16.0)
            row.append(_shared_request(data) if r % 2
                       else _tenant_request(data, t))
        load.append(row)

    srv = Server(algorithm="greedy", backend="pallas")
    results = {t: [] for t in range(tenants)}
    errors = []

    def run(t: int) -> None:
        try:
            for fn in load[t]:
                results[t].append(srv.submit(t, fn))
        except Exception as e:        # delivered to the main thread below
            errors.append((t, e))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=run, args=(t,))
               for t in range(tenants)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    wall = time.perf_counter() - t0
    if any(th.is_alive() for th in threads):
        raise SmokeFailure("server: a tenant thread did not finish")
    if errors:
        raise SmokeFailure(f"server: tenant failures {errors}") \
            from errors[0][1]
    st = lowering_report(srv.runtime, "server")
    if st["pallas_blocks"] <= 0:
        raise SmokeFailure("server: no block ran as a Pallas kernel")
    log(f"  server: {tenants} tenants x {requests} requests of {size} "
        f"elements in {wall!r} s (cold)")

    ref_srv = Server(algorithm="greedy", backend="pallas", batching=False)
    for t in range(tenants):
        for r, fn in enumerate(load[t]):
            check_close(f"server tenant {t} request {r}", results[t][r],
                        ref_srv.submit(t, fn), SERVER_RTOL)


def lm_config(n_layers: int = 4):
    """qwen1.5-4b at its published widths, cut to ``n_layers`` of 40
    layers, without the qkv bias the lazy lane rejects, in float32."""
    from repro.configs.qwen15_4b import CONFIG
    return CONFIG.scaled(n_layers=n_layers, qkv_bias=False, dtype="float32",
                         param_dtype="float32", remat=False)


def _random_norm_gains(params, key):
    """``init_params`` zeroes every rmsnorm gain (the ``(1+g)``
    convention); qwen scales by ``g`` itself, so draw the gains near 1 or
    every logit is 0."""
    import jax
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        1.0 + 0.1 * jax.random.normal(k, x.shape, x.dtype)
        if getattr(path[-1], "key", None) == "g" else x
        for (path, x), k in zip(leaves, keys)])


def phase_lm(cfg=None, batch: int = 2, prompt: int = 512, steps: int = 3,
             seed: int = 0) -> None:
    import jax
    from repro.models import transformer as T
    from repro.models.lazy_transformer import LazyTransformer

    cfg = cfg or lm_config()
    log(f"  lm: {cfg.name} d_model {cfg.d_model}, {cfg.n_heads} heads x "
        f"{cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; cut to "
        f"{cfg.n_layers} layers, qkv_bias off, float32")
    max_seq = -(-(prompt + steps) // 128) * 128    # lane-aligned cache
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (batch, prompt), dtype=np.int32)
    step_tokens = rng.integers(0, cfg.vocab_size, (steps, batch, 1),
                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        # reference first, kept on the host; its device parameters are
        # freed before the lazy model adopts its own copy
        params, _ = T.init_params(cfg, jax.random.PRNGKey(seed))
        params = _random_norm_gains(params, jax.random.PRNGKey(seed + 1))
        prefill = jax.jit(lambda p, t: T.serve_prefill(p, t, cfg, max_seq))
        decode = jax.jit(lambda p, c, t: T.serve_decode(p, c, t, cfg))
        logits, caches = prefill(params, tokens)
        refs = [np.asarray(logits)]
        for s in range(steps):
            logits, caches = decode(params, caches, step_tokens[s])
            refs.append(np.asarray(logits))
        host = jax.tree.map(np.asarray, params)
        del params, caches, logits
        lt = LazyTransformer(host, cfg)
        del host

        t0 = time.perf_counter()
        got = lt.prefill(tokens, max_seq)
        log(f"  lm prefill: {time.perf_counter() - t0!r} s (cold)")
        st = lowering_report(lt.rt, "lm prefill")
        claims = st["backend_blocks"]
        L = cfg.n_layers
        if claims.get("rmsnorm", 0) < 2 * L + 1 \
                or claims.get("flash_attention", 0) < 2 * L:
            raise SmokeFailure(f"lm: claimants took {claims}, want rmsnorm "
                               f">= {2 * L + 1}, flash_attention >= {2 * L}")
        check_close("lm prefill logits", got, refs[0], LM_RTOL)
        for s in range(steps):
            t0 = time.perf_counter()
            got = lt.decode(step_tokens[s])
            log(f"  lm decode {s}: {time.perf_counter() - t0!r} s (re-plans "
                "and compiles each token)")
            check_close(f"lm decode {s} logits", got, refs[s + 1], LM_RTOL)
        lowering_report(lt.rt, "lm total")


def phase_sharded(n_dev: int = 4, sizes=None) -> None:
    from benchmarks import comm_scaling
    from repro.core import dist
    from repro.core import lazy as bh
    from repro.core.dist import host_mesh
    from repro.core.lazy import fresh_runtime

    sizes = sizes or {"window_pipeline": dict(n=1 << 24),
                      "stencil": dict(n=4096)}
    for name, fn in comm_scaling.PROGRAMS.items():
        kw = dict(sizes[name], dtype="float32")
        with fresh_runtime(cost_model="comm", algorithm="greedy",
                           mesh=host_mesh(n_dev)) as rt:
            t0 = time.perf_counter()
            got = fn(bh, dist, n_dev, **kw)
            wall = time.perf_counter() - t0
            st = lowering_report(rt, f"{name} on {n_dev} chips")
        if st["shard_map_blocks"] <= 0:
            raise SmokeFailure(f"{name}: no block ran through shard_map")
        log(f"  {name}: collectives {st['collectives']}, interconnect "
            f"bytes {st['interconnect_bytes']!r}, {wall!r} s (cold)")
        with fresh_runtime(cost_model="comm", algorithm="greedy"):
            ref = fn(bh, dist, n_dev, **kw)
        check_close(f"{name} vs one device", got, ref, SHARDED_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and request data")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        import benchmarks.programs  # noqa: F401
        import repro.core.lazy  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repo's code is not next to this script: {e}",
              file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices are {dev.platform}); "
              "this smoke run does not fall back to the CPU", file=sys.stderr)
        return 1
    count = len(jax.devices())
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {count}",
              file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x {count}")

    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(4))]
    else:
        phases = [("programs", phase_programs),
                  ("server", lambda: phase_server(seed=args.seed)),
                  ("lm", lambda: phase_lm(seed=args.seed))]
    for name, run in phases:
        log(f"[{name}]")
        t0 = time.perf_counter()
        try:
            run()
        except SmokeFailure as e:
            print(f"chip_smoke: phase {name} failed: {e}", file=sys.stderr)
            return 1
        log(f"[{name}] ok in {time.perf_counter() - t0!r} s")

    cache = Path(jax.config.jax_compilation_cache_dir or "")
    n_cached = sum(1 for p in cache.rglob("*") if p.is_file()) \
        if cache.is_dir() else 0
    log(f"compile cache: {n_cached} entries in {cache}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
