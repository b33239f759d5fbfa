"""Canonical perf snapshot — one JSON artifact per commit (ISSUE 4), plus
the CI perf-regression gate (ISSUE 5), the cross-flush loop-fusion speedup
gate (ISSUE 6), the serving-runtime gate (ISSUE 8), the ILP
partition-quality gate (ISSUE 9) and the LM serving gate (ISSUE 10).

    PYTHONPATH=src python benchmarks/run_all.py --json BENCH_10.json [--quick]
    PYTHONPATH=src python benchmarks/run_all.py --json BENCH_10.json \\
        --compare BENCH_10.json --tolerance 0.25     # gate vs the baseline

The repo keeps ONE committed snapshot — the latest (``BENCH_<n>.json``
with the highest issue number); superseded snapshots are deleted when the
next one lands, and history lives in git + the per-commit CI artifacts
(DESIGN.md §20).

``--compare`` loads a baseline snapshot (BEFORE overwriting ``--json``) and
fails the run when any gated metric regresses past ``--tolerance``:

* partition-scaling graph+partition seconds per (family, size) row may not
  exceed ``base*(1+tol)`` plus a small absolute slack (CI timers are noisy
  on sub-100ms rows), with the baseline scaled to this machine's speed via
  the snapshots' ``machine_ref_s`` pure-Python reference measurement;
* aggregate kernel coverage may not drop below ``base*(1-tol)``;
* per-program comm-bytes savings (``1 - fused/unfused``) may not drop
  below ``base*(1-tol)`` minus a 2-point absolute slack;
* loop fusion: every iterative program must stay bit-identical to the
  per-flush path (no tolerance), at least ``LOOP_MIN_PROGRAMS`` programs
  must keep a flush-path speedup of ``LOOP_SPEEDUP_FLOOR*(1-tol)``, and no
  program's speedup may drop below ``base*(1-tol)``;
* observability: one disabled ``obs.trace.span()`` call may not exceed
  ``OBS_SPAN_NS_CEILING`` nanoseconds (absolute — a property of the
  disabled fast path, not of the workload or machine baseline);
* partition quality: the ILP backend may never report a calibrated plan
  cost above either greedy baseline (the anytime never-worse contract,
  absolute — model costs are deterministic), and at least
  ``ILP_MIN_IMPROVED`` paper programs must keep a strict improvement over
  the default (byte-model greedy) planner;
* serving: concurrent multi-tenant results must stay bit-identical to the
  serial batching-off server (absolute), the fresh-runtime warm start must
  hit the disk plan store at least once with zero corrupt/stale entries
  (absolute), p99 submit latency must stay under
  ``serving.TAIL_RATIO_CEILING`` x p50 (absolute), and QPS may not drop
  below the machine-normalized ``base*(1-tol)``;
* lm: lazy-runtime transformer logits must stay within ``LM_LOGIT_ERR``
  of the jitted direct model's at every prefill/decode step and the
  ``rowblock`` backend must claim >= 4 blocks a layer + 1 (absolute);
  lazy per-token decode latency may not exceed the machine-normalized
  ``base*(1+tol)`` plus ``LM_TIME_SLACK_MS``.

Aggregates the three benchmark families that gate this repo into a single
machine-readable snapshot, seeding the bench trajectory (CI runs this and
uploads the JSON as an artifact; compare artifacts across commits to see
the trend):

* ``partition_scaling`` — staged graph+partition seconds per tape family
  and size (ISSUE 1 metric);
* ``kernel_coverage``   — fused-vs-fallback Pallas coverage over the paper
  suite through the lowering-selection path (ISSUE 3 metric), plus the
  per-reason fallback breakdown;
* ``comm_scaling``      — fused vs unfused interconnect bytes over
  simulated host devices (ISSUE 2 metric), with the executor-swap
  bit-identity check;
* ``mixed_lowering``    — per-backend block counts of one representative
  ``backend='pallas'`` flush (ISSUE 4: the lower stage routing one flush
  across ≥ 2 backends);
* ``partition_quality`` — calibrated plan cost of the default greedy
  planner vs ``partition_backend="ilp"`` per paper program, with the
  solver's optimality gap and wall clock (ISSUE 9 metric);
* ``loop_fusion``       — iterative-suite per-iteration wall-clock,
  loop-fused vs per-flush, with the bitwise-identity check (ISSUE 6
  metric; see ``benchmarks.iterative`` for the two reported times);
* ``lm``                — transformer prefill wall + per-token decode
  latency, lazy runtime (``backend="lm"`` stack) vs the jitted direct
  model, with the logits' relative error and per-backend block counts
  (ISSUE 10 metric);
* ``obs``               — disabled-tracing span overhead (ns/call) and the
  span-count profile of one canonical traced flush (ISSUE 7 metric);
* ``serving``           — multi-tenant Server QPS + p50/p99 under mixed
  coalescable/distinct load, the micro-batched share, the bitwise check
  and the plan-store warm start (ISSUE 8 metric; see
  ``benchmarks.serving``).

Every section is a summary, not a sweep: the snapshot must stay cheap
enough to run on every CI push.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List

# runnable both as `python benchmarks/run_all.py` and `-m benchmarks.run_all`
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def snap_partition_scaling(sizes: List[int]) -> List[Dict]:
    from benchmarks.partition_scaling import TAPES, run_engine
    rows = []
    for family, make in TAPES.items():
        for n_ops in sizes:
            tape = make(n_ops)
            r = run_engine(tape, "staged")
            rows.append({"family": family, "n_ops": len(tape),
                         "t_graph_s": r["t_graph"],
                         "t_partition_s": r["t_partition"],
                         "cost": r["cost"], "n_blocks": r["n_blocks"]})
            print(f"partition_scaling/{family}/{len(tape)}ops: "
                  f"graph+partition {r['t']:.3f}s "
                  f"({r['n_blocks']} blocks)", flush=True)
    return rows


def snap_kernel_coverage() -> Dict:
    from benchmarks.roofline import kernel_coverage
    rows = kernel_coverage()
    blocks = sum(r["blocks"] for r in rows)
    pallas = sum(r["pallas"] for r in rows)
    reasons: Dict[str, int] = {}
    for r in rows:
        for k, v in r["reasons"].items():
            reasons[k] = reasons.get(k, 0) + v
    out = {"programs": len(rows), "work_blocks": blocks, "pallas": pallas,
           "coverage": pallas / max(1, blocks), "reasons": reasons,
           "per_program": rows}
    print(f"kernel_coverage: {pallas}/{blocks} blocks "
          f"({out['coverage']:.1%}) across {len(rows)} programs", flush=True)
    return out


def snap_comm_scaling(devices: List[int]) -> List[Dict]:
    from benchmarks.comm_scaling import _spawn
    rows: List[Dict] = []
    for n in devices:
        for r in _spawn(n):
            rows.append(r)
            bu, bf = r["bytes_singleton"], r["bytes_greedy"]
            sv = f"{(1 - bf / bu) * 100:.0f}%" if bu else "-"
            print(f"comm_scaling/{r['program']}/{n}dev: "
                  f"fused {bf:.0f}B vs unfused {bu:.0f}B ({sv} saved), "
                  f"identical={r['bit_identical']}", flush=True)
    return rows


def snap_mixed_lowering() -> Dict:
    """One flush, ≥ 2 backends: the lower stage routes a matmul to the XLA
    floor and the elementwise/reduction blocks to the Pallas codegen."""
    import numpy as np
    from repro.core import lazy as bh
    from repro.core.lazy import fresh_runtime
    with fresh_runtime(algorithm="greedy", backend="pallas") as rt:
        a = bh.asarray(np.arange(64.0).reshape(8, 8))
        b = bh.asarray(np.arange(64.0)[::-1].reshape(8, 8))
        mm = bh.matmul(a, b)
        x = bh.random((4096,))
        y = (bh.sin(x) * 0.5 + x * 0.25) * 2.0
        total = float((mm.sum() + y.sum()).numpy())
        st = rt.executor.stats
        out = {"result": total,
               "backend_blocks": dict(st["backend_blocks"]),
               "fallback_reasons": {k: dict(v) for k, v in
                                    st["backend_fallbacks"].items() if v}}
    print(f"mixed_lowering: backend_blocks={out['backend_blocks']}",
          flush=True)
    return out


def snap_obs() -> Dict:
    """Observability overhead + per-flush span profile (ISSUE 7 metric).

    ``span_ns_disabled`` measures one disabled ``obs.trace.span()`` call
    (the cost every instrumented stage pays when no tracer is installed);
    ``--compare`` gates it at ``OBS_SPAN_NS_CEILING`` absolutely — this is
    a per-call property of the fast path, not a workload measurement, so no
    baseline is needed.  ``span_counts`` records the event profile of one
    canonical traced flush (the chain program), pinning how chatty the
    instrumentation is per flush."""
    import numpy as np
    from repro.core import lazy as bh
    from repro.core.lazy import fresh_runtime
    from repro.core.obs import trace
    ns = trace.disabled_span_overhead_ns()
    tr = trace.Tracer()
    trace.enable(tr)
    try:
        with fresh_runtime(algorithm="greedy") as rt:
            x = bh.asarray(np.linspace(0.0, 1.0, 4096))
            y = (bh.sin(x) * 0.5 + x * 0.25) * 2.0
            float(y.sum().numpy())
    finally:
        trace.disable()
    out = {"span_ns_disabled": ns, "span_counts": tr.span_counts(),
           "n_events": len(tr.events)}
    print(f"obs: disabled span {ns:.0f}ns/call, "
          f"{out['n_events']} events for the canonical flush", flush=True)
    return out


def snap_serving(quick: bool) -> Dict:
    from benchmarks.serving import run_bench
    r = run_bench(tenants=2 if quick else 4,
                  requests=4 if quick else 8,
                  size=1024 if quick else 4096)
    print(f"serving: {r['tenants']} tenants, {r['qps']:.0f} QPS, "
          f"p50 {r['p50_ms']:.1f}ms p99 {r['p99_ms']:.1f}ms, "
          f"{r['batched_share']:.0%} batched, "
          f"warm hits {r['warm']['hits']}, "
          f"identical={r['bit_identical']}", flush=True)
    return r


def snap_partition_quality(quick: bool) -> Dict:
    """ISSUE 9 metric: calibrated cost of greedy vs ILP plans per paper
    benchmark program.

    Captures every structurally-distinct flush tape of each program, then
    prices three plans under the *calibrated* cost model (the measured
    objective; with no fit installed it degenerates to the analytic
    ``tpu`` pricing):

    * ``cost_greedy_default``  — the production default planner (greedy
      under the sparse ``bohrium`` byte model), its plan re-priced under
      the calibrated model.  Zero-byte-saving merges are invisible to the
      byte model, so this plan pays dispatch overhead the calibrated
      objective sees;
    * ``cost_greedy``          — greedy solving the calibrated objective
      directly (the ILP warm start);
    * ``cost_ilp``             — ``partition_backend="ilp"`` with a per-tape
      wall-clock budget, plus the solver's reported optimality gap.

    The ``--compare`` gate asserts ilp never exceeds either greedy cost
    (the anytime contract) and that at least ``ILP_MIN_IMPROVED`` programs
    keep a strict improvement over the default planner."""
    from benchmarks.programs import BENCHMARKS
    from repro.core import partition
    from repro.core.cache import tape_signature
    from repro.core.cost import make_cost_model
    from repro.core.lazy import fresh_runtime

    cal = make_cost_model("calibrated")
    budget = 0.25 if quick else 1.0
    rows: List[Dict] = []
    for name, fn in BENCHMARKS.items():
        tapes: List[List] = []
        seen: set = set()
        with fresh_runtime(algorithm="greedy", cost_model="bohrium",
                           loop_fusion=False) as rt:
            orig = rt.scheduler.plan

            def plan(tape, *a, _orig=orig, seen=seen, tapes=tapes, **kw):
                sig = tape_signature(tape, "greedy", "calibrated")
                if sig not in seen:
                    seen.add(sig)
                    tapes.append(list(tape))
                return _orig(tape, *a, **kw)

            rt.scheduler.plan = plan
            fn()
        c_def = c_greedy = c_ilp = wall = max_gap = 0.0
        statuses: Dict[str, int] = {}
        for tape in tapes:
            r_def = partition(tape, algorithm="greedy", cost_model="bohrium")
            c_def += cal.partition_cost(list(r_def.state.blocks.values()))
            c_greedy += partition(tape, algorithm="greedy",
                                  cost_model="calibrated").cost
            r_ilp = partition(tape, cost_model="calibrated",
                              partition_backend="ilp", time_budget_s=budget)
            c_ilp += r_ilp.cost
            wall += r_ilp.stats["ilp_wall_s"]
            max_gap = max(max_gap, r_ilp.stats["ilp_gap"])
            s = r_ilp.stats["ilp_status"]
            statuses[s] = statuses.get(s, 0) + 1
        imp = (1.0 - c_ilp / c_def) if c_def else 0.0
        rows.append({"program": name, "tapes": len(tapes),
                     "cost_greedy_default": c_def,
                     "cost_greedy": c_greedy,
                     "cost_ilp": c_ilp,
                     "improvement": imp,
                     "max_gap": max_gap,
                     "solver_wall_s": wall,
                     "statuses": statuses})
        print(f"partition_quality/{name}: greedy(default) {c_def:.3e} "
              f"-> ilp {c_ilp:.3e} ({imp:+.1%}), max gap {max_gap:.2f}, "
              f"solver {wall:.2f}s {statuses}", flush=True)
    improved = sum(1 for r in rows
                   if r["cost_ilp"] < r["cost_greedy_default"] * (1 - 1e-9))
    return {"time_budget_s": budget, "improved_programs": improved,
            "rows": rows}


def snap_lm(quick: bool) -> Dict:
    """ISSUE 10 metric: LM serving through the lazy runtime vs the jitted
    direct model — per-token decode latency and prefill wall, with the
    logits' relative error and the per-backend block counts.

    The latency ratio is *diagnostic* (the lazy path pays tracing +
    planning per step and runs its row-replay kernels in Pallas interpret
    mode on CPU); what the ``--compare`` gate holds absolute is
    correctness: logits within ``LM_LOGIT_ERR`` of the direct model's at
    every step, and the ``rowblock`` backend taking the rmsnorm and
    softmax blocks.  Lazy decode latency is additionally gated against
    the machine-normalized baseline."""
    import jax
    import numpy as np

    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.models.lazy_transformer import LazyTransformer

    cfg = ModelConfig(name="bench_lm", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                      vocab_size=256, dtype="float32",
                      param_dtype="float32", norm_plus_one=True,
                      tie_embeddings=False)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(7))
    rng = np.random.default_rng(7)
    b, s, max_seq = 2, 16, 48
    steps = 4 if quick else 12
    tokens = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
    step_toks = [rng.integers(0, cfg.vocab_size, (b, 1)).astype(np.int32)
                 for _ in range(steps)]

    # -- direct jitted serving (the reference: timing AND logits) --------
    with jax.default_matmul_precision("highest"):
        prefill = jax.jit(lambda p, t: T.serve_prefill(p, t, cfg, max_seq))
        decode = jax.jit(lambda p, c, t: T.serve_decode(p, c, t, cfg))
        ref_logits, caches0 = prefill(params, tokens)           # compile
        jax.block_until_ready(ref_logits)
        t0 = time.perf_counter()
        ref_logits, caches0 = prefill(params, tokens)
        jax.block_until_ready(ref_logits)
        prefill_ms_direct = (time.perf_counter() - t0) * 1e3
        jax.block_until_ready(decode(params, caches0, step_toks[0]))
        ref_steps, t_direct, caches = [], [], caches0
        for tok in step_toks:
            t0 = time.perf_counter()
            lg, caches = decode(params, caches, tok)
            jax.block_until_ready(lg)
            t_direct.append((time.perf_counter() - t0) * 1e3)
            ref_steps.append(np.asarray(lg))

    def rel(got, want) -> float:
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    # -- lazy runtime: one flushed tape per prefill/decode step ----------
    lt = LazyTransformer(params, cfg)
    lt.prefill(tokens, max_seq)                 # warm merge/executable caches
    t0 = time.perf_counter()
    got_logits = lt.prefill(tokens, max_seq)
    prefill_ms_lazy = (time.perf_counter() - t0) * 1e3
    logit_err = rel(got_logits, ref_logits)
    t_lazy = []
    for i, tok in enumerate(step_toks):
        t0 = time.perf_counter()
        lg = lt.decode(tok)
        t_lazy.append((time.perf_counter() - t0) * 1e3)
        logit_err = max(logit_err, rel(lg, ref_steps[i]))
    claims = dict(lt.rt.executor.stats["backend_blocks"])

    def med(xs: List[float]) -> float:
        return float(sorted(xs)[len(xs) // 2])

    out = {"config": {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
                      "n_heads": cfg.n_heads, "d_ff": cfg.d_ff,
                      "batch": b, "prompt": s, "max_seq": max_seq},
           "steps": steps, "logit_err": logit_err,
           "prefill_ms_direct": prefill_ms_direct,
           "prefill_ms_lazy": prefill_ms_lazy,
           "decode_ms_direct": med(t_direct),
           "decode_ms_lazy": med(t_lazy),
           "backend_blocks": claims}
    print(f"lm: prefill {prefill_ms_lazy:.1f}ms lazy vs "
          f"{prefill_ms_direct:.1f}ms direct; decode "
          f"{out['decode_ms_lazy']:.1f}ms vs "
          f"{out['decode_ms_direct']:.1f}ms/token; "
          f"rowblock={claims.get('rowblock', 0)}, "
          f"logit_err={logit_err!r}", flush=True)
    return out


def snap_loop_fusion(quick: bool) -> List[Dict]:
    from benchmarks.iterative import run_suite
    rows = run_suite(quick=quick)
    for r in rows:
        print(f"loop_fusion/{r['program']}: "
              f"flush {r['flush_ms_per_iter_flush']:.3f}"
              f"->{r['flush_ms_per_iter_loop']:.3f}ms/it "
              f"({r['speedup_flush']:.1f}x, wall {r['speedup_wall']:.1f}x), "
              f"identical={r['bit_identical']}", flush=True)
    return rows


def _savings(row: Dict) -> float:
    bu, bf = row.get("bytes_singleton", 0.0), row.get("bytes_greedy", 0.0)
    return (1.0 - bf / bu) if bu else 0.0


# absolute slacks under the relative tolerance: CI wall-clock noise can be
# tens of milliseconds on rows that only take tens of milliseconds, and
# comm savings are quantized by collective counts on tiny meshes.
TIME_SLACK_S = 0.1
SAVINGS_SLACK = 0.02

# ISSUE 6 acceptance floor: >= LOOP_MIN_PROGRAMS iterative programs must
# hold a >= LOOP_SPEEDUP_FLOOR flush-path speedup (the gate applies the
# run's relative tolerance to the floor, CI machines being noisy).
LOOP_SPEEDUP_FLOOR = 5.0
LOOP_MIN_PROGRAMS = 3

# ISSUE 9 acceptance floor: the ILP backend must keep a strict calibrated-
# cost improvement over the default planner on at least this many paper
# programs.  Absolute (no baseline, no tolerance): plan costs are priced by
# a deterministic model, not measured wall clock, so they are machine-
# independent — and the never-worse contract is exact by construction.
ILP_MIN_IMPROVED = 3

# ISSUE 7 acceptance ceiling: one disabled obs.trace.span() call must stay
# under this many nanoseconds.  Absolute (no baseline, no tolerance): the
# disabled fast path is one global load + `is None` test by construction,
# and CI machines comfortably do that in tens of ns.
OBS_SPAN_NS_CEILING = 100.0

# ISSUE 10: absolute slack under the lazy-decode latency gate — per-token
# times are ~100ms of tracing + planning Python, and CI scheduler jitter
# alone can add a large fraction of that.
LM_TIME_SLACK_MS = 100.0
# largest max|lazy - direct| / max|direct| of the lm snapshot's
# logits, the benchmark's own limit: the two paths sum the same float32
# products in different orders (a few 1e-7), bfloat16 products move them
# by 1e-5 or more.
LM_LOGIT_ERR = 3e-6


def machine_ref_s() -> float:
    """Seconds for a fixed pure-Python dict/set workload (~0.1s here).

    Stored in every snapshot; the time gate scales the baseline's
    partition times by ``snap_ref / base_ref`` so a baseline captured on
    one machine gates runs on another (CI runners are routinely 2x slower
    than an authoring workstation — without normalization every absolute
    wall-clock comparison across machines is a false alarm).  Pure Python
    on purpose: graph build + partition time is dict/set bound, not BLAS
    bound.  Minimum of three runs de-noises scheduler jitter."""
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        d: Dict[int, int] = {}
        acc = 0
        for i in range(400_000):
            d[i] = i
            if i % 3 == 0:
                acc += d.pop(i - 1, 0)
            if i % 7 == 0:
                acc ^= hash((i, acc))
        best = min(best, time.perf_counter() - t0)
    return best


def compare_snapshots(snap: Dict, base: Dict, tolerance: float) -> List[str]:
    """Return a list of human-readable regressions of ``snap`` vs ``base``
    (empty = gate passes).  Gated metrics: partition-scaling time, kernel
    coverage, comm-bytes savings — the three headline numbers of PRs 1-3."""
    fails: List[str] = []
    # machine normalization: scale the baseline's times to this machine's
    # speed when both snapshots carry the reference measurement
    ratio = 1.0
    if snap.get("machine_ref_s") and base.get("machine_ref_s"):
        ratio = snap["machine_ref_s"] / base["machine_ref_s"]
    base_rows = {(r["family"], r["n_ops"]): r
                 for r in base.get("partition_scaling", [])}
    for r in snap.get("partition_scaling", []):
        b = base_rows.get((r["family"], r["n_ops"]))
        if b is None:
            continue
        t_new = r["t_graph_s"] + r["t_partition_s"]
        t_old = (b["t_graph_s"] + b["t_partition_s"]) * ratio
        limit = t_old * (1.0 + tolerance) + TIME_SLACK_S
        if t_new > limit:
            fails.append(
                f"partition_scaling/{r['family']}/{r['n_ops']}ops: "
                f"{t_new:.3f}s > {limit:.3f}s (base {t_old:.3f}s)")
    cov_new = snap.get("kernel_coverage", {}).get("coverage")
    cov_old = base.get("kernel_coverage", {}).get("coverage")
    if cov_new is not None and cov_old is not None:
        floor = cov_old * (1.0 - tolerance)
        if cov_new < floor:
            fails.append(f"kernel_coverage: {cov_new:.1%} < {floor:.1%} "
                         f"(base {cov_old:.1%})")
    base_comm = {(r["program"], r.get("devices")): r
                 for r in base.get("comm_scaling", [])}
    for r in snap.get("comm_scaling", []):
        # correctness first: depends only on the fresh snapshot, so it must
        # fire even for rows the committed baseline has never seen
        if not r.get("bit_identical", True):
            fails.append(f"comm_scaling/{r['program']}/{r.get('devices')}dev: "
                         "dist result not bit-identical")
        b = base_comm.get((r["program"], r.get("devices")))
        if b is None or not b.get("bytes_singleton"):
            continue
        floor = _savings(b) * (1.0 - tolerance) - SAVINGS_SLACK
        if _savings(r) < floor:
            fails.append(
                f"comm_scaling/{r['program']}/{r.get('devices')}dev: savings "
                f"{_savings(r):.1%} < {floor:.1%} (base {_savings(b):.1%})")
    # loop fusion: correctness is absolute, the speedup floor and the
    # per-program regression check take the relative tolerance
    loop_rows = snap.get("loop_fusion", [])
    base_loop = {r["program"]: r for r in base.get("loop_fusion", [])}
    fast = 0
    floor = LOOP_SPEEDUP_FLOOR * (1.0 - tolerance)
    for r in loop_rows:
        if not r.get("bit_identical", True):
            fails.append(f"loop_fusion/{r['program']}: loop-fused result "
                         "not bit-identical to per-flush")
        sp = r.get("speedup_flush", 0.0)
        if sp >= floor:
            fast += 1
        b = base_loop.get(r["program"])
        if b is not None:
            b_floor = b.get("speedup_flush", 0.0) * (1.0 - tolerance)
            if sp < b_floor:
                fails.append(
                    f"loop_fusion/{r['program']}: flush speedup {sp:.1f}x "
                    f"< {b_floor:.1f}x (base {b['speedup_flush']:.1f}x)")
    if loop_rows and fast < LOOP_MIN_PROGRAMS:
        fails.append(
            f"loop_fusion: only {fast}/{len(loop_rows)} programs reach a "
            f"{floor:.1f}x flush-path speedup "
            f"(need {LOOP_MIN_PROGRAMS} at {LOOP_SPEEDUP_FLOOR:.0f}x"
            f"*(1-tol))")
    # partition quality (ISSUE 9): deterministic model costs, gated
    # absolutely on the fresh snapshot — ilp may never exceed either greedy
    # baseline, and the strict-improvement floor must hold
    pq = snap.get("partition_quality", {})
    for r in pq.get("rows", []):
        if r["cost_ilp"] > r["cost_greedy"] * (1 + 1e-9):
            fails.append(
                f"partition_quality/{r['program']}: ilp cost "
                f"{r['cost_ilp']:.3e} > greedy(calibrated) "
                f"{r['cost_greedy']:.3e} — anytime contract broken")
        if r["cost_ilp"] > r["cost_greedy_default"] * (1 + 1e-9):
            fails.append(
                f"partition_quality/{r['program']}: ilp cost "
                f"{r['cost_ilp']:.3e} > greedy(default) "
                f"{r['cost_greedy_default']:.3e}")
    if pq and pq.get("improved_programs", 0) < ILP_MIN_IMPROVED:
        fails.append(
            f"partition_quality: ilp strictly improves only "
            f"{pq.get('improved_programs', 0)} programs "
            f"(need {ILP_MIN_IMPROVED})")
    # observability: the disabled-tracing span cost is gated absolutely —
    # it depends only on the fresh snapshot (see OBS_SPAN_NS_CEILING)
    span_ns = snap.get("obs", {}).get("span_ns_disabled")
    if span_ns is not None and span_ns > OBS_SPAN_NS_CEILING:
        fails.append(f"obs: disabled span() costs {span_ns:.0f}ns/call > "
                     f"{OBS_SPAN_NS_CEILING:.0f}ns ceiling")
    # lm: the logits' error and the rowblock claims are
    # absolute; lazy decode latency takes the machine-normalized tolerance
    lm = snap.get("lm", {})
    if lm:
        if lm.get("logit_err", 0.0) > LM_LOGIT_ERR:
            fails.append(f"lm: lazy transformer logits {lm['logit_err']!r} "
                         f"off the jitted direct model's (> {LM_LOGIT_ERR})")
        bb = lm.get("backend_blocks", {})
        want = 4 * lm.get("config", {}).get("n_layers", 0) + 1
        if bb.get("rowblock", 0) < want:
            fails.append(f"lm: rowblock claimed fewer than {want} blocks "
                         f"(backend_blocks={bb})")
        b_lm = base.get("lm", {})
        if b_lm.get("decode_ms_lazy") and lm.get("decode_ms_lazy") is not None:
            limit = b_lm["decode_ms_lazy"] * ratio * (1.0 + tolerance) \
                + LM_TIME_SLACK_MS
            if lm["decode_ms_lazy"] > limit:
                fails.append(
                    f"lm: lazy decode {lm['decode_ms_lazy']:.1f}ms/token > "
                    f"{limit:.1f}ms (base {b_lm['decode_ms_lazy']:.1f}ms, "
                    f"machine ratio {ratio:.2f})")
    # serving (ISSUE 8): correctness, warm start and the tail ratio are
    # absolute; QPS takes the machine-normalized relative tolerance
    srv = snap.get("serving", {})
    if srv:
        from benchmarks.serving import TAIL_RATIO_CEILING
        if not srv.get("bit_identical", True):
            fails.append("serving: concurrent results not bit-identical "
                         "to the serial batching-off server")
        warm = srv.get("warm", {})
        if warm.get("hits", 1) < 1:
            fails.append("serving: fresh-runtime warm start never hit "
                         "the disk plan store")
        if warm.get("corrupt", 0) or warm.get("stale", 0):
            fails.append(
                f"serving: warm start flagged store entries "
                f"(corrupt={warm.get('corrupt')}, stale={warm.get('stale')})")
        tail = srv.get("p99_ms", 0.0) / max(srv.get("p50_ms", 1e-9), 1e-9)
        if tail > TAIL_RATIO_CEILING:
            fails.append(f"serving: p99/p50 = {tail:.0f}x > "
                         f"{TAIL_RATIO_CEILING:.0f}x ceiling")
        b_srv = base.get("serving", {})
        if b_srv.get("qps") and srv.get("qps") is not None:
            qps_floor = b_srv["qps"] / ratio * (1.0 - tolerance)
            if srv["qps"] < qps_floor:
                fails.append(
                    f"serving: {srv['qps']:.0f} QPS < {qps_floor:.0f} "
                    f"(base {b_srv['qps']:.0f}, machine ratio {ratio:.2f})")
    return fails


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default="BENCH_10.json",
                    help="output path for the snapshot JSON")
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes / fewer device counts")
    ap.add_argument("--compare", default=None, metavar="BASELINE",
                    help="baseline snapshot JSON; fail on regressions "
                         "past --tolerance (loaded before --json is "
                         "overwritten, so both may name the same file)")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="relative regression tolerance for --compare")
    args = ap.parse_args()

    base = None
    if args.compare is not None:
        with open(args.compare) as f:
            base = json.load(f)

    t0 = time.time()
    sizes = [250, 1000] if not args.quick else [250]
    devices = [1, 8] if not args.quick else [2]
    snap = {
        "schema": "bench_snapshot_v1",
        "argv": sys.argv[1:],
        "unix_time": t0,
        "machine_ref_s": machine_ref_s(),
        "partition_scaling": snap_partition_scaling(sizes),
        "kernel_coverage": snap_kernel_coverage(),
        "comm_scaling": snap_comm_scaling(devices),
        "mixed_lowering": snap_mixed_lowering(),
        "partition_quality": snap_partition_quality(args.quick),
        "loop_fusion": snap_loop_fusion(args.quick),
        "lm": snap_lm(args.quick),
        "obs": snap_obs(),
        "serving": snap_serving(args.quick),
    }
    snap["wall_s"] = time.time() - t0
    with open(args.json, "w") as f:
        json.dump(snap, f, indent=1)
        f.write("\n")
    print(f"\nsnapshot -> {args.json} ({snap['wall_s']:.0f}s)", flush=True)

    if base is not None:
        fails = compare_snapshots(snap, base, args.tolerance)
        if fails:
            print(f"\nPERF REGRESSION vs {args.compare} "
                  f"(tolerance {args.tolerance:.0%}):", file=sys.stderr)
            for f_ in fails:
                print(f"  - {f_}", file=sys.stderr)
            raise SystemExit(1)
        print(f"perf gate: no regressions vs {args.compare} "
              f"(tolerance {args.tolerance:.0%})", flush=True)


if __name__ == "__main__":
    main()
