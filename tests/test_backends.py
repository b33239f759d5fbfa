"""Lowering-backend layer (ISSUE 4, DESIGN.md §14): registry, per-block
cost-priced selection, mixed-backend flushes, merge-cached decisions,
per-flush stats, and the bounded-history / LRU satellites."""

import jax
import numpy as np
import pytest

from repro.core import lazy as bh
from repro.core.backends import (BackendBuildError, LoweringBackend,
                                 LoweringContext, available_backends,
                                 default_stack, get_backend,
                                 register_backend, select_lowering,
                                 unregister_backend)
from repro.core.cache import MergeCache
from repro.core.algorithms import partition
from repro.core.dist import host_mesh
from repro.core.executor import BlockExecutor, make_block_fn
from repro.core.ir import Op
from repro.core.lazy import fresh_runtime
from repro.core.scheduler import Scheduler, plan_blocks

N_DEV = len(jax.devices())


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _simple_tape():
    """A recorded two-op elementwise tape ending in SYNC."""
    with fresh_runtime() as rt:
        x = bh.asarray(np.arange(8.0))
        y = x * 2.0 + 1.0
        rt.record(Op("sync", None, sync_bases=frozenset({y.view.base})))
        tape = list(rt.tape)
        rt.tape.clear()
        y._alive = False
    return tape


def _plans(tape):
    res = partition(tape, algorithm="greedy", cost_model="bohrium")
    return plan_blocks(tape, res.op_blocks())


class _CountingBackend(LoweringBackend):
    """Claims everything, lowers via make_block_fn, reports a fixed
    dispatch count — a registry/selection probe."""

    donates = True

    def __init__(self, name, n_dispatches=1):
        self.name = name
        self.n_dispatches = n_dispatches
        self.built = 0

    def claims(self, ops, plan, ctx):
        return None

    def dispatches(self, ops, plan, ctx):
        return self.n_dispatches

    def build(self, ops, plan, ctx):
        self.built += 1
        fn, ins, outs = make_block_fn(ops, seed=ctx.seed)
        return fn


def _mixed_program():
    """One flush whose blocks need different backends: a matmul (opaque ->
    xla), a reversed view (irregular_view -> xla) and a fusible
    elementwise chain (pallas)."""
    a = bh.asarray(np.arange(64.0).reshape(8, 8))
    b = bh.asarray(np.arange(64.0)[::-1].reshape(8, 8))
    mm = bh.matmul(a, b)
    x = bh.asarray(np.arange(256.0))
    y = bh.sqrt(x) * 0.5 + x * 0.25
    r = x[::-1] * 2.0
    bh.sync(mm, y, r)                    # ONE flush plans+runs all blocks
    return np.asarray(mm.numpy()), np.asarray(y.numpy()), np.asarray(r.numpy())


# ---------------------------------------------------------------------------
# registry + policy resolution
# ---------------------------------------------------------------------------

def test_builtin_backends_registered():
    assert {"xla", "pallas", "shard_map"} <= set(available_backends())
    assert get_backend("xla").name == "xla"
    with pytest.raises(ValueError):
        get_backend("no_such_backend")


def test_default_stack_resolution():
    assert default_stack("xla") == ("xla",)
    assert default_stack("pallas") == ("pallas", "xla")
    assert default_stack(("a", "b")) == ("a", "b")
    mesh = object()          # any non-None sentinel
    assert default_stack("xla", mesh=mesh) == ("shard_map", "xla")
    assert default_stack("pallas", mesh=mesh) == ("shard_map", "pallas", "xla")


def test_register_backend_rejects_duplicates():
    be = _CountingBackend("dup_probe")
    register_backend(be)
    try:
        with pytest.raises(ValueError):
            register_backend(_CountingBackend("dup_probe"))
        register_backend(_CountingBackend("dup_probe"), replace=True)
    finally:
        unregister_backend("dup_probe")


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------

def test_selection_prefers_cheaper_dispatch_count():
    tape = _simple_tape()
    plan = next(p for p in _plans(tape) if p.has_work)
    ops = [tape[i] for i in plan.op_indices]
    ctx = LoweringContext()
    a, b = _CountingBackend("price_a", 3), _CountingBackend("price_b", 1)
    register_backend(a)
    register_backend(b)
    try:
        # cheaper dispatch count wins over preference order ...
        d = select_lowering(ops, plan, ("price_a", "price_b"), ctx)
        assert d.backend == "price_b"
        assert d.reason_for("price_a") is None      # it claimed, just lost
        # ... and preference order breaks ties
        b.n_dispatches = 3
        d = select_lowering(ops, plan, ("price_a", "price_b"), ctx)
        assert d.backend == "price_a"
    finally:
        unregister_backend("price_a")
        unregister_backend("price_b")


def test_selection_records_declined_reasons():
    tape = _simple_tape()
    plan = next(p for p in _plans(tape) if p.has_work)
    ops = [tape[i] for i in plan.op_indices]
    ctx = LoweringContext()
    # shard_map declines (no mesh), pallas claims the elementwise chain
    d = select_lowering(ops, plan, ("shard_map", "pallas", "xla"), ctx)
    assert d.backend == "pallas"
    assert d.reason_for("shard_map") == "no_mesh"


def test_custom_backend_end_to_end():
    be = _CountingBackend("echo")
    register_backend(be)
    try:
        with fresh_runtime(algorithm="greedy", backend=("echo",)) as rt:
            x = bh.asarray(np.arange(32.0))
            got = (x * 3.0 + 1.0).numpy()
            st = rt.executor.stats
        np.testing.assert_array_equal(got, np.arange(32.0) * 3.0 + 1.0)
        assert st["backend_blocks"]["echo"] >= 1
        assert be.built >= 1
    finally:
        unregister_backend("echo")


# ---------------------------------------------------------------------------
# mixed-backend flushes (tentpole acceptance)
# ---------------------------------------------------------------------------

def test_single_flush_mixes_pallas_and_xla_and_is_bitwise_identical():
    """One flush runs blocks on >= 2 backends (per-backend stats), and the
    mixed pallas/xla schedule is bitwise-identical to a pure-XLA run."""
    results, deltas = {}, {}
    for backend in ("xla", "pallas"):
        with fresh_runtime(algorithm="greedy", backend=backend) as rt:
            results[backend] = _mixed_program()
            deltas[backend] = rt.history[0]["exec"]
    for got, want in zip(results["pallas"], results["xla"]):
        np.testing.assert_array_equal(got, want)
    bb = deltas["pallas"]["backend_blocks"]
    assert bb["pallas"] >= 1 and bb["xla"] >= 1, bb    # mixed in ONE flush
    assert deltas["xla"]["backend_blocks"]["xla"] == \
        sum(deltas["xla"]["backend_blocks"].values())


@pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device host mesh")
def test_single_flush_mixes_shard_map_and_xla():
    from repro.core import dist
    with fresh_runtime(cost_model="comm", mesh=host_mesh()) as rt:
        x = bh.asarray(np.arange(32.0 * N_DEV))
        dist.shard(x, n=N_DEV)
        y = x * 2.0 + 1.0                 # sharded elementwise: shard_map
        s = (x * x).sum()                 # reduction: declined -> xla
        bh.sync(y, s)
        delta = rt.history[0]["exec"]
        got_y, got_s = np.asarray(y.numpy()), float(s.numpy())
    base = np.arange(32.0 * N_DEV)
    np.testing.assert_array_equal(got_y, base * 2.0 + 1.0)
    assert got_s == float((base * base).sum())
    bb = delta["backend_blocks"]
    assert bb["shard_map"] >= 1 and bb["xla"] >= 1, bb


# ---------------------------------------------------------------------------
# LM kernel claimants (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

def _rmsnorm_scale_tape():
    """A recorded block both the ``rmsnorm`` claimant and generic Pallas
    can express: the div→add(eps)→rsqrt→mul scale chain on a 2-D domain."""
    with fresh_runtime() as rt:
        x = bh.asarray(np.arange(64.0).reshape(8, 8) + 1.0)
        y = x * bh.rsqrt(x / 8.0 + 1e-6)
        rt.record(Op("sync", None, sync_bases=frozenset({y.view.base})))
        tape = list(rt.tape)
        rt.tape.clear()
        y._alive = False
    return tape


def test_lm_stack_resolution():
    assert default_stack("lm") == ("flash_attention", "rmsnorm",
                                   "mamba_scan", "pallas", "xla")
    assert {"flash_attention", "rmsnorm", "mamba_scan"} \
        <= set(available_backends())


def test_claimant_and_pallas_tie_broken_by_stack_order():
    """A block claimed by BOTH a hand-written kernel claimant and generic
    Pallas prices identically (one dispatch each); preference order is the
    deterministic tie-break — flipping the stack flips the winner."""
    tape = _rmsnorm_scale_tape()
    plan = next(p for p in _plans(tape) if p.has_work)
    ops = [tape[i] for i in plan.op_indices]
    ctx = LoweringContext()
    d = select_lowering(ops, plan, ("rmsnorm", "pallas", "xla"), ctx)
    assert d.backend == "rmsnorm"
    assert d.reason_for("pallas") is None       # pallas claimed, just lost
    d = select_lowering(ops, plan, ("pallas", "rmsnorm", "xla"), ctx)
    assert d.backend == "pallas"
    assert d.reason_for("rmsnorm") is None
    # non-matching claimants decline with their matcher slug
    d = select_lowering(ops, plan,
                        ("flash_attention", "mamba_scan", "xla"), ctx)
    assert d.backend == "xla"
    assert d.reason_for("flash_attention") == "no_softmax"
    assert d.reason_for("mamba_scan") == "no_scan"


def test_claimant_builder_failure_raises_and_names_backend():
    """A backend whose build() raises fails the flush with a
    BackendBuildError naming it: the block never silently runs on XLA."""

    class _BoomBackend(_CountingBackend):
        def build(self, ops, plan, ctx):
            raise RuntimeError("builder exploded")

    register_backend(_BoomBackend("boom"))
    try:
        with fresh_runtime(algorithm="greedy", backend=("boom",)) as rt:
            x = bh.asarray(np.arange(32.0))
            with pytest.raises(BackendBuildError, match="'boom'") as exc:
                (x * 3.0 + 1.0).numpy()
            st = rt.executor.stats
        assert exc.value.backend == "boom"
        assert "builder exploded" in str(exc.value.__cause__)
        assert st["backend_blocks"].get("xla", 0) == 0
        assert "error" not in st["backend_fallbacks"].get("boom", {})
    finally:
        unregister_backend("boom")


# ---------------------------------------------------------------------------
# scheduler lower stage + merge-cached decisions
# ---------------------------------------------------------------------------

def test_plan_annotates_lowering_decisions():
    tape = _simple_tape()
    policy = BlockExecutor(backend="pallas").lowering_policy()
    sch = Scheduler().plan(tape, lowering=policy)
    assert "t_lower_s" in sch.stats
    for p in sch.blocks:
        if p.has_work:
            assert p.lowering is not None
            assert p.lowering.backend in policy.backends
        else:
            assert p.lowering is None


def test_merge_cache_replays_lowering_decisions(monkeypatch):
    import repro.core.scheduler as sched_mod
    tape = _simple_tape()
    policy = BlockExecutor(backend="pallas").lowering_policy()
    calls = []
    real = sched_mod.select_lowering
    monkeypatch.setattr(sched_mod, "select_lowering",
                        lambda *a, **k: (calls.append(1) or real(*a, **k)))
    s = Scheduler()
    first = s.plan(tape, lowering=policy)
    n_probe = len(calls)
    assert n_probe >= 1
    second = s.plan(tape, lowering=policy)          # merge-cache hit
    assert second.result is None
    assert len(calls) == n_probe                    # no backend re-probing
    assert [p.lowering for p in second.blocks] == \
        [p.lowering for p in first.blocks]


def test_merge_cache_keys_on_backend_stack():
    tape = _simple_tape()
    s = Scheduler()
    s.plan(tape, lowering=BlockExecutor(backend="pallas").lowering_policy())
    s.plan(tape, lowering=BlockExecutor(backend="xla").lowering_policy())
    assert s.cache.misses == 2 and s.cache.hits == 0
    s.plan(tape, lowering=BlockExecutor(backend="xla").lowering_policy())
    assert s.cache.hits == 1


# ---------------------------------------------------------------------------
# MergeCache LRU (satellite)
# ---------------------------------------------------------------------------

def test_merge_cache_lru_eviction():
    c = MergeCache(capacity=2)
    c.put(("k1",), "v1")
    c.put(("k2",), "v2")
    assert c.get(("k1",)) == "v1"       # touch: k2 is now least-recent
    c.put(("k3",), "v3")                # evicts k2
    assert c.evictions == 1
    assert ("k2",) not in c and ("k1",) in c and ("k3",) in c
    assert c.get(("k2",)) is None
    assert len(c) == 2


def test_merge_cache_put_existing_key_refreshes():
    c = MergeCache(capacity=2)
    c.put(("k1",), "v1")
    c.put(("k2",), "v2")
    c.put(("k1",), "v1b")               # refresh, not insert: no eviction
    assert c.evictions == 0 and len(c) == 2
    assert c.get(("k1",)) == "v1b"


# ---------------------------------------------------------------------------
# per-flush stats + bounded history (satellites)
# ---------------------------------------------------------------------------

def test_history_records_per_flush_deltas_not_totals():
    with fresh_runtime(algorithm="greedy") as rt:
        keep = []
        for _ in range(3):
            x = bh.asarray(np.arange(16.0))
            y = x * 2.0
            y.numpy()
            keep.append(y)
        per_flush = [h["exec"]["blocks_run"] for h in rt.history]
        assert all(n >= 0 for n in per_flush)
        assert sum(per_flush) == rt.executor.stats["blocks_run"]
        # each entry is a delta: no entry carries the running total
        assert per_flush[-1] < rt.executor.stats["blocks_run"]
        bb = [h["exec"]["backend_blocks"] for h in rt.history]
        assert sum(d.get("xla", 0) for d in bb) == \
            rt.executor.stats["backend_blocks"]["xla"]


def test_reset_stats_zeroes_counters_but_keeps_executables():
    with fresh_runtime(algorithm="greedy") as rt:
        x = bh.asarray(np.arange(16.0))
        (x * 2.0).numpy()
        assert rt.executor.stats["blocks_run"] >= 1
        n_exec = len(rt.executor._cache)
        rt.executor.reset_stats()
        st = rt.executor.stats
        assert st["blocks_run"] == 0
        assert all(v == 0 for v in st["backend_blocks"].values())
        assert len(rt.executor._cache) == n_exec     # compiled fns kept
        (x * 3.0).numpy()                            # still dispatches
        assert rt.executor.stats["blocks_run"] >= 1


def test_history_is_bounded():
    with fresh_runtime(history_limit=3) as rt:
        keep = []
        for i in range(6):
            x = bh.asarray(np.arange(4.0))
            y = x + float(i)
            y.numpy()
            keep.append(y)
        assert rt.flushes >= 6
        assert len(rt.history) == 3
        assert rt.history.maxlen == 3


# ---------------------------------------------------------------------------
# dist facade
# ---------------------------------------------------------------------------

def test_dist_executor_is_a_facade_over_shard_map_backend():
    from repro.core.dist import DistBlockExecutor
    ex = DistBlockExecutor(mesh=host_mesh())
    assert isinstance(ex, BlockExecutor)
    assert ex.backends[0] == "shard_map"
    assert "collectives" in ex.stats and "shard_map_blocks" in ex.stats
    # the facade adds no lowering logic of its own
    assert DistBlockExecutor.run_schedule is BlockExecutor.run_schedule
    assert not hasattr(DistBlockExecutor, "_compile_sharded")
