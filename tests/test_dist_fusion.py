"""Distributed fusion subsystem tests (core/dist): ShardSpec, the
resharding-insertion pass, COMM fusibility + graph-builder parity, the
``comm`` cost model, placement-aware caching, and DistBlockExecutor
bit-identity vs the single-device executor.

Placement/cost/partition tests use synthetic shard counts (no devices
needed).  Executor tests run on however many devices the process has — 1
under the plain tier-1 job, 8 under the CI dist job
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — plus one
subprocess test that always exercises an 8-device mesh.
"""

import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core import dist
from repro.core import lazy as bh
from repro.core.algorithms import partition
from repro.core.blocks import BlockInfo
from repro.core.cache import tape_signature
from repro.core.cost import CommCost, make_cost_model
from repro.core.dist import (DistBlockExecutor, ShardSpec, block_comm_bytes,
                             comm_op_bytes, host_mesh, insert_resharding,
                             spec_of, view_aligned)
from repro.core.fusion import build_graph, build_graph_reference, fusible
from repro.core.ir import COMM_OPS, BaseArray, Op, View
from repro.core.lazy import fresh_runtime

N_DEV = len(jax.devices())


def _sharded_tape(rt_kwargs=None, n_shards=4):
    """Trace the window-pipeline program with a sharded input; returns the
    resharded tape (COMM ops already injected by the flush path is NOT used
    — we capture the raw tape and reshard explicitly)."""
    with fresh_runtime(**(rt_kwargs or {})) as rt:
        x = bh.asarray(np.arange(32, dtype=np.float64))
        dist.shard(x, n=n_shards)
        zs = [x[i:28 + i] * 2.0 for i in range(3)]
        t = zs[0] + zs[1] + zs[2]
        t.rt.record(Op("sync", None, sync_bases=frozenset({t.view.base})))
        tape = list(rt.tape)
        rt.tape.clear()
    return insert_resharding(tape)


# ---------------------------------------------------------------------------
# ShardSpec
# ---------------------------------------------------------------------------

def test_shardspec_geometry():
    s = ShardSpec.for_dim((32, 8), 0, "dev", 4)
    assert s.n_shards == 4 and s.sharded_dim == 0 and s.divides()
    assert s.chunk_shape() == (8, 8)
    assert not s.is_replicated
    assert s.drop_dim(1).mesh_axes == ("dev",)
    assert s.placement_key() == (("dev", None), (("dev", 4),))
    r = ShardSpec.replicated((32, 8))
    assert r.is_replicated and r.sharded_dim is None
    assert ShardSpec.for_dim((30,), 0, "dev", 4).divides() is False


def test_shardspec_from_logical_reuses_rules():
    from repro.distributed.sharding import RULES_TRAIN
    fake = SimpleNamespace(shape={"data": 4, "model": 2})
    s = ShardSpec.from_logical((8, 64), ("heads", "embed"), RULES_TRAIN, fake)
    assert s.mesh_axes == ("model", "data")
    assert s.n_shards == 8
    # non-divisible dims fall back to replication (rules machinery)
    s = ShardSpec.from_logical((2, 64), ("kv_heads", "embed"), RULES_TRAIN,
                               SimpleNamespace(shape={"data": 16, "model": 16}))
    assert s.mesh_axes == (None, "data")


def test_view_aligned():
    b = BaseArray(32, np.dtype(np.float64))
    s = ShardSpec.for_dim((32,), 0, "dev", 4)
    assert view_aligned(View.contiguous(b, (32,)), s)
    assert view_aligned(View.contiguous(b, (4, 8)), s)
    assert not view_aligned(View(b, 1, (31,), (1,)), s)       # shifted window
    assert not view_aligned(View(b, 0, (16,), (2,)), s)       # strided
    assert not view_aligned(View(b, 0, (2, 32), (0, 1)), s)   # broadcast
    assert view_aligned(View(b, 1, (31,), (1,)), None)        # replicated


# ---------------------------------------------------------------------------
# Resharding insertion
# ---------------------------------------------------------------------------

def test_reshard_noop_without_sharding():
    with fresh_runtime() as rt:
        x = bh.asarray(np.arange(8.0))
        y = x[1:] * 2.0
        y.rt.record(Op("sync", None, sync_bases=frozenset({y.view.base})))
        tape = list(rt.tape)
        rt.tape.clear()
    assert insert_resharding(tape) == tape


def test_reshard_inserts_allgather_per_read_site():
    tape = _sharded_tape()
    comms = [op for op in tape if op.opcode in COMM_OPS]
    assert len(comms) == 3                       # one per window read
    assert all(op.opcode == "comm_allgather" for op in comms)
    assert all(spec_of(op.out.base) is None for op in comms)   # replicated
    # every comm output is consumed then DEL'd (single-use temporary)
    for c in comms:
        assert any(c.out.base in op.del_bases for op in tape)
    # consumers were rewritten off the sharded base
    muls = [op for op in tape if op.opcode == "mul"]
    assert all(spec_of(op.in_views()[0].base) is None for op in muls)
    # uid order still matches tape order (BlockInfo's program-order key)
    uids = [op.uid for op in tape]
    assert uids == sorted(uids)


def test_reshard_aligned_chain_needs_no_comm():
    with fresh_runtime() as rt:
        x = bh.asarray(np.arange(32, dtype=np.float64))
        dist.shard(x, n=4)
        y = bh.exp(x * 0.5) + 1.0
        y.rt.record(Op("sync", None, sync_bases=frozenset({y.view.base})))
        tape = list(rt.tape)
        rt.tape.clear()
    out = insert_resharding(tape)
    assert not any(op.opcode in COMM_OPS for op in out)
    # placement propagated through the elementwise chain
    assert spec_of(y.view.base) is not None
    assert spec_of(y.view.base).placement_key() == \
        spec_of(x.view.base).placement_key()


def test_reshard_reduction_over_sharded_axis_gathers():
    with fresh_runtime() as rt:
        x = bh.asarray(np.arange(32, dtype=np.float64))
        dist.shard(x, n=4)
        s = x.sum()
        s.rt.record(Op("sync", None, sync_bases=frozenset({s.view.base})))
        tape = list(rt.tape)
        rt.tape.clear()
    out = insert_resharding(tape)
    kinds = [op.opcode for op in out if op.opcode in COMM_OPS]
    assert kinds == ["comm_allgather"]
    assert spec_of(s.view.base) is None          # replicated result


def test_reshard_reduction_over_unsharded_axis_stays_local():
    with fresh_runtime() as rt:
        x = bh.asarray(np.arange(64, dtype=np.float64).reshape(8, 8))
        dist.shard(x, n=4)
        s = x.sum(axis=1)
        s.rt.record(Op("sync", None, sync_bases=frozenset({s.view.base})))
        tape = list(rt.tape)
        rt.tape.clear()
    out = insert_resharding(tape)
    assert not any(op.opcode in COMM_OPS for op in out)
    os_ = spec_of(s.view.base)
    assert os_ is not None and os_.shape == (8,) and os_.sharded_dim == 0


def test_reshard_ppermute_on_placement_mismatch():
    a = BaseArray(32, np.dtype(np.float64))
    a.shard_spec = ShardSpec.for_dim((32,), 0, "dev", 4)
    o = BaseArray(32, np.dtype(np.float64))
    o.shard_spec = ShardSpec.for_dim((32,), 0, "mdl", 4)
    op = Op("copy", View.contiguous(o, (32,)), (View.contiguous(a, (32,)),))
    out = insert_resharding([op])
    kinds = [x.opcode for x in out if x.opcode in COMM_OPS]
    assert kinds == ["comm_ppermute"]
    pp = out[0]
    assert spec_of(pp.out.base).placement_key() == o.shard_spec.placement_key()


def test_explicit_reshard_api_roundtrip():
    spec = ShardSpec.for_dim((32,), 0, "dev", 4)
    with fresh_runtime() as rt:
        x = bh.asarray(np.arange(32, dtype=np.float64))
        xs = dist.reshard(x, spec)               # replicated -> sharded
        kinds = [op.opcode for op in rt.tape if op.opcode in COMM_OPS]
        assert kinds == ["comm_reduce_scatter"]
        back = dist.reshard(xs, None)            # sharded -> replicated
        kinds = [op.opcode for op in rt.tape if op.opcode in COMM_OPS]
        assert kinds == ["comm_reduce_scatter", "comm_allgather"]
        np.testing.assert_array_equal(back.numpy(), np.arange(32.0))


def test_comm_op_bytes_model():
    tape = _sharded_tape()
    ag = next(op for op in tape if op.opcode == "comm_allgather")
    assert comm_op_bytes(ag) == 3 * 32 * 8       # (n-1) * nbytes
    spec = ShardSpec.for_dim((32,), 0, "dev", 4)
    b = BaseArray(32, np.dtype(np.float64))
    b.shard_spec = spec
    o = BaseArray(32, np.dtype(np.float64))
    o.shard_spec = ShardSpec.for_dim((32,), 0, "mdl", 4)
    pp = Op("comm_ppermute", View.contiguous(o, (32,)),
            (View.contiguous(b, (32,)),))
    assert comm_op_bytes(pp) == 32 * 8 * 3 / 4   # nbytes * (n-1)/n
    rs = Op("comm_reduce_scatter", View.contiguous(o, (32,)),
            (View.contiguous(BaseArray(32, np.dtype(np.float64)), (32,)),))
    assert comm_op_bytes(rs) == 0.0              # placement cast is local
    # identical collectives priced once per block
    dup = [op for op in tape if op.opcode == "comm_allgather"]
    assert block_comm_bytes(dup) == comm_op_bytes(dup[0])


# ---------------------------------------------------------------------------
# Fusibility and graph parity
# ---------------------------------------------------------------------------

def test_comm_is_a_fusion_boundary():
    tape = _sharded_tape()
    ag = next(op for op in tape if op.opcode == "comm_allgather")
    mul = next(op for op in tape if op.opcode == "mul")
    assert not fusible(ag, mul)
    assert not fusible(mul, ag)
    ags = [op for op in tape if op.opcode == "comm_allgather"]
    assert fusible(ags[0], ags[1])               # identical reshards merge
    dl = next(op for op in tape if op.opcode == "del")
    assert fusible(ag, dl)                       # system ops fuse with all


def test_graph_builder_parity_with_comm_ops():
    for n_shards in (2, 4):
        tape = _sharded_tape(n_shards=n_shards)
        g1 = build_graph(list(tape))
        g2 = build_graph_reference(list(tape))
        assert g1.dep_out == g2.dep_out
        assert g1.dep_in == g2.dep_in
        assert g1.fuse_forbidden == g2.fuse_forbidden


def test_partition_never_mixes_comm_and_compute():
    tape = _sharded_tape()
    res = partition(tape, algorithm="greedy", cost_model="comm")
    for block in res.op_blocks():
        ops = [tape[i] for i in block]
        kinds = {("comm" if op.opcode in COMM_OPS else "compute")
                 for op in ops if not op.is_system()}
        assert len(kinds) <= 1


# ---------------------------------------------------------------------------
# CommCost
# ---------------------------------------------------------------------------

def test_commcost_merge_saving_prices_collective_dedup():
    tape = _sharded_tape()
    ags = [op for op in tape if op.opcode == "comm_allgather"]
    cm = CommCost()
    cm.prepare(tape)
    b1, b2 = BlockInfo.from_op(ags[0]), BlockInfo.from_op(ags[1])
    saving = cm.merge_saving(b1, b2)
    # dedup saves the whole collective plus the deduplicated ext read
    expected_comm = comm_op_bytes(ags[0]) / cm.ici_bw
    assert saving >= expected_comm > 0
    merged = b1.merged_with(b2)
    assert block_comm_bytes(merged.ops) == comm_op_bytes(ags[0])


def test_commcost_monotone_on_program():
    tape = _sharded_tape()
    res_s = partition(tape, algorithm="singleton", cost_model="comm")
    res_g = partition(tape, algorithm="greedy", cost_model="comm")
    assert res_g.cost <= res_s.cost
    # fused partition elides collectives: sum blockwise unique comm bytes
    def fabric(res):
        return sum(block_comm_bytes([tape[i] for i in blk])
                   for blk in res.op_blocks())
    assert fabric(res_g) < fabric(res_s)


def test_commcost_sparse_weights_match_dense():
    tape = _sharded_tape()
    from repro.core.partition import PartitionState
    g = build_graph(list(tape))
    sparse = PartitionState(g, make_cost_model("comm"))
    dense = PartitionState(g, make_cost_model("comm"), dense=True)
    assert sparse.weights == dense.weights


# ---------------------------------------------------------------------------
# Placement-aware caching
# ---------------------------------------------------------------------------

def test_tape_signature_includes_topology_and_placement():
    with fresh_runtime() as rt:
        x = bh.asarray(np.arange(8.0))
        y = x * 2.0
        y.rt.record(Op("sync", None, sync_bases=frozenset({y.view.base})))
        tape = list(rt.tape)
        rt.tape.clear()
    k1 = tape_signature(tape, "greedy", "comm")
    k2 = tape_signature(tape, "greedy", "comm", topology=(("dev", 8), "cpu"))
    assert k1 != k2
    x.view.base.shard_spec = ShardSpec.for_dim((8,), 0, "dev", 4)
    k3 = tape_signature(tape, "greedy", "comm")
    assert k3 != k1                              # placement changes the key


def test_merge_cache_not_shared_across_topology():
    from repro.core.scheduler import Scheduler
    with fresh_runtime() as rt:
        x = bh.asarray(np.arange(8.0))
        y = x * 2.0
        y.rt.record(Op("sync", None, sync_bases=frozenset({y.view.base})))
        tape = list(rt.tape)
        rt.tape.clear()
    sch = Scheduler()
    sch.plan(tape, topology=(("dev", 1), "cpu"))
    sch.plan(tape, topology=(("dev", 8), "cpu"))
    assert sch.cache.misses == 2 and sch.cache.hits == 0
    sch.plan(tape, topology=(("dev", 8), "cpu"))
    assert sch.cache.hits == 1


def _doubling_tape():
    with fresh_runtime() as rt:
        x = bh.asarray(np.arange(8.0))
        y = x * 2.0
        y.rt.record(Op("sync", None, sync_bases=frozenset({y.view.base})))
        tape = list(rt.tape)
        rt.tape.clear()
    return tape, x


def test_merge_cache_misses_across_placements_once_sharding_is_used():
    from repro.core.scheduler import Scheduler
    tape, x = _doubling_tape()
    sch = Scheduler()
    x.view.base.shard_spec = ShardSpec.for_dim((8,), 0, "dev", 4)
    sch.plan(tape, cost_model="comm")
    x.view.base.shard_spec = ShardSpec.for_dim((8,), 0, "dev", 2)
    sch.plan(tape, cost_model="comm")
    assert sch.cache.misses == 2 and sch.cache.hits == 0
    again = sch.plan(tape, cost_model="comm")
    assert sch.cache.hits == 1 and again.replayed


def test_unsharded_key_skips_the_placement_digest(monkeypatch, tmp_path):
    """Before any ShardSpec exists the key's position 5 is ``()``; the
    prefix the plan store reads positionally (``key[2]``, the cost model's
    token) is where it was, and the digest comes back once sharding is
    used."""
    import json

    import repro.core.dist.spec as spec_mod
    from repro.core.cost import model_cache_token
    from repro.core.serve import PlanStore

    tape, _ = _doubling_tape()
    monkeypatch.setattr(spec_mod, "_SPECS_SEEN", False)
    store = PlanStore(str(tmp_path))
    for model in ("bohrium", "calibrated"):
        key = tape_signature(tape, "greedy", model,
                             cost_token=model_cache_token(model))
        assert key[5] == ()
        assert key[2] == model_cache_token(model)
        assert store.store(key, ((0,),), None)
        with open(store.path_for(key)) as f:
            assert json.load(f)["epoch_sensitive"] == bool(key[2])
        assert store.load(key) == (((0,),), None)
    unsharded = tape_signature(tape, "greedy", "comm")
    monkeypatch.setattr(spec_mod, "_SPECS_SEEN", True)
    sharded = tape_signature(tape, "greedy", "comm")
    assert sharded[5] == dist.spec.placement_digest(tape) == (None, None)
    assert sharded != unsharded
    assert sharded[:5] + sharded[6:] == unsharded[:5] + unsharded[6:]


def test_merge_cache_capacity_bounds_the_schedule_templates():
    """Templates live inside the LRU entries: evicting an entry evicts its
    template, so a later tape of the evicted structure is planned afresh."""
    from repro.core.cache import MergeCache
    from repro.core.scheduler import PlanEntry, Scheduler

    tapes = []
    for n in (4, 8, 16):
        with fresh_runtime() as rt:
            x = bh.asarray(np.arange(float(n)))
            y = bh.sin(x) * 2.0
            y.rt.record(Op("sync", None,
                           sync_bases=frozenset({y.view.base})))
            tapes.append(list(rt.tape))
            rt.tape.clear()
    sch = Scheduler(MergeCache(capacity=2))
    scheds = [sch.plan(t) for t in tapes]
    assert len(sch.cache) == 2 and sch.cache.evictions == 1
    assert scheds[0].key not in sch.cache
    for s in scheds[1:]:
        entry = sch.cache._store[s.key]
        assert isinstance(entry, PlanEntry)
        assert len(entry.template) == len(s.blocks)
    assert not sch.plan(tapes[0]).replayed          # evicted: planned again
    assert sch.plan(tapes[0]).replayed


# ---------------------------------------------------------------------------
# DistBlockExecutor
# ---------------------------------------------------------------------------

def _window_program():
    x = bh.asarray(np.arange(64, dtype=np.float64))
    dist.shard(x, n=N_DEV)
    zs = [x[i:60 + i] * float(i + 1) for i in range(3)]
    return (zs[0] + zs[1] + zs[2]).numpy()


def _aligned_program():
    x = bh.asarray(np.linspace(0.0, 2.0, 8 * N_DEV))
    dist.shard(x, n=N_DEV)
    y = bh.exp(x) * 0.5 + bh.sqrt(x + 1.0)
    return y.numpy()


def _reduction_program():
    x = bh.asarray(np.arange(32.0 * N_DEV))
    dist.shard(x, n=N_DEV)
    return float((x * x).sum().numpy())


@pytest.mark.parametrize("prog", [_window_program, _aligned_program,
                                  _reduction_program])
def test_dist_executor_bit_identical(prog):
    with fresh_runtime(cost_model="comm", mesh=host_mesh()):
        got = prog()
    with fresh_runtime(cost_model="comm"):
        want = prog()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dist_executor_tier1_programs_bit_identical():
    """Acceptance: DistBlockExecutor == BlockExecutor on benchmark-suite
    programs (which exercise random, reductions, RMW, stencils...)."""
    from benchmarks.programs import black_scholes, game_of_life, heat_equation
    for fn, kw in ((black_scholes, dict(iters=2, n=512)),
                   (game_of_life, dict(iters=2, n=32)),
                   (heat_equation, dict(iters=2, n=32))):
        with fresh_runtime(cost_model="comm", mesh=host_mesh()):
            got = np.asarray(fn(**kw).numpy())
        with fresh_runtime(cost_model="comm"):
            want = np.asarray(fn(**kw).numpy())
        np.testing.assert_array_equal(got, want)


@pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device host mesh")
def test_dist_executor_uses_shard_map_and_elides_comm():
    with fresh_runtime(cost_model="comm", algorithm="greedy",
                       mesh=host_mesh()) as rt:
        _window_program()
        fused = dict(rt.executor.stats)
    with fresh_runtime(cost_model="comm", algorithm="singleton",
                       mesh=host_mesh()) as rt:
        _window_program()
        unfused = dict(rt.executor.stats)
    assert fused["shard_map_blocks"] > 0
    assert 0 < fused["interconnect_bytes"] < unfused["interconnect_bytes"]
    assert fused["collectives"] < unfused["collectives"]


def test_dist_executor_cache_key_sees_placement():
    ex = DistBlockExecutor(mesh=host_mesh())
    b = BaseArray(8 * max(N_DEV, 1), np.dtype(np.float64))
    o = BaseArray(8 * max(N_DEV, 1), np.dtype(np.float64))
    v, vo = View.contiguous(b, (b.size,)), View.contiguous(o, (o.size,))
    ops = [Op("mul", vo, (v, 2.0), new_bases=frozenset({o}))]
    plan = SimpleNamespace(signature=("sig",))
    k1 = ex._cache_key(ops, plan)
    b.shard_spec = ShardSpec.for_dim((b.size,), 0, "dev", 4)
    k2 = ex._cache_key(ops, plan)
    assert k1 != k2


def test_eight_device_mesh_adopted_grids_subprocess():
    """Adopted 2-D grids, sharded by rows, on a real 8-device mesh: a grid
    whose rows divide over the mesh runs through ``shard_map`` and comes
    back in its own shape; one whose rows do not declines with
    ``placement``.  Both give NumPy's answer."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        from repro.core import dist
        from repro.core import lazy as bh
        from repro.core.dist import host_mesh
        from repro.core.lazy import fresh_runtime
        for shape, sharded in (((16, 6), True), ((4, 16), False)):
            g = np.arange(np.prod(shape), dtype=np.float64).reshape(shape)
            with fresh_runtime(cost_model="comm", mesh=host_mesh(8)) as rt:
                x = bh.asarray(g)
                dist.shard(x, n=8)
                y = x * 2.0 + 1.0
                z = y + x
                got = z.numpy()
                buf = rt.buffers[y.view.base.uid]
                stats = dict(rt.executor.stats)
            assert np.array_equal(got, g * 3.0 + 1.0), shape
            assert (stats["shard_map_blocks"] > 0) == sharded, stats
            assert buf.shape == (g.size,), buf.shape
            assert rt.buffers[x.view.base.uid].shape == shape
        print("OK")
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(os.path.dirname(__file__), "..", "src"),
                    env.get("PYTHONPATH", "")) if p)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env)
    assert res.returncode == 0, res.stderr
    assert "OK" in res.stdout


def test_eight_device_mesh_subprocess():
    """Always exercise a real 8-device mesh (mirrors the CI dist job)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        from repro.core import dist
        from repro.core import lazy as bh
        from repro.core.dist import host_mesh
        from repro.core.lazy import fresh_runtime
        with fresh_runtime(cost_model="comm", mesh=host_mesh(8)) as rt:
            x = bh.asarray(np.arange(64, dtype=np.float64))
            dist.shard(x, n=8)
            y = (x[0:60] + x[1:61] + x[2:62]) * 0.5
            got = y.numpy()
            stats = rt.executor.stats
        want = (np.arange(64.)[0:60] + np.arange(64.)[1:61]
                + np.arange(64.)[2:62]) * 0.5
        assert np.array_equal(got, want)
        assert stats["shard_map_blocks"] > 0
        assert stats["interconnect_bytes"] > 0
        print("OK")
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(os.path.dirname(__file__), "..", "src"),
                    env.get("PYTHONPATH", "")) if p)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env)
    assert res.returncode == 0, res.stderr
    assert "OK" in res.stdout
