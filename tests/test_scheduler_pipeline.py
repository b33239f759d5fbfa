"""Staged scheduler pipeline tests (no hypothesis required).

* differential: the base-indexed graph builder produces identical E_d/E_f
  to the O(V²) reference on randomized tapes and on structured programs,
* regression: heap-based ``greedy`` picks the same merge sequence as the
  reference O(E)-rescan implementation,
* sparse weight-graph construction/maintenance matches the dense all-pairs
  path for every sparse cost model,
* ``Schedule``/``BlockPlan``: block IO, donatable inputs, stage stats,
* ``where`` result dtype follows the promoted dtype of its value branches.
"""

import random

import numpy as np
import pytest

from repro.core import (build_graph, build_graph_reference, make_cost_model,
                        partition, plan_blocks)
from repro.core import lazy as bh
from repro.core.algorithms import greedy, greedy_reference
from repro.core.lazy import fresh_runtime
from repro.core.partition import PartitionState

SPARSE_MODELS = ("bohrium", "max_contract", "max_locality")


# ---------------------------------------------------------------------------
# Randomized tape generator (deterministic; a seeded cousin of the
# hypothesis generator in test_wsp_properties.py, plus matmul/range ops so
# opaque and mixed-domain edges are exercised).
# ---------------------------------------------------------------------------

def random_tape(seed: int, n_actions: int = 24, size: int = 6):
    rnd = random.Random(seed)
    with fresh_runtime() as rt:
        pool = [bh.full(size, float(i)) for i in range(3)]

        def live():
            return [a for a in pool if a is not None]

        for _ in range(n_actions):
            act = rnd.randrange(10)
            arrays = live()
            a = arrays[rnd.randrange(len(arrays))]
            if act == 0:
                pool.append(bh.full(size, rnd.random()))
            elif act == 1:
                b = arrays[rnd.randrange(len(arrays))]
                pool.append(a + b)
            elif act == 2:
                pool.append(bh.sqrt(bh.absolute(a)))
            elif act == 3:
                b = arrays[rnd.randrange(len(arrays))]
                a += b
            elif act == 4:                      # shifted views (overlap)
                c = a.copy()
                c[1:] = a[:-1]
                pool.append(c)
            elif act == 5:                      # reduction (domain differs)
                s = a.sum()
                out = bh.zeros(size)
                out += s.broadcast_to((size,))
                pool.append(out)
            elif act == 6 and len(arrays) > 1:
                i = pool.index(a)
                a.delete()
                pool[i] = None
            elif act == 7:
                pool.append(bh.arange(size))
            elif act == 8:                      # opaque op
                m = bh.ones((size, size))
                v = a.broadcast_to((1, size))
                pool.append(bh.matmul(v, m).reshape(size))
                m.delete()
            else:
                pool.append(a * rnd.random())
        tape = list(rt.tape)
        rt.tape.clear()
        for a in pool:
            if a is not None:
                a._alive = False
    return tape


def structured_tapes():
    """Small versions of the structured programs (stencil, chain)."""
    tapes = {}
    with fresh_runtime() as rt:
        g = bh.zeros((10, 10))
        for _ in range(4):
            inner = (g[1:-1, :-2] + g[1:-1, 2:] + g[:-2, 1:-1]
                     + g[2:, 1:-1]) * 0.25
            g2 = g.copy()
            g2[1:-1, 1:-1] = inner
            inner.delete()
            g.delete()
            g = g2
        tapes["stencil"] = list(rt.tape)
        rt.tape.clear()
        g._alive = False
    with fresh_runtime() as rt:
        x = bh.full(32, 1.0)
        for _ in range(6):
            t = x * 1.01
            y = t + 0.5
            t.delete()
            x.delete()
            x = y
        tapes["chain"] = list(rt.tape)
        rt.tape.clear()
        x._alive = False
    return tapes


ALL_TAPES = [("rand%d" % s, random_tape(s)) for s in range(12)]
ALL_TAPES += list(structured_tapes().items())


# ---------------------------------------------------------------------------
# Differential: indexed builder == O(V²) reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,tape", ALL_TAPES, ids=[n for n, _ in ALL_TAPES])
def test_indexed_builder_matches_reference(name, tape):
    a = build_graph(list(tape))
    b = build_graph_reference(list(tape))
    assert a.dep_out == b.dep_out
    assert a.dep_in == b.dep_in
    assert a.fuse_forbidden == b.fuse_forbidden


# ---------------------------------------------------------------------------
# Sparse weight graph == dense weight graph, at init and across merges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model_name", SPARSE_MODELS)
def test_sparse_weights_match_dense_init(model_name):
    for name, tape in ALL_TAPES:
        g = build_graph(list(tape))
        sp = PartitionState(g, make_cost_model(model_name))
        de = PartitionState(g, make_cost_model(model_name), dense=True)
        assert not sp._dense and de._dense
        assert sp.weights == de.weights, (name, model_name)


@pytest.mark.parametrize("model_name", SPARSE_MODELS)
def test_sparse_weights_match_dense_after_merges(model_name):
    rnd = random.Random(0)
    for name, tape in ALL_TAPES[:8]:
        g = build_graph(list(tape))
        sp = PartitionState(g, make_cost_model(model_name))
        de = PartitionState(g, make_cost_model(model_name), dense=True)
        for _ in range(6):
            ids = sorted(sp.blocks)
            pairs = [(u, v) for i, u in enumerate(ids) for v in ids[i + 1:]
                     if sp.legal_merge(u, v)]
            if not pairs:
                break
            u, v = rnd.choice(pairs)
            sp.merge(u, v)
            de.merge(u, v)
            assert sp.weights == de.weights, (name, model_name)


# ---------------------------------------------------------------------------
# Regression: heap greedy picks the same merge sequence as the reference
# ---------------------------------------------------------------------------

def _merge_log(algo, state):
    log = []
    orig = state.merge

    def logging_merge(u, v):
        log.append((u, v))
        return orig(u, v)

    state.merge = logging_merge
    algo(state)
    return log, state


@pytest.mark.parametrize("model_name", SPARSE_MODELS + ("robinson", "tpu"))
def test_heap_greedy_matches_reference_sequence(model_name):
    for name, tape in ALL_TAPES:
        g = build_graph(list(tape))
        l_heap, s_heap = _merge_log(
            greedy, PartitionState(g, make_cost_model(model_name)))
        l_ref, s_ref = _merge_log(
            greedy_reference,
            PartitionState(g, make_cost_model(model_name), dense=True))
        assert l_heap == l_ref, (name, model_name)
        mem_heap = {frozenset(m) for m in s_heap.members.values()}
        mem_ref = {frozenset(m) for m in s_ref.members.values()}
        assert mem_heap == mem_ref, (name, model_name)


def test_partition_engine_matches_reference_path():
    """Staged engine (indexed builder + sparse weights + heap greedy) ==
    seed path (reference builder + dense weights + rescan greedy)."""
    for name, tape in ALL_TAPES:
        fast = partition(tape, algorithm="greedy", cost_model="bohrium")
        slow = partition(tape, algorithm="greedy_reference",
                         cost_model="bohrium", builder="reference",
                         dense_weights=True)
        assert fast.cost == slow.cost, name
        assert fast.op_blocks() == slow.op_blocks(), name


# ---------------------------------------------------------------------------
# Schedule / BlockPlan
# ---------------------------------------------------------------------------

def _record_dying_input_program(rt):
    """x is consumed and deleted inside the block that reads it."""
    from repro.core.ir import Op
    x = bh.random((32,))
    bh.flush()                      # x pre-exists: it is a block INPUT
    y = x * 2.0 + 1.0
    x.delete()                      # dies inside the same flush
    rt.record(Op("sync", None, sync_bases=frozenset({y.view.base})))
    tape = list(rt.tape)
    rt.tape.clear()
    y._alive = False
    return tape


def test_blockplan_marks_dying_inputs_donatable():
    with fresh_runtime() as rt:
        tape = _record_dying_input_program(rt)
        x_uid = next(op for op in tape if op.opcode == "mul").inputs[0].base.uid
    res = partition(tape, algorithm="greedy", cost_model="bohrium")
    plans = plan_blocks(tape, res.op_blocks())
    work = [p for p in plans if p.has_work]
    blk = next(p for p in work if x_uid in p.inputs)
    assert blk.inputs.index(x_uid) in blk.donatable
    # the SYNC'd output must never be donatable
    y_uid = next(op for op in tape if op.opcode == "add").out.base.uid
    for p in plans:
        if y_uid in p.inputs:
            assert p.inputs.index(y_uid) not in p.donatable


def test_synced_base_never_donatable():
    from repro.core.ir import Op
    with fresh_runtime() as rt:
        x = bh.random((16,))
        bh.flush()
        y = x + 1.0
        # host keeps x: DEL+SYNC in one flush
        rt.record(Op("sync", None, sync_bases=frozenset({x.view.base})))
        x.delete()
        tape = list(rt.tape)
        rt.tape.clear()
        y._alive = False
        x_uid = next(op for op in tape if op.opcode == "add").inputs[0].base.uid
    res = partition(tape, algorithm="greedy", cost_model="bohrium")
    for p in plan_blocks(tape, res.op_blocks()):
        if x_uid in p.inputs:
            assert p.inputs.index(x_uid) not in p.donatable


def test_flush_pipeline_stats_and_cache():
    with fresh_runtime(algorithm="greedy") as rt:
        ys = []
        for it in range(2):
            x = bh.random((64,))
            y = x * 3.0
            x.delete()
            _ = y.numpy()
            ys.append(y)            # keep alive: both tapes stay identical
        cold, warm = rt.history[0], rt.history[1]
        assert not cold["cached"] and warm["cached"]
        assert "t_graph_s" in cold and "t_partition_s" in cold
        assert "t_schedule_s" in cold and "t_schedule_s" in warm
        # CPU backend: donation is auto-disabled, dispatch still correct
        if rt.executor.donation_enabled() is False:
            assert rt.executor.stats["donated_buffers"] == 0


def test_forced_donation_still_correct():
    """donate=True end-to-end: on CPU jax ignores the donation (warning),
    on GPU/TPU it aliases buffers — results must be identical either way."""
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with fresh_runtime(algorithm="greedy", donate=True) as rt:
            x = bh.random((128,))
            bh.flush()
            ref = np.asarray(x.numpy())
            y = x * 2.0 + 1.0
            x.delete()
            got = y.numpy()
    np.testing.assert_allclose(got, ref * 2.0 + 1.0)


def test_legacy_executor_run_still_works():
    from repro.core.executor import BlockExecutor
    with fresh_runtime() as rt:
        x = bh.full(8, 2.0)
        y = x * 4.0
        rt.record_sync = None
        from repro.core.ir import Op
        rt.record(Op("sync", None, sync_bases=frozenset({y.view.base})))
        tape = list(rt.tape)
        rt.tape.clear()
        x._alive = y._alive = False
        y_uid = y.view.base.uid
    res = partition(tape, algorithm="greedy", cost_model="bohrium")
    ex = BlockExecutor()
    buffers = {}
    ex.run(tape, res.op_blocks(), buffers)
    np.testing.assert_allclose(np.asarray(ex.sync_store[y_uid]).reshape(8),
                               np.full(8, 8.0))


# ---------------------------------------------------------------------------
# where() dtype promotion
# ---------------------------------------------------------------------------

def test_where_dtype_follows_value_branches():
    with fresh_runtime():
        a32 = bh.full((8,), 2.0, np.float32)
        b32 = bh.full((8,), 3.0, np.float32)
        c = bh.where(a32 > b32, a32, b32)
        assert c.dtype == np.float32
        got = c.numpy()
        assert got.dtype == np.float32
        np.testing.assert_allclose(got, np.full(8, 3.0, np.float32))
        b64 = bh.full((8,), 3.0, np.float64)
        assert bh.where(a32 > 0.0, a32, b64).dtype == np.float64
        i32 = bh.full((8,), 5, np.int32)
        j32 = bh.full((8,), 7, np.int32)
        w = bh.where(i32 < j32, i32, j32)
        assert w.dtype == np.int32
        assert w.numpy().dtype == np.int32


# ---------------------------------------------------------------------------
# Merge-cache replay: a hit rebinds the cached schedule, and the rebound
# schedule equals a fresh schedule + lower stage on the same tape
# ---------------------------------------------------------------------------

def _spy_replays(monkeypatch):
    """Wrap ``Scheduler.plan``: for every replayed schedule, record it
    beside what ``plan_blocks`` + ``lower_plans`` give on the same tape
    from the same block lists."""
    from dataclasses import replace

    from repro.core.scheduler import Scheduler, lower_plans

    seen = []
    real = Scheduler.plan

    def plan(self, tape, **kw):
        sched = real(self, tape, **kw)
        if sched.replayed:
            fresh = plan_blocks(tape, [p.op_indices for p in sched.blocks])
            lowering = kw.get("lowering")
            if lowering is not None:
                model = make_cost_model(kw.get("cost_model", "bohrium"))
                fresh = [replace(p, lowering=d) if d is not None else p
                         for p, d in zip(fresh, lower_plans(
                             tape, fresh, lowering, model))]
            seen.append((sched.blocks, fresh))
        return sched

    monkeypatch.setattr(Scheduler, "plan", plan)
    return seen


def _tiny_dense():
    import jax

    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.models.lazy_transformer import LazyTransformer

    cfg = ModelConfig(name="lm_tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=97,
                      dtype="float32", param_dtype="float32",
                      norm_plus_one=True, tie_embeddings=False)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    return LazyTransformer(params, cfg, backend="lm"), 97


def _tiny_dsv2lite():
    import json
    from pathlib import Path

    from bench.spec import load_module

    bench = Path(__file__).resolve().parent.parent / "bench"
    cfg = dict(json.loads((bench / "configs" / "dsv2lite.json").read_text()),
               hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               kv_lora_rank=16, qk_rope_head_dim=8, qk_nope_head_dim=16,
               v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
               n_routed_experts=4, held_experts=[0, 4], num_experts_per_tok=2,
               n_shared_experts=1, num_hidden_layers=3, vocab_size=256)
    return load_module("systems", "lazy_mla_moe", bench)._program(cfg, 7), 256


@pytest.mark.parametrize("make", [_tiny_dense, _tiny_dsv2lite],
                         ids=["dense", "dsv2lite"])
def test_replayed_prefill_schedule_equals_a_fresh_one(make, monkeypatch):
    """Short (S) and long (L) prompts in the orders S->S, S->L, L->L and
    L->S, twice, so each order's key (it holds the previous prompt's
    frees) is planned once and replayed once; the replayed logits are
    bit-identical to the same prompt planned without the cache."""
    import jax

    with jax.default_matmul_precision("highest"):
        lt, vocab = make()
        seen = _spy_replays(monkeypatch)
        rng = np.random.default_rng(3)
        for n in (4, 4, 12, 12, 4) * 2:
            tokens = rng.integers(0, vocab, (1, n), dtype=np.int32)
            got = lt.prefill(tokens, 16)
            if lt.rt.history[-1]["replayed"]:
                lt.rt.use_cache = False
                try:
                    fresh = lt.prefill(tokens, 16)
                finally:
                    lt.rt.use_cache = True
                assert got.tobytes() == fresh.tobytes()
    assert len(seen) >= 4
    for replayed, fresh in seen:
        assert replayed == fresh


def test_replayed_loop_body_schedule_equals_a_fresh_one(monkeypatch):
    """A recurring numerical tape: its steady-state flushes replay, and
    the loop fuser plans its body from a replayed schedule."""
    seen = _spy_replays(monkeypatch)
    with fresh_runtime(loop_fusion=True, loop_threshold=3,
                       loop_unroll=8) as rt:
        x = bh.full(64, 1.0)
        bh.flush()
        for _ in range(12):
            y = bh.sin(x) * 0.5 + x * 0.25
            x.delete()
            x = y
            bh.flush()
        out = x.numpy()
        assert rt.executor.stats["loop_iterations"] > 0
    assert np.all(np.isfinite(out))
    assert seen
    for replayed, fresh in seen:
        assert replayed == fresh


def test_plan_store_hit_then_replay_in_a_fresh_scheduler(tmp_path,
                                                        monkeypatch):
    """A second process's runtime loads the plan from disk once (a disk
    hit builds the schedule from the stored blocks and decisions) and
    replays it from memory after."""
    def program():
        with fresh_runtime(loop_fusion=False, plan_store=str(tmp_path)) \
                as rt:
            vals = []
            for _ in range(4):
                x = bh.asarray(np.linspace(0.0, 1.0, 32))
                vals.append(float((bh.sin(x) * 0.5 + x * 0.25).sum()
                                  .numpy()))
            return vals, list(rt.history)

    cold, _ = program()
    seen = _spy_replays(monkeypatch)
    warm, hist = program()
    assert warm == cold
    assert hist[0]["cached"] and not hist[0]["replayed"]   # from disk
    assert [h["replayed"] for h in hist[2:]] == [True, True]
    assert len(seen) == 2
    for replayed, fresh in seen:
        assert replayed == fresh


def test_hit_runs_no_per_block_planning(monkeypatch):
    """A hit rebinds: no partition, no ``plan_blocks``, ``block_io``,
    ``block_dead_bases``, per-block ``block_signature`` or
    ``lower_plans``."""
    import repro.core.scheduler as sched_mod
    from repro.core.executor import BlockExecutor

    tape = random_tape(5, n_actions=40)
    policy = BlockExecutor(backend="pallas").lowering_policy()
    s = sched_mod.Scheduler()
    first = s.plan(tape, lowering=policy)
    calls = []
    for name in ("partition", "plan_blocks", "lower_plans", "block_io",
                 "block_dead_bases", "block_signature", "replace"):
        monkeypatch.setattr(sched_mod, name,
                            lambda *a, _n=name, **k: calls.append(_n))
    second = s.plan(tape, lowering=policy)
    assert calls == []
    assert second.replayed and not first.replayed
    assert second.blocks == first.blocks
    assert second.stats.keys() == {"t_schedule_s"}
