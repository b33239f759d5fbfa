"""Cross-flush loop fusion (DESIGN.md §16): recurrence detection edges,
hysteresis boundaries, deferral/drain bookkeeping, and bitwise fidelity of
the loop-lowered path against per-flush execution."""

import pytest

from repro.core import lazy as bh
from repro.core.cache import TapeMatcher, tape_io, tapes_structurally_equal
from repro.core.lazy import fresh_runtime


def _step(x, c=1.01):
    y = x * c + 0.5
    x.delete()
    return y


def _run_chain(iters, c=1.01, **rt_kw):
    """The minimal recurring program: x <- x*c + 0.5 with a flush per
    step (fresh-chain carry: new base every step, old base deleted)."""
    with fresh_runtime(**rt_kw) as rt:
        x = bh.full(256, 1.0)
        bh.flush()
        for _ in range(iters):
            x = _step(x, c)
            bh.flush()
        out = x.numpy()
        hist = list(rt.history)
        x._alive = False
    return out, hist


def _deferred(hist):
    return [h for h in hist if h.get("loop_deferred")]


def _drains(hist):
    return [h for h in hist if h.get("loop_drain")]


# ---------------------------------------------------------------------------
# Steady-state detection and history bookkeeping
# ---------------------------------------------------------------------------

def test_steady_state_defers_and_drains():
    out, hist = _run_chain(10, loop_fusion=True, loop_threshold=3,
                           loop_unroll=32)
    ref, _ = _run_chain(10, loop_fusion=False)
    assert out.tobytes() == ref.tobytes()
    # threshold=3: iterations 1-3 execute per-flush, 4-10 defer
    assert len(_deferred(hist)) == 7
    drains = _drains(hist)
    assert len(drains) == 1                      # tail drain at materialize
    assert drains[0]["n_iterations"] == 7
    assert drains[0]["cached"] is True
    assert "exec" in drains[0]


def test_deferred_entries_carry_pending_depth():
    _, hist = _run_chain(6, loop_fusion=True, loop_threshold=2,
                         loop_unroll=32)
    pend = [h["pending"] for h in _deferred(hist)]
    assert pend == [1, 2, 3, 4]                  # queue depth grows by one


def test_normal_entries_carry_merge_counters():
    _, hist = _run_chain(4, loop_fusion=False)
    work = [h for h in hist if "merge_hits" in h]
    assert work, "executed flushes must record merge-cache deltas"
    assert all("merge_misses" in h for h in work)
    # the recurring structure hits the cache from the second flush on
    assert sum(h["merge_hits"] for h in work) > 0


# ---------------------------------------------------------------------------
# Hysteresis boundaries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("threshold", [1, 2, 4])
def test_hysteresis_boundary(threshold):
    """Deferral starts exactly at occurrence ``threshold + 1``."""
    iters = threshold + 3
    _, hist = _run_chain(iters, loop_fusion=True, loop_threshold=threshold,
                         loop_unroll=64)
    assert len(_deferred(hist)) == iters - threshold


def test_below_threshold_never_defers():
    _, hist = _run_chain(3, loop_fusion=True, loop_threshold=3,
                         loop_unroll=64)
    assert _deferred(hist) == []
    assert _drains(hist) == []


def test_unroll_capacity_forces_mid_run_drains():
    _, hist = _run_chain(12, loop_fusion=True, loop_threshold=2,
                         loop_unroll=4)
    # 10 deferred iterations -> capacity drains of 4, 4, tail drain of 2
    assert [d["n_iterations"] for d in _drains(hist)] == [4, 4, 2]


# ---------------------------------------------------------------------------
# Recurrence edges: what must (and must not) break the streak
# ---------------------------------------------------------------------------

def _run_two_phase(cs, **rt_kw):
    with fresh_runtime(**rt_kw) as rt:
        x = bh.full(256, 1.0)
        bh.flush()
        for c in cs:
            x = _step(x, c)
            bh.flush()
        out = x.numpy()
        hist = list(rt.history)
        x._alive = False
    return out, hist


def test_changed_constant_breaks_recurrence():
    """A different literal is a different program: structure comparison
    includes literal operands, so the streak resets and nothing fuses a
    stale constant into the loop body."""
    cs = [1.01, 1.01, 1.01, 1.01, 2.5, 2.5]
    ref, _ = _run_two_phase(cs, loop_fusion=False)
    out, hist = _run_two_phase(cs, loop_fusion=True, loop_threshold=2,
                               loop_unroll=32)
    assert out.tobytes() == ref.tobytes()
    # the constant switch lands mid-streak: deferred iterations drain and
    # the 2.5 steps re-warm from scratch
    assert any(d["n_iterations"] for d in _drains(hist))


def test_changed_structure_breaks_recurrence():
    def run(**rt_kw):
        with fresh_runtime(**rt_kw) as rt:
            x = bh.full(256, 1.0)
            bh.flush()
            for i in range(8):
                if i == 5:
                    y = x * 1.01 + bh.sin(x)    # different shape of step
                else:
                    y = x * 1.01 + 0.5
                x.delete()
                x = y
                bh.flush()
            out = x.numpy()
            hist = list(rt.history)
            x._alive = False
        return out, hist

    ref, _ = run(loop_fusion=False)
    out, hist = run(loop_fusion=True, loop_threshold=2, loop_unroll=32)
    assert out.tobytes() == ref.tobytes()
    # iterations 3-5 deferred, drained when the odd step appears, then the
    # tail re-warms (6,7 per-flush under threshold=2)
    assert sum(d["n_iterations"] for d in _drains(hist)) == len(
        _deferred(hist))


def test_interleaved_tapes_never_defer():
    """A/B/A/B alternation: consecutive flushes never repeat, so the
    streak never forms and everything executes per-flush."""
    def run(**rt_kw):
        with fresh_runtime(**rt_kw) as rt:
            x = bh.full(256, 1.0)
            y = bh.full(128, 2.0)
            bh.flush()
            for _ in range(6):
                x = _step(x)
                bh.flush()
                y = _step(y, 1.5)
                bh.flush()
            ox, oy = x.numpy(), y.numpy()
            hist = list(rt.history)
            x._alive = y._alive = False
        return ox, oy, hist

    rx, ry, _ = run(loop_fusion=False)
    ox, oy, hist = run(loop_fusion=True, loop_threshold=2, loop_unroll=32)
    assert _deferred(hist) == []
    assert ox.tobytes() == rx.tobytes()
    assert oy.tobytes() == ry.tobytes()


def test_mid_loop_materialize_drains():
    """A .numpy() mid-loop is a SYNC: the queue drains so the host sees
    the true current state, then the loop re-arms."""
    def run(**rt_kw):
        with fresh_runtime(**rt_kw):
            x = bh.full(256, 1.0)
            bh.flush()
            mid = None
            for i in range(10):
                x = _step(x)
                bh.flush()
                if i == 6:
                    mid = x.numpy().copy()
            out = x.numpy()
            x._alive = False
        return mid, out

    rmid, rout = run(loop_fusion=False)
    mid, out = run(loop_fusion=True, loop_threshold=2, loop_unroll=64)
    assert mid.tobytes() == rmid.tobytes()
    assert out.tobytes() == rout.tobytes()


def test_use_cache_off_disables_deferral():
    _, hist = _run_chain(8, loop_fusion=True, loop_threshold=2,
                         loop_unroll=32, use_cache=False)
    assert _deferred(hist) == []


def test_empty_flush_drains_pending():
    with fresh_runtime(loop_fusion=True, loop_threshold=2,
                       loop_unroll=64) as rt:
        x = bh.full(256, 1.0)
        bh.flush()
        for _ in range(6):
            x = _step(x)
            bh.flush()
        assert rt._loop.pending
        bh.flush()                               # empty tape -> drain
        assert not rt._loop.pending
        assert _drains(rt.history)
        out = x.numpy()
        x._alive = False
    ref, _ = _run_chain(6, loop_fusion=False)
    assert out.tobytes() == ref.tobytes()


# ---------------------------------------------------------------------------
# Bitwise fidelity of the loop-lowered path
# ---------------------------------------------------------------------------

def _heat(iters, **rt_kw):
    with fresh_runtime(**rt_kw) as rt:
        g = bh.zeros((32, 32))
        g[0, :] = 100.0
        bh.flush()
        for _ in range(iters):
            inner = (g[1:-1, :-2] + g[1:-1, 2:] + g[:-2, 1:-1]
                     + g[2:, 1:-1]) * 0.25
            g[1:-1, 1:-1] = inner
            inner.delete()
            bh.flush()
        out = g.numpy()
        g._alive = False
    return out


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_inplace_stencil_bitwise(backend):
    """RMW partial-write carry (same base every step) on both backend
    stacks — the loop body composes whatever the lower stage picked."""
    ref = _heat(9, loop_fusion=False, backend=backend)
    got = _heat(9, loop_fusion=True, loop_threshold=2, loop_unroll=4,
                backend=backend)
    assert ref.tobytes() == got.tobytes()


def test_adopted_grid_carry_keeps_its_shape():
    """An adopted 2-D grid carried by a fused loop: its store buffer keeps
    the grid's shape through every drain (a ``fori_loop`` carry keeps one
    shape), and the result is the per-flush one."""
    import numpy as np

    def run(**rt_kw):
        with fresh_runtime(**rt_kw) as rt:
            g = bh.asarray(np.arange(24.0 * 24.0).reshape(24, 24) % 7.0)
            shapes = []
            for _ in range(9):
                inner = (g[1:-1, :-2] + g[1:-1, 2:] + g[:-2, 1:-1]
                         + g[2:, 1:-1]) * 0.25
                g[1:-1, 1:-1] = inner
                inner.delete()
                bh.flush()
                buf = rt.buffers.get(g.view.base.uid)
                shapes.append(None if buf is None else buf.shape)
            bh.flush()                   # an empty tape drains the loop
            shapes.append(rt.buffers[g.view.base.uid].shape)
            out = g.numpy()
            g._alive = False
            hist = list(rt.history)
        return out, shapes, hist

    ref, ref_shapes, _ = run(loop_fusion=False)
    got, shapes, hist = run(loop_fusion=True, loop_threshold=2,
                            loop_unroll=4)
    assert _drains(hist) and _deferred(hist)
    assert set(ref_shapes) == set(shapes) == {(24, 24)}
    assert ref.tobytes() == got.tobytes()


def test_random_bearing_loop_bitwise():
    """Each deferred iteration's RNG ops must replay their own trace-time
    salts from the stacked salt matrix."""
    def run(**rt_kw):
        with fresh_runtime(**rt_kw) as rt:
            x = bh.full(512, 0.0)
            bh.flush()
            for _ in range(9):
                r = bh.floor(bh.random((512,)) * 8.0)
                y = x + r
                r.delete()
                x.delete()
                x = y
                bh.flush()
            out = x.numpy()
            x._alive = False
        return out
    ref = run(loop_fusion=False)
    got = run(loop_fusion=True, loop_threshold=2, loop_unroll=4)
    assert ref.tobytes() == got.tobytes()


@pytest.mark.parametrize("donate", [False, True])
def test_donation_bitwise(donate):
    """Forcing buffer donation on must not change loop-fused results (on
    CPU jit ignores the donation hint, on GPU/TPU it aliases buffers —
    either way the fused loop's final state must match per-flush)."""
    ref, _ = _run_chain(9, loop_fusion=False, donate=donate)
    got, hist = _run_chain(9, loop_fusion=True, loop_threshold=2,
                           loop_unroll=4, donate=donate)
    assert _deferred(hist)
    assert ref.tobytes() == got.tobytes()


# ---------------------------------------------------------------------------
# TapeMatcher: the steady-state fast path is exactly the generic check
# ---------------------------------------------------------------------------

def _record(build):
    with fresh_runtime() as rt:
        keep = build()
        tape = list(rt.tape)
        rt.tape.clear()
        for a in keep:
            a._alive = False
    return tape


def test_matcher_agrees_with_generic_path():
    def build(c=0.5):
        x = bh.full(64, 1.0)
        y = x * 2.0 + c
        z = y.sum()
        y.delete()
        return [x, z]

    t1, t2 = _record(build), _record(build)
    m = TapeMatcher(t1, tape_io(t1))
    assert m.match(t1) == tape_io(t1)            # template self-match
    assert tapes_structurally_equal(t1, t2)
    assert m.match(t2) == tape_io(t2)            # fresh bases, same shape

    t3 = _record(lambda: build(0.75))            # literal changed
    assert not tapes_structurally_equal(t1, t3)
    assert m.match(t3) is None

    def build_other():
        x = bh.full(64, 1.0)
        y = x + x
        z = y.sum()
        y.delete()
        return [x, z]

    t4 = _record(build_other)                    # structure changed
    assert m.match(t4) is None
    assert m.match(t1[:-1]) is None              # length changed


def test_matcher_rejects_aliasing_pattern_change():
    """Two tapes whose ops agree field-by-field but whose base-identity
    pattern differs (same base read twice vs two distinct bases) must not
    match: the renumbering is part of the structure."""
    def aliased():
        x = bh.full(64, 1.0)
        y = x * x                                # same base twice
        return [x, y]

    def split():
        x = bh.full(64, 1.0)
        w = bh.full(64, 1.0)
        y = x * w                                # two distinct bases
        return [x, w, y]

    ta, ts = _record(aliased), _record(split)
    # align lengths: drop the extra full() op, keep only the mul
    mul_a = [op for op in ta if op.opcode not in ("full",)]
    mul_s = [op for op in ts if op.opcode not in ("full",)]
    ma = TapeMatcher(mul_a, tape_io(mul_a))
    assert ma.match(mul_a) == tape_io(mul_a)
    assert ma.match(mul_s) is None
