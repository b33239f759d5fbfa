"""Direct unit tests for executor block-IO semantics and the strided-view
slice fast path (ISSUE 2 satellites):

* ``block_io`` read-modify-write classification: a partial write of a
  pre-existing base makes the base a block INPUT; a full overwrite does not;
* the del−sync rule (``block_dead_bases``): SYNC'd bases stay observable —
  they are never donated, contracted, or dropped from outputs;
* ``_slice_plan`` lowers single-slice regularly-strided views to static
  reshape+slice (no O(size) gather-index constants in block jaxprs), with
  exact read/write equivalence against NumPy's own striding;
* ``_permute_plan`` lowers transposed and broadcast views to that slice of
  the view's axes reordered by stride, then a transpose and a broadcast;
  only reversed and overlapping views still read through an index gather;
* every lowering reads and writes a store buffer of any base shape by the
  base's flat row-major order, a whole view in the buffer's own shape is
  read as it is (``"stored"``, no reshape), and a write leaves the buffer
  in its base's shape.
"""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import (_read, _slice_plan, _view_index,
                                 _view_lowering, _write, block_dead_bases,
                                 block_io, make_block_fn)
from repro.core.ir import BaseArray, Op, View


def _base(n, name="b"):
    return BaseArray(n, np.dtype(np.float64), name=name)


# ---------------------------------------------------------------------------
# block_io read-modify-write classification
# ---------------------------------------------------------------------------

def test_partial_write_of_preexisting_base_is_input():
    src, dst = _base(8, "src"), _base(8, "dst")
    # copy src[0:4] into dst[2:6] — a partial write of pre-existing dst
    ops = [Op("copy", View(dst, 2, (4,), (1,)), (View(src, 0, (4,), (1,)),))]
    ins, outs, contracted = block_io(ops)
    assert ins == [src.uid, dst.uid]      # RMW: dst is read before defined
    assert outs == [dst.uid]
    assert contracted == []


def test_full_overwrite_of_preexisting_base_is_not_input():
    src, dst = _base(8, "src"), _base(8, "dst")
    ops = [Op("copy", View.contiguous(dst, (8,)),
              (View.contiguous(src, (8,)),))]
    ins, outs, _ = block_io(ops)
    assert ins == [src.uid]
    assert outs == [dst.uid]


def test_new_base_never_an_input_even_on_partial_write():
    dst = _base(8, "dst")
    ops = [Op("copy", View(dst, 2, (4,), (1,)), (1.0,),
              new_bases=frozenset({dst}))]
    ins, outs, _ = block_io(ops)
    assert ins == []                      # first touch happens in-block
    assert outs == [dst.uid]


def test_contracted_requires_new_and_del():
    src, tmp, out = _base(8, "src"), _base(8, "tmp"), _base(8, "out")
    vs, vt, vo = (View.contiguous(b, (8,)) for b in (src, tmp, out))
    ops = [Op("mul", vt, (vs, 2.0), new_bases=frozenset({tmp})),
           Op("add", vo, (vt, vs), new_bases=frozenset({out})),
           Op("del", None, del_bases=frozenset({tmp}))]
    ins, outs, contracted = block_io(ops)
    assert ins == [src.uid]
    assert outs == [out.uid]
    assert contracted == [tmp.uid]


def test_del_sync_rule_keeps_synced_base_observable():
    src, tmp = _base(8, "src"), _base(8, "tmp")
    vs, vt = View.contiguous(src, (8,)), View.contiguous(tmp, (8,))
    ops = [Op("mul", vt, (vs, 2.0), new_bases=frozenset({tmp})),
           Op("sync", None, sync_bases=frozenset({tmp})),
           Op("del", None, del_bases=frozenset({tmp}))]
    assert block_dead_bases(ops) == set()          # SYNC beats DEL
    ins, outs, contracted = block_io(ops)
    assert outs == [tmp.uid]                       # still materialized
    assert contracted == []
    ops_nosync = [ops[0], ops[2]]
    assert block_dead_bases(ops_nosync) == {tmp.uid}
    _, outs, contracted = block_io(ops_nosync)
    assert outs == [] and contracted == [tmp.uid]


def test_donation_analysis_respects_del_sync():
    """The scheduler's donatable set is derived from block_dead_bases: a
    SYNC'd base must never be donated (the host still observes it)."""
    from repro.core.scheduler import plan_blocks
    src, tmp = _base(8, "src"), _base(8, "tmp")
    vs, vt = View.contiguous(src, (8,)), View.contiguous(tmp, (8,))
    tape = [Op("mul", vt, (vs, 2.0), new_bases=frozenset({tmp})),
            Op("add", vt, (vt, vs)),
            Op("sync", None, sync_bases=frozenset({tmp})),
            Op("del", None, del_bases=frozenset({src, tmp}))]
    (plan,) = plan_blocks(tape, [[0, 1, 2, 3]])
    donated = {plan.inputs[k] for k in plan.donatable}
    assert donated == {src.uid}                    # src dies; tmp is SYNC'd


# ---------------------------------------------------------------------------
# _slice_plan fast path
# ---------------------------------------------------------------------------

def _np_view(base_np, view):
    """NumPy oracle: materialize a View against a flat numpy base."""
    idx = _view_index(view)
    if idx is None:
        return base_np.reshape(view.shape)
    return base_np[idx].reshape(view.shape)


FAST_VIEWS = [
    # (base size, offset, shape, strides) — all single-slice expressible
    (24, 0, (24,), (1,)),            # whole base
    (24, 3, (10,), (1,)),            # offset contiguous run
    (24, 1, (10,), (2,)),            # strided 1-D subsample
    (24, 5, (1,), (1,)),             # single element
    (36, 6, (4, 3), (6, 1)),         # inner-dim window of a (6,6) parent
    (36, 7, (4, 4), (6, 1)),         # shifted stencil window
    (48, 0, (4, 2), (12, 3)),        # strided in both dims
    (36, 0, (6, 1, 6), (6, 6, 1)),   # size-1 dim with arbitrary stride
]

#: a (1, s, h, d) = (1, 8, 4, 16) attention-head base: the LM's q/k/v
HEAD = (1, 8, 4, 16)


def _axes_view(b, order, offset=0, shape=None):
    """The view of the row-major ``HEAD``-shaped base ``b`` whose axes are
    the base's in ``order`` (``x.transpose(order)``), optionally cut to
    ``shape`` at ``offset``."""
    strides = View.contiguous(b, HEAD).strides
    full = tuple(HEAD[i] for i in order)
    return View(b, offset, shape or full, tuple(strides[i] for i in order))


PERMUTED_VIEWS = [
    # (base size, offset, shape, strides, also written) — a reordering of
    # the view's axes by stride is one slice; stride-0 axes broadcast
    (24, 0, (4, 6), (1, 4), True),             # 2-D transpose
    (24, 0, (3, 24), (0, 1), False),           # broadcast (stride 0)
    (24, 0, (6, 3, 4), (1, 0, 6), False),      # transpose with a broadcast
    (24, 2, (2, 5, 3), (12, 0, 1), False),     # mid-axis broadcast, offset
    (24, 0, (6, 1, 4), (1, 0, 6), True),       # size-1 axis of stride 0
    (512, 0, (1, 4, 8, 16), (512, 16, 64, 1), True),   # q.transpose(0,2,1,3)
    (512, 0, (1, 4, 16, 8), (512, 16, 1, 64), True),   # k.transpose(0,2,3,1)
    (512, 8, (1, 4, 8, 8), (512, 16, 64, 1), True),    # half of each head
]

GATHER_VIEWS = [
    (24, 23, (24,), (-1,)),          # reversed
    (16, 0, (4, 4), (2, 1)),         # overlapping rows (stride < width)
]


@pytest.fixture
def no_gather(monkeypatch):
    """Make the executor's gather path raise (the tests' oracle keeps its
    own reference to ``_view_index``)."""
    import repro.core.executor as ex

    def boom(v):
        raise AssertionError(f"gather path hit for {v}")
    monkeypatch.setattr(ex, "_view_index", boom)


def _check_read(v, size):
    base_np = np.arange(size, dtype=np.float64)
    np.testing.assert_array_equal(
        np.asarray(_read(jnp.asarray(base_np), v)), _np_view(base_np, v))


def _check_write(v, size):
    base_np = np.arange(size, dtype=np.float64)
    val = -1.0 - np.arange(v.size, dtype=np.float64).reshape(v.shape)
    got = np.asarray(_write(jnp.asarray(base_np), v, jnp.asarray(val)))
    want = base_np.copy()
    want[_view_index(v)] = val.reshape(-1)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("size,off,shape,strides", FAST_VIEWS)
def test_slice_plan_read_write_match_numpy(no_gather, size, off, shape,
                                           strides):
    v = View(_base(size), off, shape, strides)
    assert _slice_plan(v) is not None
    assert _view_lowering(v)[0] in ("stored", "whole", "slice")
    assert _view_lowering(v, write=True)[0] == _view_lowering(v)[0]
    _check_read(v, size)
    _check_write(v, size)


@pytest.mark.parametrize("mode", ["read", "write"])
@pytest.mark.parametrize("size,off,shape,strides,writes", PERMUTED_VIEWS)
def test_permuted_views_lower_without_gather(no_gather, size, off, shape,
                                             strides, writes, mode):
    v = View(_base(size), off, shape, strides)
    assert _slice_plan(v) is None
    if mode == "read":
        assert _view_lowering(v)[0] == "permute"
        _check_read(v, size)
    elif writes:
        assert _view_lowering(v, write=True)[0] == "permute"
        _check_write(v, size)
    else:                            # a write through a broadcast gathers
        assert _view_lowering(v, write=True)[0] == "gather"


@pytest.mark.parametrize("order", list(itertools.permutations(range(4))))
def test_every_axis_order_of_a_head_view_reads_and_writes(no_gather, order):
    b = _base(int(np.prod(HEAD)))
    v = _axes_view(b, order)
    assert _view_lowering(v)[0] in ("whole", "permute")
    _check_read(v, b.size)
    _check_write(v, b.size)


def test_transposed_head_view_read_has_no_gather_in_its_jaxpr():
    import jax
    b = _base(int(np.prod(HEAD)))
    for order in ((0, 2, 1, 3), (0, 2, 3, 1)):
        v = _axes_view(b, order)
        jaxpr = jax.make_jaxpr(lambda x: _read(x, v))(jnp.zeros(b.size))
        prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
        assert "gather" not in prims and "transpose" in prims, prims


@pytest.mark.parametrize("size,off,shape,strides", GATHER_VIEWS)
def test_gather_views_fall_back_and_stay_correct(size, off, shape, strides):
    b = _base(size)
    v = View(b, off, shape, strides)
    assert _slice_plan(v) is None
    assert _view_lowering(v)[0] == _view_lowering(v, write=True)[0] == "gather"
    base_np = np.arange(size, dtype=np.float64)
    np.testing.assert_array_equal(
        np.asarray(_read(jnp.asarray(base_np), v)), _np_view(base_np, v))


def test_fast_path_emits_no_gather_constants(monkeypatch):
    """The satellite's point: sliceable views must not reach the index-
    gather path at all (no O(size) int32 constants in the jaxpr)."""
    import repro.core.executor as ex

    def boom(v):
        raise AssertionError(f"gather path hit for {v}")

    b = _base(36)
    v = View(b, 7, (4, 4), (6, 1))
    buf = jnp.arange(36.0)
    monkeypatch.setattr(ex, "_view_index", boom)
    _read(buf, v)                               # must use the slice plan
    _write(buf, v, jnp.zeros((4, 4)))
    _read(buf, View(b, 0, (6, 6), (1, 6)))      # a transpose permutes
    with pytest.raises(AssertionError):
        _read(buf, View(b, 0, (4, 6), (3, 1)))  # overlapping rows gather


# ---------------------------------------------------------------------------
# store buffers of any base shape (ir.BaseArray.shape)
# ---------------------------------------------------------------------------

#: 24-element bases: flat, 2-D, 3-D, and 3-D with a size-1 axis
BASE_SHAPES = [(24,), (4, 6), (2, 3, 4), (2, 1, 12)]
KINDS = ["stored", "whole", "slice", "permute", "gather"]
NO_SALTS = jnp.zeros((0,), jnp.int32)


def _view_of_kind(b, kind):
    """A view of the 24-element base ``b`` that ``_view_lowering`` lowers
    as ``kind``, for reads and writes alike."""
    if kind == "stored":
        return View.contiguous(b, b.shape)
    if kind == "whole":
        return View.contiguous(b, (4, 6) if len(b.shape) == 1 else (24,))
    if kind == "slice":
        return View(b, 2, (3, 4), (6, 1))      # a window of (4, 6) rows
    if kind == "permute":
        return View(b, 0, (6, 4), (1, 6))      # (4, 6) transposed
    return View(b, 23, (24,), (-1,))           # reversed


def _flat_read(flat, v):
    """NumPy's flat semantics of a view: its elements by flat index."""
    idx = _view_index(v)
    return (flat if idx is None else flat[idx]).reshape(v.shape)


@pytest.mark.parametrize("shape", BASE_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("mode", ["read", "write"])
@pytest.mark.parametrize("kind", KINDS)
def test_view_lowering_on_a_store_buffer_of_its_base_shape(kind, mode,
                                                           shape):
    """Each lowering, read and written, on a buffer of the base's shape:
    values by NumPy's flat semantics; a block's result, a read-modify-write
    included, leaves in the base's shape."""
    b = BaseArray(24, np.dtype(np.float64), shape=shape)
    v = _view_of_kind(b, kind)
    assert _view_lowering(v, write=mode == "write")[0] == kind
    flat = np.arange(24, dtype=np.float64)
    buf = jnp.asarray(flat.reshape(shape))
    want_read = _flat_read(flat, v)
    if mode == "read":
        np.testing.assert_array_equal(np.asarray(_read(buf, v)), want_read)
        out = BaseArray(v.size, np.dtype(np.float64))
        ops = [Op("copy", View.contiguous(out, v.shape), (v,),
                  new_bases=frozenset({out}))]
        fn, ins, outs = make_block_fn(ops)
        assert (ins, outs) == ([b.uid], [out.uid])
        (got,) = fn(buf, NO_SALTS)
        assert got.shape == (v.size,)
        np.testing.assert_array_equal(np.asarray(got).reshape(v.shape),
                                      want_read)
        return
    val = -1.0 - np.arange(v.size, dtype=np.float64).reshape(v.shape)
    got = _write(buf, v, jnp.asarray(val))
    want = flat.copy()
    idx = _view_index(v)
    want[slice(None) if idx is None else idx] = val.reshape(-1)
    assert got.shape == shape
    np.testing.assert_array_equal(np.asarray(got).reshape(-1), want)
    # the same view read, negated and written back inside one block
    ops = [Op("neg", v, (v,))]
    fn, ins, outs = make_block_fn(ops)
    assert ins == outs == [b.uid]
    (got,) = fn(buf, NO_SALTS)
    want = flat.copy()
    want[slice(None) if idx is None else idx] = -want_read.reshape(-1)
    assert got.shape == shape
    np.testing.assert_array_equal(np.asarray(got).reshape(-1), want)


def test_whole_adopted_weight_is_read_with_no_reshape():
    """A block reading a whole adopted 2-D weight takes its buffer as it
    is: no ``reshape`` of that input in the block's jaxpr, and the block's
    span counts the weight's bytes as ``stored_bytes``."""
    import jax

    from repro.core import lazy as bh
    from repro.core.lazy import fresh_runtime
    from repro.core.obs import trace

    w_np = np.arange(8 * 16, dtype=np.float32).reshape(8, 16) / 64.0
    x_np = np.arange(4 * 8, dtype=np.float32).reshape(4, 8) / 32.0
    wb = BaseArray(w_np.size, w_np.dtype, shape=w_np.shape)
    xb = BaseArray(x_np.size, x_np.dtype)              # an op-made base
    ob = BaseArray(4 * 16, w_np.dtype)
    ops = [Op("matmul", View.contiguous(ob, (4, 16)),
              (View.contiguous(xb, (4, 8)), View.contiguous(wb, (8, 16))),
              new_bases=frozenset({ob}))]
    fn, ins, _ = make_block_fn(ops)
    assert ins == [xb.uid, wb.uid]
    assert fn.view_lowerings["stored"] == 1
    assert fn.stored_bytes == w_np.nbytes
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros((x_np.size,), jnp.float32),
                               jnp.asarray(w_np), NO_SALTS).jaxpr
    w_in = jaxpr.invars[1]
    reshaped = [e for e in jaxpr.eqns if e.primitive.name == "reshape"]
    assert reshaped                                   # x is reshaped
    assert all(w_in not in e.invars for e in reshaped)

    tr = trace.enable()
    try:
        with fresh_runtime(loop_fusion=False):
            w = bh.asarray(w_np)
            x = bh.ones((4, 8), np.float32) * 0.5
            got = bh.matmul(x, w).numpy()
    finally:
        trace.disable()
    np.testing.assert_allclose(got, (np.ones((4, 8), np.float32) * 0.5)
                               @ w_np, rtol=1e-6)
    blocks = [ev["args"] for ev in tr.events
              if ev.get("ph") == "X" and ev["name"] == "block"]
    assert blocks and all("stored_bytes" in a for a in blocks)
    assert sum(a["stored_bytes"] for a in blocks) == w_np.nbytes


def test_stencil_program_uses_fast_path_end_to_end():
    """heat-equation-style RMW through the full runtime stays exact."""
    from repro.core import lazy as bh
    from repro.core.lazy import fresh_runtime
    n = 16
    with fresh_runtime():
        g = bh.asarray(np.arange(n * n, dtype=np.float64).reshape(n, n))
        inner = (g[1:-1, :-2] + g[1:-1, 2:] + g[:-2, 1:-1] + g[2:, 1:-1]) * 0.25
        g[1:n - 1, 1:n - 1] = inner
        got = g.numpy()
    want = np.arange(n * n, dtype=np.float64).reshape(n, n)
    w = (want[1:-1, :-2] + want[1:-1, 2:] + want[:-2, 1:-1] + want[2:, 1:-1]) * 0.25
    want[1:n - 1, 1:n - 1] = w
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# persistent compilation cache placement (executor.use_compile_cache, called
# by every dispatch path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["env", "tpu", "cpu"])
def test_compile_cache_dir(tmp_path, case):
    """``JAX_COMPILATION_CACHE_DIR`` set: compiled entries land there and
    the runtime sets no other directory.  Unset: on a TPU the cache goes to
    the checkout's ``.jax_cache``; on the CPU it stays off."""
    import os
    import subprocess
    import sys
    import textwrap

    from repro.core.executor import CHECKOUT_CACHE_DIR
    code = textwrap.dedent(f"""
        import jax, numpy as np
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        if {case == "tpu"}:
            jax.default_backend = lambda: "tpu"     # steer the platform test
            from repro.core.executor import use_compile_cache
            use_compile_cache()
        else:
            from repro.core import lazy as bh
            with bh.fresh_runtime():
                x = bh.asarray(np.arange(64.0))
                (x * 3.0 + 1.0).numpy()
        print(jax.config.jax_compilation_cache_dir)
    """)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    want = tmp_path / "jax_cache"
    if case == "env":
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = out.stdout.strip().splitlines()[-1]
    assert got == {"env": str(want), "tpu": str(CHECKOUT_CACHE_DIR),
                   "cpu": "None"}[case]
    if case == "env":
        assert any(want.iterdir()), "no compiled entry in the env cache dir"
