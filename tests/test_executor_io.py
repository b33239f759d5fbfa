"""Direct unit tests for executor block-IO semantics and the strided-view
slice fast path (ISSUE 2 satellites):

* ``block_io`` read-modify-write classification: a partial write of a
  pre-existing base makes the base a block INPUT; a full overwrite does not;
* the del−sync rule (``block_dead_bases``): SYNC'd bases stay observable —
  they are never donated, contracted, or dropped from outputs;
* ``_slice_plan`` lowers single-slice regularly-strided views to static
  reshape+slice (no O(size) gather-index constants in block jaxprs), with
  exact read/write equivalence against NumPy's own striding.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import (_read, _slice_plan, _view_index, _write,
                                 block_dead_bases, block_io)
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

GATHER_VIEWS = [
    (24, 0, (4, 6), (1, 4)),         # transpose
    (24, 0, (3, 24), (0, 1)),        # broadcast (stride 0)
    (24, 23, (24,), (-1,)),          # reversed
    (16, 0, (4, 4), (2, 1)),         # overlapping rows (stride < width)
]


@pytest.mark.parametrize("size,off,shape,strides", FAST_VIEWS)
def test_slice_plan_read_write_match_numpy(size, off, shape, strides):
    b = _base(size)
    v = View(b, off, shape, strides)
    assert _slice_plan(v) is not None
    base_np = np.arange(size, dtype=np.float64)
    buf = jnp.asarray(base_np)
    np.testing.assert_array_equal(np.asarray(_read(buf, v)), _np_view(base_np, v))
    val = np.full(shape, -1.0)
    got = np.asarray(_write(buf, v, jnp.asarray(val)))
    want = base_np.copy()
    want[_view_index(v) if _view_index(v) is not None
         else slice(None)] = val.reshape(-1)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("size,off,shape,strides", GATHER_VIEWS)
def test_gather_views_fall_back_and_stay_correct(size, off, shape, strides):
    b = _base(size)
    v = View(b, off, shape, strides)
    assert _slice_plan(v) is None
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
    with pytest.raises(AssertionError):
        _read(buf, View(b, 0, (6, 6), (1, 6)))  # transpose needs gather


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
