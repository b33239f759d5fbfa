"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracle,
swept over shapes and dtypes — the fused-block codegen directly, and the
LM blocks (masked-softmax attention, add+rmsnorm, a scan update) recorded
on the lazy runtime's ``lm`` stack, where the ``rowblock`` backend lowers
them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ir import BaseArray, Op, View
from repro.kernels.fused_block.codegen import (FusedBlockUnsupported,
                                               build_block_kernel)
from repro.kernels.fused_block.ref import reference_block
from repro.kernels.flash_attention.ref import reference_attention
from repro.kernels.rmsnorm.ref import reference_add_rmsnorm


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _saltless_kernel(ops, *, interpret=None):
    """``build_block_kernel`` for a block without ``random`` ops: the
    returned function takes the block's input buffers alone."""
    fn, ins, outs = build_block_kernel(ops, interpret=interpret)
    empty = jnp.zeros((0,), jnp.int32)
    return (lambda *bufs: fn(*bufs, empty)), ins, outs


# ---------------------------------------------------------------------------
# fused_block — the paper's kernel: build a synthetic WSP block and compare.
# ---------------------------------------------------------------------------

def _make_block(n, dtype):
    """(a*b + sqrt(|c|)) with two contracted temporaries."""
    mk = lambda name: BaseArray(n, np.dtype(dtype), name=name)   # noqa: E731
    a, b, c, t1, t2, out = (mk(x) for x in
                            ["a", "b", "c", "t1", "t2", "out"])
    va, vb, vc = (View.contiguous(x, (n,)) for x in (a, b, c))
    vt1, vt2, vo = (View.contiguous(x, (n,)) for x in (t1, t2, out))
    ops = [
        Op("mul", vt1, (va, vb), new_bases=frozenset({t1})),
        Op("abs", vt2, (vc,), new_bases=frozenset({t2})),
        Op("sqrt", vt2, (vt2,)),
        Op("add", vo, (vt1, vt2), new_bases=frozenset({out})),
        Op("del", None, del_bases=frozenset({t1})),
        Op("del", None, del_bases=frozenset({t2})),
    ]
    return ops


@pytest.mark.parametrize("n", [8, 100, 1024, 5000])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_fused_block_matches_ref(n, dtype):
    ops = _make_block(n, dtype)
    fn, ins, outs = _saltless_kernel(ops, interpret=True)
    key = jax.random.PRNGKey(0)
    bufs = [jax.random.normal(jax.random.fold_in(key, i), (n,),
                              jnp.float32).astype(dtype) for i in range(len(ins))]
    got = fn(*bufs)
    want = reference_block(ops, *bufs)
    assert len(got) == len(want) == 1
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)


def test_fused_block_contracts_temporaries():
    ops = _make_block(64, np.float32)
    fn, ins, outs = _saltless_kernel(ops, interpret=True)
    assert len(ins) == 3 and len(outs) == 1   # t1, t2 contracted


def test_fused_block_lowers_strided():
    """ISSUE 3: regularly-strided views lower through the codegen (the old
    flat tiler rejected them) and match the XLA slice semantics exactly."""
    n = 32
    a = BaseArray(n, np.dtype(np.float32))
    o = BaseArray(n, np.dtype(np.float32))
    va = View(a, 0, (n // 2,), (2,))          # strided view
    vo = View.contiguous(o, (n // 2,))
    ops = [Op("copy", vo, (va,), new_bases=frozenset({o}))]
    fn, ins, outs = _saltless_kernel(ops)
    buf = jnp.arange(n, dtype=jnp.float32)
    (got,) = fn(buf)
    np.testing.assert_array_equal(np.asarray(got)[:n // 2],
                                  np.asarray(buf)[::2])


def test_fused_block_rejects_gather_shaped():
    """Reversed (negative-stride) views have no slice plan — the one view
    class that still needs a gather and falls back."""
    n = 32
    a = BaseArray(n, np.dtype(np.float32))
    o = BaseArray(n, np.dtype(np.float32))
    va = View(a, n - 1, (n,), (-1,))          # reversed view
    vo = View.contiguous(o, (n,))
    ops = [Op("copy", vo, (va,), new_bases=frozenset({o}))]
    with pytest.raises(FusedBlockUnsupported) as ei:
        _saltless_kernel(ops)
    assert ei.value.reason == "irregular_view"


# Differential sweep across the 1024-element tile boundary: sizes that are
# NOT multiples of the flat tile (nor of the 128 lane) pin the pad/slice
# logic, and integer dtypes pin the astype on the store path.
TILE_EDGE_SIZES = [1, 7, 127, 129, 1000, 1023, 1025, 2061]


@pytest.mark.parametrize("n", TILE_EDGE_SIZES)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_fused_block_tile_boundary_sweep(n, dtype):
    ops = _make_block(n, dtype)
    fn, ins, outs = _saltless_kernel(ops, interpret=True)
    key = jax.random.PRNGKey(n)
    bufs = [jax.random.normal(jax.random.fold_in(key, i), (n,),
                              jnp.float32).astype(dtype)
            for i in range(len(ins))]
    got = fn(*bufs)
    want = reference_block(ops, *bufs)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def _make_int_block(n, dtype):
    """where(a > b, a*b, a+b) — integer-safe ops only."""
    mk = lambda name: BaseArray(n, np.dtype(dtype), name=name)   # noqa: E731
    a, b, t1, t2, t3, out = (mk(x) for x in "abcdef")
    va, vb = View.contiguous(a, (n,)), View.contiguous(b, (n,))
    vt1, vt2 = View.contiguous(t1, (n,)), View.contiguous(t2, (n,))
    vt3, vo = View.contiguous(t3, (n,)), View.contiguous(out, (n,))
    return [
        Op("greater", vt1, (va, vb), new_bases=frozenset({t1})),
        Op("mul", vt2, (va, vb), new_bases=frozenset({t2})),
        Op("add", vt3, (va, vb), new_bases=frozenset({t3})),
        Op("where", vo, (vt1, vt2, vt3), new_bases=frozenset({out})),
        Op("del", None, del_bases=frozenset({t1})),
        Op("del", None, del_bases=frozenset({t2})),
        Op("del", None, del_bases=frozenset({t3})),
    ]


@pytest.mark.parametrize("n", [7, 1000, 1025])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_fused_block_integer_dtypes(n, dtype):
    ops = _make_int_block(n, dtype)
    fn, ins, outs = _saltless_kernel(ops, interpret=True)
    rng = np.random.default_rng(n)
    bufs = [jnp.asarray(rng.integers(-50, 50, size=n, dtype=dtype))
            for _ in range(len(ins))]
    got = fn(*bufs)
    want = reference_block(ops, *bufs)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_fused_block_fallback_boundary_is_pinned():
    """fused_block_fn must fall back to the XLA path exactly for the blocks
    the codegen cannot express — and the fallback must stay correct.  After
    ISSUE 3, strided views and reductions LOWER; after ISSUE 9, so do 1-D
    axis-0 whole-table gathers (other gather forms keep a pinned slug)."""
    from repro.kernels.fused_block.ops import fused_block_fn
    salts = jnp.zeros((0,), jnp.int32)
    n = 100                                   # not a multiple of the tile
    # same-domain elementwise chain -> Pallas path
    ops = _make_block(n, np.float32)
    fn, ins, outs, reason = fused_block_fn(ops)
    assert reason is None
    # strided view -> now ALSO the Pallas path
    a = BaseArray(n, np.dtype(np.float32))
    o = BaseArray(n, np.dtype(np.float32))
    ops = [Op("copy", View.contiguous(o, (n // 2,)),
              (View(a, 0, (n // 2,), (2,)),), new_bases=frozenset({o}))]
    fn, ins, outs, reason = fused_block_fn(ops)
    assert reason is None
    buf = jnp.arange(n, dtype=jnp.float32)
    (got,) = fn(buf, salts)
    np.testing.assert_array_equal(np.asarray(got)[:n // 2],
                                  np.asarray(buf)[::2])
    # full 1-D reduction -> now the Pallas path (grid-accumulated)
    r = BaseArray(1, np.dtype(np.float32))
    ops = [Op("reduce_sum", View.contiguous(r, ()),
              (View.contiguous(a, (n,)),), axis=0, new_bases=frozenset({r}))]
    fn, ins, outs, reason = fused_block_fn(ops)
    assert reason is None
    (got,) = fn(buf, salts)
    np.testing.assert_allclose(float(np.asarray(got).reshape(())),
                               float(np.sum(np.arange(n))), rtol=1e-6)
    # 1-D axis-0 whole-table gather -> now the Pallas path (ISSUE 9): the
    # table streams in whole via a constant-index-map block and the kernel
    # computes the exact jnp.take of the fallback
    idx = BaseArray(4, np.dtype(np.float32))
    g = BaseArray(4, np.dtype(np.float32))
    ops = [Op("gather", View.contiguous(g, (4,)),
              (View.contiguous(a, (n,)), View.contiguous(idx, (4,))),
              axis=0, new_bases=frozenset({g}))]
    fn, ins, outs, reason = fused_block_fn(ops)
    assert reason is None
    got = fn(buf, jnp.asarray([0., 3., 7., 11.], jnp.float32), salts)
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(buf)[[0, 3, 7, 11]])
    # unsupported gather form (partial table view) -> pinned slug
    ops = [Op("gather", View.contiguous(g, (4,)),
              (View(a, 8, (n // 2,), (1,)), View.contiguous(idx, (4,))),
              axis=0, new_bases=frozenset({g}))]
    fn, ins, outs, reason = fused_block_fn(ops)
    assert reason == "gather_form"
    got = fn(buf, jnp.asarray([0., 3., 7., 11.], jnp.float32), salts)
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(buf)[8:][[0, 3, 7, 11]])


# ---------------------------------------------------------------------------
# LM blocks on the lm stack: the rowblock backend's claim boundary
# ---------------------------------------------------------------------------

#: the row replay and the references sum the same float32 terms in other
#: orders (and the references' einsums in other blocks): a few ulps of the
#: largest term, well inside 2e-5 at these widths
ROW_TOL = dict(rtol=2e-5, atol=2e-5)

#: (sq, sk, hq, hkv, d) — MHA, GQA 2:1, MQA, ragged rows, cross-length
ATTN_SHAPES = [(128, 128, 4, 4, 64), (128, 128, 4, 2, 64),
               (256, 256, 8, 1, 32), (100, 100, 2, 2, 64),
               (64, 256, 2, 1, 128)]
#: (window, softcap, causal) over (1, 4 heads, 256, 64), kv heads 2
ATTN_FEATURES = [(None, None, False), (64, None, True), (None, 30.0, True),
                 (32, 50.0, True)]
#: (batch, steps, width) of a scan update: the mamba scan's (b, t,
#: d_inner * d_state) and the rwkv6 scan's (b * h, t, n), ragged steps too
SCAN_SHAPES = [(2, 64, 256), (1, 128, 1024), (2, 64, 32), (4, 128, 64),
               (1, 96, 64), (1, 100, 64)]


def _lm_runtime():
    from repro.core.lazy import fresh_runtime
    return fresh_runtime(algorithm="greedy", cost_model="bohrium",
                         backend="lm", loop_fusion=False)


def _lazy_attention(rt, q, k, v, *, causal, window=None, softcap=None):
    """Masked-softmax attention recorded as ``LazyTransformer`` records it,
    kv heads repeated to the query heads on the host."""
    from repro.core import lazy as bh
    b, hq, sq, d = q.shape
    sk, group = k.shape[2], hq // k.shape[1]
    k, v = (np.repeat(np.asarray(a), group, axis=1) for a in (k, v))
    q_pos, k_pos = np.arange(sq)[:, None], np.arange(sk)[None, :]
    mask = np.ones((sq, sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    qa, ka, va = (rt.adopt(np.asarray(a)) for a in (q, k, v))
    mask = rt.adopt(mask.reshape(1, 1, sq, sk))
    neg = rt.adopt(np.full((1, 1, 1, 1), -1e30, np.float32))
    sc = bh.matmul(qa, ka.transpose(0, 1, 3, 2)) * float(1.0 / np.sqrt(d))
    if softcap is not None:
        sc = bh.tanh(sc / float(softcap)) * float(softcap)
    shape = (b, hq, sq, sk)
    scm = bh.where(mask.broadcast_to(shape), sc, neg.broadcast_to(shape))
    m = scm.max(axis=-1)
    e = bh.exp(scm - m.reshape(b, hq, sq, 1).broadcast_to(shape))
    z = e.sum(axis=-1)
    p = e / z.reshape(b, hq, sq, 1).broadcast_to(shape)
    return bh.matmul(p, va).numpy()


def _rowblock_stats(rt):
    st = rt.executor.stats
    return (st["backend_blocks"].get("rowblock", 0),
            dict(st["backend_fallbacks"].get("rowblock", {})))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sq,sk,hq,hkv,d", ATTN_SHAPES)
def test_rowblock_attention_shapes(sq, sk, hq, hkv, d, causal):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (2, hq, sq, d), jnp.float32)
    k = _rand(ks[1], (2, hkv, sk, d), jnp.float32)
    v = _rand(ks[2], (2, hkv, sk, d), jnp.float32)
    with _lm_runtime() as rt:
        got = _lazy_attention(rt, q, k, v, causal=causal)
        claimed, _ = _rowblock_stats(rt)
    # the masked max and the shifted exp-sum blocks
    assert claimed == 2
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, np.asarray(want), **ROW_TOL)


@pytest.mark.parametrize("window,softcap,causal", ATTN_FEATURES)
def test_rowblock_attention_features(window, softcap, causal):
    """A sliding window is only another mask.  A softcap puts ``tanh`` in
    the masked-max block, which the row-pattern screen does not admit:
    that block declines with ``no_row_pattern``, the exp-sum block is
    still claimed."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (1, 4, 256, 64), jnp.float32)
    k = _rand(ks[1], (1, 2, 256, 64), jnp.float32)
    v = _rand(ks[2], (1, 2, 256, 64), jnp.float32)
    with _lm_runtime() as rt:
        got = _lazy_attention(rt, q, k, v, causal=causal, window=window,
                              softcap=softcap)
        claimed, declined = _rowblock_stats(rt)
    if softcap is None:
        assert claimed == 2
    else:
        assert claimed == 1 and declined.get("no_row_pattern", 0) >= 1
    want = reference_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap)
    np.testing.assert_allclose(got, np.asarray(want), **ROW_TOL)


@pytest.mark.parametrize("rows,d", [(8, 128), (100, 256), (512, 512)])
@pytest.mark.parametrize("plus_one", [False, True])
def test_rowblock_add_rmsnorm(rows, d, plus_one):
    from repro.core import lazy as bh
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = _rand(ks[0], (rows, d), jnp.float32)
    r = _rand(ks[1], (rows, d), jnp.float32)
    g = _rand(ks[2], (d,), jnp.float32)
    with _lm_runtime() as rt:
        g1 = np.asarray(g) + np.float32(1.0 if plus_one else 0.0)
        h = rt.adopt(np.asarray(x)) + rt.adopt(np.asarray(r))
        var = (h * h).sum(axis=-1)
        inv = bh.rsqrt(var.reshape(rows, 1).broadcast_to((rows, d))
                       / float(d) + 1e-6)
        y = h * inv * rt.adopt(g1).reshape(1, d).broadcast_to((rows, d))
        got_y, got_h = y.numpy(), h.numpy()
        claimed, _ = _rowblock_stats(rt)
    assert claimed == 1                        # the rsqrt scale block
    want_y, want_h = reference_add_rmsnorm(x, r, g, plus_one=plus_one)
    np.testing.assert_allclose(got_y, np.asarray(want_y), **ROW_TOL)
    np.testing.assert_allclose(got_h, np.asarray(want_h), **ROW_TOL)


@pytest.mark.parametrize("b,t,n", SCAN_SHAPES)
def test_rowblock_scan_update(b, t, n):
    """One selective-scan update ``exp(dt*a) * h + gate * u`` with its
    trailing contraction, against numpy."""
    from repro.core import lazy as bh
    rng = np.random.default_rng(b * 1000 + t + n)
    dt_a = -rng.random((b, t, n), dtype=np.float32) - 0.05
    hid, upd, gate = (rng.standard_normal((b, t, n)).astype(np.float32)
                      for _ in range(3))
    with _lm_runtime() as rt:
        gt = rt.adopt(gate)
        h_new = bh.exp(rt.adopt(dt_a)) * rt.adopt(hid) + gt * rt.adopt(upd)
        y = (h_new * gt).sum(axis=-1)
        out = h_new + y.reshape(b, t, 1).broadcast_to((b, t, n))
        got_h, got_out = h_new.numpy(), out.numpy()
        claimed, _ = _rowblock_stats(rt)
    assert claimed >= 1
    h64 = np.exp(dt_a.astype(np.float64)) * hid + gate.astype(np.float64) * upd
    want = h64 + (h64 * gate).sum(axis=-1, keepdims=True)
    np.testing.assert_allclose(got_h, h64, **ROW_TOL)
    np.testing.assert_allclose(got_out, want, **ROW_TOL)


# ---------------------------------------------------------------------------
# fused_block as the runtime executor backend (end-to-end paper path)
# ---------------------------------------------------------------------------

def test_pallas_backend_end_to_end():
    """backend='pallas' must route fusible blocks through the Pallas kernel
    (interpret mode) and produce identical results to the XLA path."""
    from repro.core import lazy as bh
    from repro.core.lazy import fresh_runtime
    results = {}
    stats = {}
    for backend in ("xla", "pallas"):
        with fresh_runtime(algorithm="greedy", backend=backend) as rt:
            a = bh.full(2048, 1.5)
            b_ = bh.full(2048, -0.5)
            t = a * b_ + 2.0
            u = bh.sqrt(bh.absolute(t)) * 0.1
            t.delete()
            results[backend] = u.numpy()
            stats[backend] = dict(rt.executor.stats)
    np.testing.assert_allclose(results["pallas"], results["xla"],
                               rtol=1e-6, atol=1e-6)
    assert stats["pallas"]["pallas_blocks"] >= 1


@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (4, 1)])
def test_dense_attn_matches_chunked(hq, hkv):
    """The two XLA attention paths must agree (GQA head-mapping identical)."""
    from repro.models.layers import _dense_attn, _chunked_attn
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 3)
    q = _rand(ks[0], (2, 96, hq, 32), jnp.float32)
    k = _rand(ks[1], (2, 96, hkv, 32), jnp.float32)
    v = _rand(ks[2], (2, 96, hkv, 32), jnp.float32)
    a = _dense_attn(q, k, v, causal=True, window=None, softcap=None,
                    scale=0.2)
    b_ = _chunked_attn(q, k, v, causal=True, window=None, softcap=None,
                       scale=0.2, chunk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                               rtol=2e-5, atol=2e-5)
