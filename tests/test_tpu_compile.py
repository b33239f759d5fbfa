"""Main-path kernels compiled for a described TPU v5e, without the chip.

The TPU compiler is installed with jaxlib: ``get_topology_desc`` describes
a ``v5e:2x2`` slice, and ``jit(...).lower(...).compile()`` against one of
its devices raises exactly what Mosaic would raise on the chip.  Interpret
mode (every other test) cannot see the (8, 128) block rule, 64-bit element
types, primitives with no Pallas TPU lowering, or the VMEM limit; these
tests do, at real widths, with nothing executed.  Each compiled case must
contain a ``tpu_custom_call`` (the Pallas kernel survived lowering).

The topology is described inside a module fixture, never at import: only
the test process that is given this file loads the TPU library.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lazy as bh
from repro.core.backends import (LM_STACK, LoweringContext, LoweringPolicy,
                                 get_backend)
from repro.core.cache import MergeCache
from repro.core.executor import _BINARY, _UNARY
from repro.core.ir import BaseArray, Op, View
from repro.core.lazy import LazyArray, fresh_runtime
from repro.core.scheduler import Scheduler
from repro.kernels.fused_block.codegen import (MOSAIC_OPCODES, REASONS,
                                               block_lower_reason,
                                               mosaic_reason)

F32 = np.dtype(np.float32)
COMPILED = LoweringContext(interpret=False)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the persistent
    # cache (no chip to load them on): keep it out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# helpers: trace at real size (nothing runs), plan, build, compile
# ---------------------------------------------------------------------------

def _input(rt, shape, dtype=F32) -> LazyArray:
    """A lazy input of ``shape`` with no data behind it."""
    base = BaseArray(math.prod(shape), dtype)
    rt.buffers[base.uid] = None
    return LazyArray(rt, View.contiguous(base, tuple(shape)))


def _traced(record):
    """The tape ``record(rt)`` traces, captured before any flush."""
    with fresh_runtime(loop_fusion=False) as rt:
        keep = record(rt)
        tape = list(rt.tape)
        rt.tape.clear()
        for a in keep:
            a._alive = False
    return tape


def _blocks(tape, backends):
    """(backend, ops, plan) of every work block the lower stage gives a
    non-XLA backend, planned as a compiled (TPU) executor would."""
    sched = Scheduler(MergeCache()).plan(
        tape, algorithm="greedy", cost_model="bohrium", use_cache=False,
        lowering=LoweringPolicy(tuple(backends), COMPILED))
    out = []
    for plan in sched.blocks:
        if plan.has_work and plan.lowering.backend != "xla":
            out.append((plan.lowering.backend,
                        [tape[i] for i in plan.op_indices], plan))
    return out


def _compile(one_chip, backend, ops, plan=None, custom=True):
    """AOT-compile one block's executable for the described chip; with
    ``custom``, it must hold a kernel (a ``tpu_custom_call``)."""
    if plan is None:
        from repro.core.scheduler import plan_blocks
        plan = plan_blocks(ops, [list(range(len(ops)))])[0]
    fn = get_backend(backend).build(ops, plan, COMPILED)
    meta = {}
    for op in ops:
        for v in (*op.in_views(), *op.out_views()):
            meta[v.base.uid] = (v.base.size, v.base.dtype)
    n_rand = sum(op.opcode == "random" for op in ops)
    args = [jax.ShapeDtypeStruct((meta[u][0],), meta[u][1], sharding=one_chip)
            for u in plan.inputs]
    args.append(jax.ShapeDtypeStruct((n_rand,), jnp.int32, sharding=one_chip))
    compiled = jax.jit(fn, out_shardings=one_chip).lower(*args).compile()
    assert not custom or "tpu_custom_call" in compiled.as_text()
    return compiled


def _v(base, shape):
    return View.contiguous(base, shape)


# ---------------------------------------------------------------------------
# the runtime's main-path blocks at real widths
# ---------------------------------------------------------------------------

def test_stencil_block_1026(one_chip):
    """The heat-equation sweep: four shifted windows and a window write."""
    n = 1026

    def record(rt):
        g = _input(rt, (n, n))
        inner = (g[1:-1, :-2] + g[1:-1, 2:] + g[:-2, 1:-1]
                 + g[2:, 1:-1]) * 0.25
        g[1:n - 1, 1:n - 1] = inner
        inner.delete()
        return [g]

    blocks = _blocks(_traced(record), ("pallas", "xla"))
    assert blocks
    for block in blocks:
        _compile(one_chip, *block)


def test_elementwise_chain_3375000(one_chip):
    """A lattice-Boltzmann-sized (150^3) elementwise chain in one kernel."""
    def record(rt):
        x = _input(rt, (150, 150, 150))
        y = bh.exp(x * 0.5 - 1.0) * x + bh.sqrt(bh.absolute(x))
        return [x, y]

    blocks = _blocks(_traced(record), ("pallas", "xla"))
    assert [b for b, _, _ in blocks] == ["pallas"]
    compiled = _compile(one_chip, *blocks[0])
    # a misaligned minor dim (150) runs on the flat domain in lane-dense
    # rows, so XLA needs no relayout temporary around the kernel
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("axis", [0, 1])
def test_reduction_4096x4096(one_chip, axis):
    a, o = BaseArray(4096 * 4096, F32), BaseArray(4096, F32)
    ops = [Op("reduce_sum", _v(o, (4096,)), (_v(a, (4096, 4096)),),
              axis=axis)]
    assert block_lower_reason(ops) is None
    _compile(one_chip, "pallas", ops)


def test_rmsnorm_rowblock_8192x2560(one_chip):
    """The residual + rmsnorm scale chain of a d_model-2560 model over 8192
    tokens, lowered by the rmsnorm claimant's row-replay kernel."""
    b, s, d = 4, 2048, 2560

    def record(rt):
        x = _input(rt, (b, s, d))
        g1 = _input(rt, (d,))
        var = (x * x).sum(axis=-1)
        inv = bh.rsqrt(var.reshape(b, s, 1).broadcast_to((b, s, d))
                       / float(d) + 1e-6)
        y = x * inv * g1.reshape(1, 1, d).broadcast_to((b, s, d))
        return [x, g1, y]

    blocks = _blocks(_traced(record), LM_STACK)
    assert "rmsnorm" in [name for name, _, _ in blocks]
    for name, ops, plan in blocks:
        _compile(one_chip, name, ops, plan)


def test_softmax_rowblock_40960x512(one_chip):
    """The masked softmax of 2 x 40 heads x 512 queries x 512 keys."""
    shape = (2, 40, 512, 512)

    def record(rt):
        sc = _input(rt, shape)
        mask = _input(rt, (1, 1, 512, 512), np.bool_)
        neg = _input(rt, (1, 1, 1, 1))
        scm = bh.where(mask.broadcast_to(shape), sc, neg.broadcast_to(shape))
        m = scm.max(axis=-1)
        e = bh.exp(scm - m.reshape(2, 40, 512, 1).broadcast_to(shape))
        z = e.sum(axis=-1)
        p = e / z.reshape(2, 40, 512, 1).broadcast_to(shape)
        return [sc, mask, neg, p]

    blocks = _blocks(_traced(record), LM_STACK)
    assert "flash_attention" in [name for name, _, _ in blocks]
    for name, ops, plan in blocks:
        _compile(one_chip, name, ops, plan)


def test_vmem_budget_edge(one_chip):
    """An 8-row slab set that fits the double-buffered budget compiles; a
    vocabulary-wide row (151936 lanes) does not fit and is declined with
    ``vmem`` instead of reaching the compiler."""
    def softmax_rows(r, c):
        x, m, o = BaseArray(r * c, F32), BaseArray(r, F32), BaseArray(r * c, F32)
        xv = _v(x, (r, c))
        mb = View(m, 0, (r, c), (1, 0))
        return [Op("reduce_max", _v(m, (r,)), (xv,), axis=1),
                Op("sub", _v(o, (r, c)), (xv, mb))]

    fits = softmax_rows(64, 32768)
    from repro.kernels.fused_block.rowblock import rowblock_lower_reason
    assert rowblock_lower_reason(fits) is None
    _compile(one_chip, "flash_attention", fits)
    assert rowblock_lower_reason(softmax_rows(64, 151936)) == "vmem"


def test_routing_blocks_at_deepseek_v2_lite_widths(one_chip):
    """The expert layer's opaque blocks of a 2048-token prefill: the top-6
    sort of 2048 x 64 router scores, the sort of the 12288 assignments, and
    the grouped product of their rows with 16 held experts of 2048 x 1408,
    declined by every kernel claimant and compiled by XLA (the product to
    the TPU's ``ragged-dot`` kernel)."""
    n, e, a, d, g, f = 2048, 64, 12288, 2048, 16, 1408
    s, o = BaseArray(n * e, F32), BaseArray(n * e, F32)
    k, p = BaseArray(a, F32), BaseArray(a, F32)
    x, w = BaseArray(a * d, F32), BaseArray(g * d * f, F32)
    sizes, y = BaseArray(g, F32), BaseArray(a * f, F32)
    blocks = [
        [Op("argsort", _v(o, (n, e)), (_v(s, (n, e)),), axis=1)],
        [Op("argsort", _v(p, (a,)), (_v(k, (a,)),), axis=0)],
        [Op("ragged_matmul", _v(y, (a, f)),
            (_v(x, (a, d)), _v(w, (g, d, f)), _v(sizes, (g,))))]]
    for ops in blocks:
        for name in LM_STACK[:-1]:
            assert get_backend(name).claims(ops, None, COMPILED) is not None
    with jax.default_matmul_precision("highest"):
        texts = [_compile(one_chip, "xla", ops, custom=False).as_text()
                 for ops in blocks]
    assert "ragged-dot" in texts[2] and "tpu_custom_call" in texts[2]


# ---------------------------------------------------------------------------
# what Mosaic cannot compile is declined, never handed to the compiler
# ---------------------------------------------------------------------------

def test_float64_gather_and_unlowerable_opcodes_declined():
    n = 1024
    a64, o64 = BaseArray(n, np.float64), BaseArray(n, np.float64)
    x64 = [Op("mul", _v(o64, (n,)), (_v(a64, (n,)), 2.0))]
    tbl, idx, out = BaseArray(n, F32), BaseArray(n, F32), BaseArray(n, F32)
    gather = [Op("gather", _v(out, (n,)), (_v(tbl, (n,)), _v(idx, (n,))),
                 axis=0)]
    a, o = BaseArray(n, F32), BaseArray(n, F32)
    erf = [Op("erf", _v(o, (n,)), (_v(a, (n,)),))]
    assert {"mosaic_x64", "mosaic_gather", "mosaic_opcode"} <= set(REASONS)
    pallas = get_backend("pallas")
    for ops, slug in ((x64, "mosaic_x64"), (gather, "mosaic_gather"),
                      (erf, "mosaic_opcode")):
        assert block_lower_reason(ops) is None      # the interpreter runs it
        assert mosaic_reason(ops) == slug
        assert pallas.claims(ops, None, COMPILED) == slug
        assert pallas.claims(ops, None, LoweringContext(interpret=True)) \
            is None
    # the row-replay claimants apply the same screen
    r, c = 8, 128
    x, m, o = BaseArray(r * c, np.float64), BaseArray(r, np.float64), \
        BaseArray(r * c, np.float64)
    soft64 = [Op("reduce_max", _v(m, (r,)), (_v(x, (r, c)),), axis=1),
              Op("sub", _v(o, (r, c)), (_v(x, (r, c)), View(m, 0, (r, c),
                                                             (1, 0))))]
    assert get_backend("flash_attention").claims(soft64, None, COMPILED) \
        in ("mosaic_x64", "no_softmax")


OPCODES = sorted(set(_UNARY) - MOSAIC_OPCODES) \
    + sorted(set(_BINARY) - MOSAIC_OPCODES) \
    + ["where", "range", "random", "copy_literal", "reduce_sum",
       "reduce_max", "reduce_min"]


@pytest.mark.parametrize("opcode", OPCODES)
def test_claimed_opcode_compiles(one_chip, opcode):
    """Every opcode the pallas backend claims on a TPU compiles to Mosaic:
    ``MOSAIC_OPCODES`` holds exactly the ones that do not."""
    r, c = 64, 256
    a, b, o = (BaseArray(r * c, F32) for _ in range(3))
    va, vb, vo = _v(a, (r, c)), _v(b, (r, c)), _v(o, (r, c))
    if opcode in _UNARY:
        ops = [Op(opcode, vo, (va,))]
    elif opcode in _BINARY:
        ops = [Op(opcode, vo, (va, vb))]
    elif opcode == "where":
        m = BaseArray(r * c, np.bool_)
        ops = [Op("where", vo, (_v(m, (r, c)), 1.0, 0.0))]
    elif opcode == "copy_literal":
        ops = [Op("copy", vo, (100.0,))]
    elif opcode in ("range", "random"):
        ops = [Op(opcode, vo)]
    else:
        ops = [Op(opcode, _v(BaseArray(r, F32), (r,)), (va,), axis=1)]
    assert mosaic_reason(ops) is None and block_lower_reason(ops) is None
    _compile(one_chip, "pallas", ops)
