"""The ``rowblock`` lowering backend for LM blocks (DESIGN.md §20).

The WSP fuse rule ends a block at every reduction, so a transformer tape
partitions into small reduction blocks whose results feed later ops of the
same block through a broadcast (``exp(x - max)``, ``x * rsqrt(var)``).
The generic ``pallas`` tiler declines those (``view_conflict``); the
row-replay codegen (``kernels.fused_block.rowblock``) expresses them as
one row-tiled Pallas kernel.  This backend claims a block when

1. its opcodes pass one of the row-pattern screens below (a masked
   softmax piece, an rmsnorm scale chain or an exp-gated scan update),
   else it declines with ``no_row_pattern``;
2. on a TPU, Mosaic can compile its opcodes and dtypes (``mosaic_reason``);
3. the row-replay codegen can express it (``rowblock_lower_reason``).

A claimed block costs one dispatch, the price the generic ``pallas``
backend quotes when it can also express the block; the ``lm`` stack's
order (``rowblock`` first) breaks that tie.  Where ``pallas`` declines,
``rowblock`` wins outright over the two-dispatch XLA fallback.

The row-replay kernel applies the same jnp op tables as the XLA fallback
(``make_block_fn``) to each complete row, so its results agree with XLA's
to float32 rounding; tests hold them to a stated tolerance.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Optional, Sequence, Tuple

from .base import LoweringBackend, LoweringContext

#: a masked-softmax piece: the masked max ``where -> reduce_max``, or the
#: shifted exponentials and their sum ``sub -> exp -> reduce_sum`` (a bare
#: exp + sum without the max subtraction is a scan shape)
_SOFTMAX_OPS = {"mul", "div", "where", "sub", "add", "neg", "exp",
                "reduce_max", "reduce_sum", "copy", "maximum"}
_SOFTMAX_ANCHORS = ({"where", "reduce_max"}, {"sub", "exp", "reduce_sum"})
#: the rmsnorm scale chain ``div -> add(eps) -> rsqrt -> mul -> mul``,
#: anchored on ``rsqrt`` so plain sums of squares are not claimed
_NORM_OPS = {"add", "sub", "mul", "div", "rsqrt", "sqrt", "square",
             "reciprocal", "reduce_sum", "copy"}
_NORM_ANCHORS = {"rsqrt", "mul"}
#: an exp-gated scan update ``exp(decay) * state + gate * input`` with at
#: most one trailing contraction
_SCAN_OPS = {"exp", "add", "sub", "mul", "div", "neg", "reduce_sum", "copy"}
_SCAN_ANCHORS = {"exp", "add"}


def row_pattern_reason(ops: Sequence) -> Optional[str]:
    """``None`` when the block's opcodes pass one of the row-pattern
    screens, else ``"no_row_pattern"``.  A pure opcode screen, cheap
    enough to run on every block; whether the row replay can express the
    block is :func:`rowblock_lower_reason`'s question."""
    work = [op.opcode for op in ops if not op.is_system()]
    seen = set(work)
    if seen <= _SOFTMAX_OPS and any(a <= seen for a in _SOFTMAX_ANCHORS):
        return None
    if seen <= _NORM_OPS and _NORM_ANCHORS <= seen:
        return None
    if (seen <= _SCAN_OPS and _SCAN_ANCHORS <= seen
            and work.count("mul") >= 2 and work.count("reduce_sum") <= 1):
        return None
    return "no_row_pattern"


_ROW_MEMO: "OrderedDict[Tuple, Optional[str]]" = OrderedDict()
_ROW_MEMO_CAP = 4096


def rowblock_lower_reason(ops: Sequence, plan) -> Optional[str]:
    """Row-replay expressibility, memoized on the plan's structural
    signature like :func:`repro.core.backends.base.pallas_lower_reason`, so
    a block planned again after a merge-cache miss is not re-analysed."""
    key = getattr(plan, "signature", None)
    if key is not None and key in _ROW_MEMO:
        _ROW_MEMO.move_to_end(key)
        return _ROW_MEMO[key]
    from ...kernels.fused_block.rowblock import rowblock_lower_reason as raw
    reason = raw(ops)
    if key is not None:
        _ROW_MEMO[key] = reason
        if len(_ROW_MEMO) > _ROW_MEMO_CAP:
            _ROW_MEMO.popitem(last=False)
    return reason


class RowBlockBackend(LoweringBackend):
    """Row-pattern screen, then the row-replay claim and build."""

    name = "rowblock"
    donates = False      # operands may be read through broadcast views

    def claims(self, ops: Sequence, plan, ctx: LoweringContext) -> Optional[str]:
        reason = row_pattern_reason(ops)
        if reason is None and not ctx.interpret:
            from ...kernels.fused_block.codegen import mosaic_reason
            reason = mosaic_reason(ops)
        if reason is not None:
            return reason
        return rowblock_lower_reason(ops, plan)

    def build(self, ops: Sequence, plan, ctx: LoweringContext):
        from ...kernels.fused_block.rowblock import build_rowblock_kernel
        fn, ins, outs = build_rowblock_kernel(ops, seed=ctx.seed,
                                              interpret=ctx.interpret)
        assert tuple(ins) == plan.inputs and tuple(outs) == plan.outputs
        return fn


#: preference order of the ``backend="lm"`` stack: the row replay first,
#: then the generic codegen, then the XLA floor
LM_STACK: Tuple[str, ...] = ("rowblock", "pallas", "xla")
