"""Hand-written-kernel lowering claimants for LM blocks (DESIGN.md §20).

Three backends — ``flash_attention``, ``rmsnorm``, ``mamba_scan`` — wrap
the kernels under ``repro.kernels.*`` as first-class lowering backends:
each one *claims* a fusion block when (a) its op-pattern matcher
(``kernels.<name>.block.match``) recognizes the block's opcode shape and
(b) the row-replay codegen (``kernels.fused_block.rowblock``) can express
it as one row-tiled Pallas kernel.  Blocks outside the pattern decline
with the matcher's slug (``no_softmax`` / ``no_rmsnorm`` / ``no_scan``);
pattern-shaped blocks the tiler cannot express decline with the codegen
reason, so fallback stats separate "not mine" from "mine but
inexpressible".

Pricing: one dispatch per claimed block, the same price the generic
``pallas`` backend quotes when it can also express the block — the tie
is broken by the ``lm`` stack's preference order (claimants first), so a
matched block always runs the hand-written path.  When the generic tiler
declines (``view_conflict`` on blocks that consume an in-block reduction
through a broadcast view — the shape the row-replay codegen exists for)
the claimant wins outright over the 2-dispatch XLA fallback under any
cost model's ``dispatch_price``.

Bit-identity note: the claimants lower through the row-replay generator —
the same jnp op tables as the XLA fallback, applied in the same per-row
order — NOT through the hand-written kernel bodies in
``kernels/*/kernel.py``.  The flash kernel's online-softmax rewrite
``(p @ v) / l`` differs from XLA's ``(p / l) @ v`` in the last ulp; the
claim protocol requires results bitwise-identical to the XLA fallback, so
the kernels' *claim boundary* (the matchers) and the *replay* lowering
are what ship here.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Optional, Sequence, Tuple

from .base import LoweringBackend, LoweringContext

_ROW_MEMO: "OrderedDict[Tuple, Optional[str]]" = OrderedDict()
_ROW_MEMO_CAP = 4096


def rowblock_lower_reason(ops: Sequence, plan) -> Optional[str]:
    """Memoized row-replay expressibility, keyed like
    :func:`repro.core.backends.base.pallas_lower_reason` on the plan's
    structural signature — all three claimants consult it during one
    selection, so the second and third lookups are free."""
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


class _RowKernelBackend(LoweringBackend):
    """Shared machinery: matcher screen, then row-replay claim + build."""

    donates = False      # operands may be read through broadcast views

    def _match(self, ops: Sequence) -> Optional[str]:
        raise NotImplementedError

    def claims(self, ops: Sequence, plan, ctx: LoweringContext) -> Optional[str]:
        reason = self._match(ops)
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


class FlashAttentionBackend(_RowKernelBackend):
    name = "flash_attention"

    def _match(self, ops: Sequence) -> Optional[str]:
        from ...kernels.flash_attention.block import match
        return match(ops)


class RMSNormBackend(_RowKernelBackend):
    name = "rmsnorm"

    def _match(self, ops: Sequence) -> Optional[str]:
        from ...kernels.rmsnorm.block import match
        return match(ops)


class MambaScanBackend(_RowKernelBackend):
    name = "mamba_scan"

    def _match(self, ops: Sequence) -> Optional[str]:
        from ...kernels.mamba_scan.block import match
        return match(ops)


#: preference order of the ``backend="lm"`` stack: specific claimants
#: first (most selective matcher wins ties), generic codegen, XLA floor
LM_STACK: Tuple[str, ...] = ("flash_attention", "rmsnorm", "mamba_scan",
                             "pallas", "xla")
