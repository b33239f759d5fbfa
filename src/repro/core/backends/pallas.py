"""The ``pallas`` lowering backend — tiled fused-block kernels.

Wraps the generalized Pallas codegen (``kernels.fused_block.codegen``,
DESIGN.md §13): a claimed block becomes ONE ``pl.pallas_call`` over a
multi-dimensional ``BlockSpec`` grid with contracted temporaries held in
VMEM.  ``claims`` is the codegen's DEL-insensitive analysis layer
(``block_lower_reason``), so the reason slugs surfaced in per-backend
fallback stats are exactly the documented ``codegen.REASONS``, and the
claim answer matches what the ``tpu*`` cost models priced during
partitioning.  Compiled (non-interpret) lowering additionally declines
what Mosaic cannot compile (``codegen.mosaic_reason``).

Donation is disabled: RMW (partial-write) outputs read their base inside
the kernel epilogue, so input buffers must outlive the call.
"""

from __future__ import annotations

from typing import Optional, Sequence

from .base import LoweringBackend, LoweringContext


class PallasBackend(LoweringBackend):
    name = "pallas"
    donates = False

    def claims(self, ops: Sequence, plan, ctx: LoweringContext) -> Optional[str]:
        from .base import pallas_lower_reason
        if not ctx.interpret:
            from ...kernels.fused_block.codegen import mosaic_reason
            reason = mosaic_reason(ops)
            if reason is not None:
                return reason
        return pallas_lower_reason(ops, plan)

    def build(self, ops: Sequence, plan, ctx: LoweringContext):
        from ...kernels.fused_block.codegen import build_block_kernel
        fn, ins, outs = build_block_kernel(ops, seed=ctx.seed,
                                           interpret=ctx.interpret)
        assert tuple(ins) == plan.inputs and tuple(outs) == plan.outputs
        return fn
