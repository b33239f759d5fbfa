"""The ``xla`` lowering backend — the always-available floor.

Wraps ``executor.make_block_fn``: one straight-line jitted JAX program per
block, with every view lowered statically (``executor._view_lowering``:
a reshape, a slice, a slice transposed or broadcast, or an index gather).
It claims every block (COMM ops execute as identity placement casts on a
single device), so it is the terminal fallback of every policy.

Its ``dispatches`` answer is where the PR 3 cost alignment becomes real:
blocks the Pallas codegen cannot express as ONE kernel are free for XLA to
split into several fusions, modelled as 2 dispatches — exactly the
``_KernelAlignment`` pricing in ``core.cost``, so the lower stage's
backend comparison and the partitioner's merge pricing agree.
"""

from __future__ import annotations

from typing import Optional, Sequence

from .base import LoweringBackend, LoweringContext


class XLABackend(LoweringBackend):
    name = "xla"
    donates = True

    def claims(self, ops: Sequence, plan, ctx: LoweringContext) -> Optional[str]:
        return None                      # XLA expresses every block

    def dispatches(self, ops: Sequence, plan, ctx: LoweringContext) -> int:
        # DEL-insensitive expressibility analysis (kernels.fused_block
        # .codegen): inexpressible blocks are priced at 2 dispatches, the
        # same rule the tpu* cost models apply during partitioning.
        from .base import pallas_lower_reason
        return 1 if pallas_lower_reason(ops, plan) is None else 2

    def build(self, ops: Sequence, plan, ctx: LoweringContext):
        from ..executor import make_block_fn
        fn, ins, outs = make_block_fn(ops, seed=ctx.seed)
        assert tuple(ins) == plan.inputs and tuple(outs) == plan.outputs
        return fn
