"""Back-compat facade over the generalized tiled codegen (``codegen.py``).

The original module was a flat 1-D tiler restricted to whole-base,
same-domain elementwise blocks; ISSUE 3 replaced it with the general
multi-dimensional ``BlockSpec`` grid generator in
:mod:`repro.kernels.fused_block.codegen` (reductions, strided/partial
views, broadcasts).  This module keeps the historical entry point
``build_fused_kernel`` (salt-less calling convention) for existing tests
and external callers; new code should use
:func:`~repro.kernels.fused_block.codegen.build_block_kernel`.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

from ...core.ir import Op
from .codegen import (FusedBlockUnsupported, LANE, SUBLANE,  # noqa: F401
                      VMEM_BUDGET, block_lower_reason, build_block_kernel)


def build_fused_kernel(ops: Sequence[Op], *, tile: int = 0,
                       interpret: Optional[bool] = None):
    """Compile a WSP block into one Pallas kernel (legacy signature).

    Returns ``(fn, input_uids, output_uids)`` with ``fn(*flat_bufs) ->
    tuple(flat_out_bufs)``.  ``tile`` is ignored: the generalized codegen
    picks its own ``(rows, lanes)`` slab from the block's domain and the
    VMEM budget.  Raises :class:`FusedBlockUnsupported` (with a ``reason``
    slug) for the truly inexpressible blocks — gather-indexed views, COMM
    ops, opaque opcodes."""
    fn, ins, outs = build_block_kernel(ops, interpret=interpret)
    empty = jnp.zeros((0,), jnp.int32)

    def saltless(*bufs):
        return fn(*bufs, empty)

    return saltless, ins, outs
