"""Public wrapper around the fused-block Pallas codegen, with automatic
fallback to the XLA per-block path (``make_block_fn``) for the blocks the
tiler cannot express.  The returned ``reason`` tells the caller *why* a
block fell back (``None`` means the Pallas kernel is used).

The runtime no longer dispatches through this wrapper: the ``pallas``
lowering backend (``repro.core.backends.pallas``, DESIGN.md §14) calls
``build_block_kernel`` directly and the scheduler's lower stage handles
fallback selection and per-reason stats.  This facade remains the
convenient claim-or-fallback entry point for tests and standalone use."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from ...core.executor import make_block_fn
from ...core.ir import Op
from .codegen import FusedBlockUnsupported, build_block_kernel


def fused_block_fn(ops: Sequence[Op], *, seed: int = 0,
                   interpret: Optional[bool] = None):
    """Best-effort fused executable for a WSP block.

    Returns ``(fn, input_uids, output_uids, reason)``.  ``fn(*bufs, salts)``
    follows the ``make_block_fn`` calling convention either way, so the
    executor dispatches both paths identically; ``reason`` is ``None`` when
    the block lowered through the Pallas codegen, else the
    :class:`FusedBlockUnsupported` reason slug and ``fn`` is the
    (bit-identical) XLA fallback."""
    try:
        fn, ins, outs = build_block_kernel(ops, seed=seed, interpret=interpret)
        return fn, ins, outs, None
    except FusedBlockUnsupported as e:
        reason = e.reason
    fn, ins, outs = make_block_fn(ops, seed=seed)
    return fn, ins, outs, reason
