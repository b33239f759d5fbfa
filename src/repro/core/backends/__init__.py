"""Pluggable lowering backends (DESIGN.md §14).

``LoweringBackend`` is the protocol, the registry maps names to instances,
and :func:`select_lowering` is the per-block, cost-model-priced selection
rule the scheduler's **lower** stage runs.  The built-in backends register
on import:

* ``xla``       — one jitted XLA program per block (claims everything);
* ``pallas``    — one tiled Pallas kernel per block (claims what the
  fused-block codegen expresses, DESIGN.md §13);
* ``shard_map`` — multi-device blocks with real collectives (claims
  sharded blocks on a mesh, DESIGN.md §12);
* ``rowblock``  — one row-tiled Pallas kernel per LM reduction block
  (a row-pattern screen + the row-replay codegen, DESIGN.md §20; the
  ``backend="lm"`` stack).

New backends (interpreter/debug, multi-GPU pallas, CPU-vectorized)
implement the protocol and call :func:`register_backend`; any executor
whose policy names them will start routing blocks their way.
"""

from __future__ import annotations

from typing import Tuple

from .base import (BackendBuildError, LoweringBackend,       # noqa: F401
                   LoweringContext, LoweringDecision, LoweringPolicy,
                   available_backends, build_block, get_backend,
                   register_backend, select_lowering, unregister_backend)
from .lm import LM_STACK, RowBlockBackend                   # noqa: F401
from .pallas import PallasBackend                            # noqa: F401
from .shard_map import ShardMapBackend                       # noqa: F401
from .xla import XLABackend                                  # noqa: F401

register_backend(XLABackend())
register_backend(PallasBackend())
register_backend(ShardMapBackend())
register_backend(RowBlockBackend())


def default_stack(backend="xla", mesh=None) -> Tuple[str, ...]:
    """Resolve an executor's ``backend=`` parameter into the
    preference-ordered candidate list of the lowering policy.

    Strings keep their historical meaning (``"xla"`` → XLA only,
    ``"pallas"`` → Pallas with XLA fallback, ``"lm"`` → the row replay
    over Pallas over XLA (``lm.LM_STACK``), any other
    registered name → that backend with XLA fallback); a tuple/list is
    taken verbatim.  A mesh prepends ``shard_map`` so sharded blocks
    prefer collectives."""
    if isinstance(backend, (tuple, list)):
        names = tuple(backend)
    elif backend == "xla":
        names = ("xla",)
    elif backend == "lm":
        names = LM_STACK
    else:
        names = (backend, "xla")
    if mesh is not None and "shard_map" not in names:
        names = ("shard_map",) + names
    return names
