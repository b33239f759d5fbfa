"""The lowering-backend protocol and the per-block selection rule
(DESIGN.md §14).

A :class:`LoweringBackend` is one way to turn a fusion block (a
``BlockPlan`` plus its ops) into an executable with the ``make_block_fn``
calling convention ``fn(*input_bufs, salts) -> output_bufs``.  Backends are
*peers* registered under a name — the executor is a dispatch engine over
the registry, and the scheduler's **lower** stage decides per block which
backend runs it:

1. every backend in the policy's preference-ordered candidate list is asked
   whether it *claims* the block (``claims`` returns ``None``, or a stable
   reason slug explaining why it cannot express the block);
2. among the claimants, each backend reports how many executable
   *dispatches* the block will cost on it (the XLA backend reports 2 for
   blocks the Pallas codegen cannot express as one kernel — the same
   DEL-insensitive analysis the ``tpu*`` cost models price);
3. the cost model converts dispatch counts into a price
   (``CostModel.dispatch_price``) and the cheapest claimant wins, with ties
   broken by the policy's preference order.

The decision is recorded on the ``BlockPlan`` (and in the merge cache), so
steady-state flushes skip both partitioning and backend probing, and the
executed schedule matches exactly what the cost model priced.

Everything here is pure metadata — no jax tracing, no device access — so
selection is cheap enough to run inside the scheduler.  Backend modules
import their heavyweight dependencies (codegen, shard_map, the executor's
interpreter tables) lazily inside methods to keep the core import graph
acyclic.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

from ...kernels import resolve_interpret


@dataclass(frozen=True)
class LoweringContext:
    """Executor configuration a backend may need to claim or build a block.

    ``interpret`` selects Pallas interpret mode; it defaults to
    :func:`repro.kernels.resolve_interpret` (compiled on a TPU, interpreted
    elsewhere).  ``mesh``/``axis``/
    ``n_dev`` describe the device mesh for sharded lowerings (``mesh`` is
    ``None`` on single-device executors).  The context deliberately carries
    no buffers: backends compile pure functions, the executor owns the
    store, donation and jit wrapping.
    """

    seed: int = 0
    jit: bool = True
    interpret: bool = field(default_factory=resolve_interpret)
    mesh: object = None
    axis: Optional[str] = None
    n_dev: int = 1


@dataclass(frozen=True)
class LoweringDecision:
    """Outcome of the lower stage for one block.

    ``backend`` names the winning backend; ``declined`` records, for every
    backend the policy *preferred* over the winner, the reason slug it gave
    for not claiming the block — the executor turns these into per-backend
    fallback stats (``stats["backend_fallbacks"]``).
    """

    backend: str
    declined: Tuple[Tuple[str, str], ...] = ()

    def reason_for(self, name: str) -> Optional[str]:
        """Why ``name`` declined this block (None if it did not decline)."""
        return dict(self.declined).get(name)


@dataclass(frozen=True)
class LoweringPolicy:
    """What the executor hands the scheduler: the preference-ordered
    candidate backend names plus the context they compile under.  The key
    is part of the merge-cache key — decisions made for one backend stack
    are never replayed under another, nor decisions made for interpreted
    kernels on compiled ones (Mosaic declines more, ``mosaic_reason``)."""

    backends: Tuple[str, ...]
    ctx: LoweringContext

    def key(self) -> Tuple[str, ...]:
        return self.backends if self.ctx.interpret \
            else self.backends + ("mosaic",)


class LoweringBackend:
    """One way to lower a fusion block to an executable.

    Subclasses override :meth:`claims` and :meth:`build`; ``dispatches``,
    ``cache_token`` and ``post_dispatch`` have sensible defaults.  Register
    instances with :func:`register_backend`; the three built-ins (``xla``,
    ``pallas``, ``shard_map``) self-register on package import, and every
    future backend (interpreter/debug, multi-GPU pallas, CPU-vectorized)
    plugs in the same way.
    """

    #: registry name, also the stats key (``stats["backend_blocks"][name]``)
    name: str = "abstract"
    #: True when executables tolerate ``jax.jit(donate_argnums=...)`` input
    #: donation (the executor only donates on backends that opt in)
    donates: bool = False

    def claims(self, ops: Sequence, plan, ctx: LoweringContext) -> Optional[str]:
        """``None`` when this backend can lower the block, else a stable
        reason slug (feeds per-backend fallback stats).  Must be a pure
        metadata check — no tracing."""
        raise NotImplementedError

    def dispatches(self, ops: Sequence, plan, ctx: LoweringContext) -> int:
        """How many executable dispatches the block costs on this backend —
        the quantity the cost model prices during selection."""
        return 1

    def build(self, ops: Sequence, plan, ctx: LoweringContext):
        """Compile the block: returns ``fn(*input_bufs, salts) ->
        output_bufs`` (NOT yet jitted — the executor applies ``jax.jit`` and
        donation uniformly)."""
        raise NotImplementedError

    def cache_token(self, ops: Sequence, plan, ctx: LoweringContext) -> Tuple:
        """Extra executable-cache key components beyond the structural
        signature (e.g. placement).  Default: none."""
        return ()

    def post_dispatch(self, ops: Sequence, plan, ctx: LoweringContext,
                      stats: Dict) -> None:
        """Per-dispatch accounting hook (e.g. collective/fabric-byte
        counters on the shard_map backend)."""


# ---------------------------------------------------------------------------
# Shared analysis memo
# ---------------------------------------------------------------------------

_ANALYSIS_MEMO: "OrderedDict[Tuple, Optional[str]]" = OrderedDict()
_ANALYSIS_MEMO_CAP = 4096


def pallas_lower_reason(ops: Sequence, plan) -> Optional[str]:
    """Memoized ``codegen.block_lower_reason`` keyed on the plan's canonical
    structural signature (the analysis is purely structural, so the
    signature is its exact identity).  Both the ``pallas`` backend's claim
    and the ``xla`` backend's dispatch count consult this analysis during
    one selection — the memo makes the second (and any later) lookup free."""
    key = getattr(plan, "signature", None)
    if key is not None and key in _ANALYSIS_MEMO:
        _ANALYSIS_MEMO.move_to_end(key)
        return _ANALYSIS_MEMO[key]
    from ...kernels.fused_block.codegen import block_lower_reason
    reason = block_lower_reason(ops)
    if key is not None:
        _ANALYSIS_MEMO[key] = reason
        if len(_ANALYSIS_MEMO) > _ANALYSIS_MEMO_CAP:
            _ANALYSIS_MEMO.popitem(last=False)
    return reason


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, LoweringBackend] = {}


def register_backend(backend: LoweringBackend, *, replace: bool = False) -> LoweringBackend:
    """Register a backend instance under ``backend.name``.

    ``replace=True`` swaps an existing registration (tests, debug
    interposers); otherwise double registration is an error."""
    if not replace and backend.name in _REGISTRY:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def unregister_backend(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_backend(name: str) -> LoweringBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown lowering backend {name!r}; have {sorted(_REGISTRY)}")


def available_backends() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


class BackendBuildError(RuntimeError):
    """A lowering backend failed to build a block the lower stage gave it.

    Raised, never swallowed: a block silently re-run on another backend
    would hide the device path it was decided for (DESIGN.md §14)."""

    def __init__(self, backend: str, op_indices):
        self.backend = backend
        super().__init__(f"lowering backend {backend!r} failed to build "
                         f"block {tuple(op_indices)!r}")


def build_block(name: str, ops: Sequence, plan, ctx: LoweringContext):
    """``get_backend(name).build(...)``, with any failure re-raised as a
    :class:`BackendBuildError` naming the backend."""
    try:
        return get_backend(name).build(ops, plan, ctx)
    except Exception as e:
        raise BackendBuildError(name, plan.op_indices) from e


# ---------------------------------------------------------------------------
# Selection — the lower stage's per-block rule
# ---------------------------------------------------------------------------

def select_lowering(ops: Sequence, plan, backends: Sequence[str],
                    ctx: LoweringContext,
                    cost_model=None, amortize: int = 1) -> LoweringDecision:
    """Pick the backend that runs one block.

    ``backends`` is the preference-ordered candidate list.  Each candidate
    is asked to claim the block; claimants are priced through
    ``cost_model.lowering_price(n_dispatches, ext_bytes, backend=name)``
    (the raw dispatch count when no model is given) and the cheapest wins,
    preference order breaking ties.  For analytic models the price reduces
    to ``dispatch_price`` — external bytes move at one assumed bandwidth
    regardless of backend, so the byte term cancels from the comparison.
    A calibrated model (DESIGN.md §15) prices each candidate at its own
    *measured* per-dispatch overhead and per-byte slope, which is what lets
    measured reality flip a decision.  ``amortize`` is the unroll factor
    when the block is being re-lowered for a fused cross-flush loop body
    (DESIGN.md §16): launch overhead amortizes over the loop, byte traffic
    does not.  Returns a :class:`LoweringDecision` whose ``declined`` tuple
    keeps the reasons of every backend preferred over the winner."""
    order = {n: i for i, n in enumerate(backends)}
    declined = []
    claimants = []
    for name in backends:
        be = get_backend(name)
        reason = be.claims(ops, plan, ctx)
        if reason is None:
            claimants.append(be)
        else:
            declined.append((name, reason))
    if not claimants:
        raise RuntimeError(
            f"no backend claims block {plan.op_indices!r} "
            f"(candidates {tuple(backends)}, reasons {declined})")
    if len(claimants) == 1:
        best = claimants[0]
    else:
        ext_bytes = 0.0
        if cost_model is not None:
            from ..cost import CostModel
            if type(cost_model).lowering_price is not CostModel.lowering_price:
                # only models that actually price bytes per backend (e.g.
                # "calibrated") pay for the block summary; for analytic
                # models the byte term cancels out of the comparison anyway
                from ..blocks import BlockInfo
                ext_bytes = float(BlockInfo.from_ops(ops).ext_size("bytes"))

        def price(be: LoweringBackend) -> float:
            n = be.dispatches(ops, plan, ctx)
            return (cost_model.lowering_price(n, ext_bytes, backend=be.name,
                                              amortize=amortize)
                    if cost_model is not None else float(n))
        best = min(claimants, key=lambda be: (price(be), order[be.name]))
    cut = order[best.name]
    return LoweringDecision(
        backend=best.name,
        declined=tuple((n, r) for n, r in declined if order[n] < cut))
