"""Staged scheduler pipeline: trace → graph → partition → schedule → lower
→ execute.

This module is the explicit spine of the runtime (DESIGN.md §7).  The six
stages and their owners:

1. **trace**     — ``repro.core.lazy.Runtime`` records array bytecode.
2. **graph**     — ``fusion.build_graph`` builds the WSP instance
   (base-indexed, near-linear on real tapes).
3. **partition** — ``algorithms.partition`` contracts the graph into fusion
   blocks under a cost model.
4. **schedule**  — this module turns the block list into a ``Schedule``: a
   topologically-ordered sequence of ``BlockPlan``s carrying each block's
   external inputs/outputs, contracted temporaries, executable-cache
   signature and *donatable* input positions (buffers whose base dies
   inside the block and can be donated to XLA for in-place reuse).
5. **lower**     — each ``BlockPlan`` is annotated with a ``lowering``
   decision: which registered backend (``repro.core.backends``, DESIGN.md
   §14) runs the block, chosen by querying backend expressibility and the
   cost model's per-backend dispatch price — so one flush can mix
   pallas/xla/shard_map blocks and the executed schedule matches what the
   cost model priced.
6. **execute**   — ``executor.BlockExecutor.run_schedule`` dispatches the
   plans asynchronously against the buffer store.

The ``Schedule`` object is the seam between the partitioner and the
executor, and the distributed subsystem (``repro.core.dist``, DESIGN.md §12)
plugs in exactly here: the resharding pass runs on the tape before stage 2
(so COMM ops are ordinary graph nodes the partitioner prices via the
``comm`` cost model), ``plan`` mixes the executor's device/mesh
``topology`` into the merge-cache key, and the ``shard_map`` backend claims
multi-device blocks in stage 5 — lowering them through ``jax.shard_map``
with explicit collectives while other blocks run on ``pallas``/``xla``
unchanged.

Stages 2-5 are skipped on a merge-cache hit (§IV-F): the cache maps a
canonical tape signature (+ lowering policy) to a :class:`PlanEntry` that
holds the block structure, the per-block lowering decisions and the lowered
schedule itself with its base uids written as canonical indices.  A hit
costs the key's one walk of the tape plus a rebind of those indices to the
fresh tape's uids — nothing per block is re-derived.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Tuple

from .algorithms import PartitionResult, partition
from .backends import LoweringDecision, LoweringPolicy, select_lowering
from .cache import MergeCache, tape_key
from .cost import make_cost_model, model_cache_token
from .executor import block_dead_bases, block_io, block_signature
from .ir import Op
from .obs import trace


@dataclass(frozen=True)
class BlockPlan:
    """Everything the executor needs to dispatch one fusion block."""

    op_indices: Tuple[int, ...]    # tape positions, program order
    inputs: Tuple[int, ...]        # base uids consumed from the store
    outputs: Tuple[int, ...]       # base uids written back to the store
    contracted: Tuple[int, ...]    # new∩del temporaries (never materialized)
    donatable: Tuple[int, ...]     # positions in `inputs` whose buffer dies
    signature: Tuple               # executable-cache key (structural)
    has_work: bool                 # False for DEL/SYNC-only blocks
    #: stage-5 decision (None until lowered / for DEL/SYNC-only blocks)
    lowering: Optional[LoweringDecision] = None


@dataclass
class Schedule:
    """A fully-planned flush: the tape plus its ordered block plans."""

    tape: List[Op]
    blocks: List[BlockPlan]
    result: Optional[PartitionResult] = None   # None on a merge-cache hit
    stats: Dict[str, float] = field(default_factory=dict)
    key: Optional[Tuple] = None                # merge-cache key (use_cache)
    replayed: bool = False                     # rebound from a memory hit


class PlanEntry:
    """One merge-cache value (DESIGN.md §7).

    ``template`` is the lowered schedule of the tape that made the entry,
    with every uid in ``inputs``/``outputs``/``contracted`` replaced by its
    canonical index (``cache.canonical_signature``); its ``op_indices`` and
    lowerings are the block lists and decisions the plan store persists.
    ``digest`` is the key's trace digest, made once, the first time a
    tracer asks for it.

    :meth:`replay` is exact: a key hit means the fresh tape is the entry's
    tape under the renaming ``canon_entry[i] -> canon[i]``, and everything
    ``plan_blocks`` derives is either structural (``op_indices``,
    ``donatable`` positions, ``signature``, ``has_work``, the lowering) or
    a list of uids in first-occurrence order, which commutes with a
    renaming."""

    __slots__ = ("template", "digest")

    def __init__(self, plans: Sequence[BlockPlan], canon: Sequence[int],
                 digest: Optional[str] = None):
        index = {u: i for i, u in enumerate(canon)}.__getitem__
        self.template = tuple(
            (p.op_indices, tuple(map(index, p.inputs)),
             tuple(map(index, p.outputs)), tuple(map(index, p.contracted)),
             p.donatable, p.signature, p.has_work, p.lowering)
            for p in plans)
        self.digest = digest

    def replay(self, canon: Sequence[int]) -> List[BlockPlan]:
        """The template's plans with canonical indices bound to ``canon``,
        the fresh tape's uid vector."""
        uid = canon.__getitem__
        return [BlockPlan(ops, tuple(map(uid, ins)), tuple(map(uid, outs)),
                          tuple(map(uid, con)), don, sig, work, low)
                for ops, ins, outs, con, don, sig, work, low
                in self.template]


@dataclass(frozen=True)
class LoopPlan:
    """The loop planning product for cross-flush fusion (DESIGN.md §16):
    everything the executor's ``run_loop`` needs to compile ONE steady-state
    iteration into a ``fori_loop`` body.

    The plan is purely *structural* — a template tape (any representative of
    the recurring structure) plus per-block plans, the tape-level io in
    canonical first-occurrence order, and the carried-state mapping saying
    where each input position reads from (``("carry", q)`` = loop state slot
    ``q``, ``("inv", j)`` = loop-invariant input ``j``).  It is cached in
    the merge cache beside the block plan (under a ``("loop",)`` prefix) and
    replayed for every structurally-equal tape, whatever its base uids."""

    tape: Tuple[Op, ...]            # template tape, program order
    plans: Tuple[BlockPlan, ...]    # per-block plans (loop-lowered)
    tape_inputs: Tuple[int, ...]    # template tape-level input uids
    tape_outputs: Tuple[int, ...]   # template tape-level output uids
    input_sources: Tuple[Tuple, ...]  # carried-state mapping per input pos
    key: Tuple                      # loop-executable cache identity


def plan_blocks(tape: Sequence[Op],
                op_blocks: Sequence[Sequence[int]]) -> List[BlockPlan]:
    """Stage 4: lower a partition's block lists into ``BlockPlan``s.

    A block input is donatable when its base is deleted (and not SYNC'd)
    inside the same block: no later block may observe it — the partition's
    dependency edges order every access before the DEL — so its device
    buffer can be handed to XLA for output aliasing."""
    plans: List[BlockPlan] = []
    for block in op_blocks:
        ops = [tape[i] for i in block]
        ins, outs, contracted = block_io(ops)
        dead = block_dead_bases(ops)
        donatable = tuple(k for k, u in enumerate(ins) if u in dead)
        plans.append(BlockPlan(
            op_indices=tuple(block),
            inputs=tuple(ins),
            outputs=tuple(outs),
            contracted=tuple(contracted),
            donatable=donatable,
            signature=block_signature(ops),
            has_work=any(not op.is_system() for op in ops),
        ))
    return plans


def lower_plans(tape: Sequence[Op], plans: Sequence[BlockPlan],
                policy: LoweringPolicy, cost_model,
                amortize: int = 1) -> Tuple[Optional[LoweringDecision], ...]:
    """Stage 5: decide, per work block, which backend runs it.

    For each plan the policy's candidate backends are asked to claim the
    block; claimants are priced via ``cost_model.dispatch_price`` over
    their dispatch counts and the cheapest wins (preference order breaking
    ties) — see ``backends.select_lowering``.  ``amortize`` > 1 re-lowers
    for a fused loop body, where launch overhead amortizes over the unroll
    (DESIGN.md §16).  Returns one decision per plan (``None`` for
    DEL/SYNC-only blocks), aligned with ``plans``."""
    return tuple(
        select_lowering([tape[i] for i in p.op_indices], p,
                        policy.backends, policy.ctx, cost_model,
                        amortize=amortize)
        if p.has_work else None
        for p in plans)


class Scheduler:
    """Owns stages 2–5 and the merge cache; policy arrives per call so the
    Runtime can retarget algorithm/cost model/backends between flushes."""

    def __init__(self, cache: Optional[MergeCache] = None):
        self.cache = cache if cache is not None else MergeCache()
        #: optional persistent plan cache (``repro.core.serve.PlanStore``,
        #: DESIGN.md §18) — probed after an in-memory merge-cache miss and
        #: written through on fresh plans, so a warm process start replays
        #: block structure + lowering decisions from disk
        self.plan_store = None

    def plan(self, tape: Sequence[Op], *, algorithm: str = "greedy",
             cost_model: str = "bohrium", node_budget: int = 100_000,
             use_cache: bool = True, topology: Tuple = (),
             lowering: Optional[LoweringPolicy] = None,
             partition_backend: str = "greedy",
             time_budget_s: Optional[float] = None) -> Schedule:
        """Stages 2–5: turn a recorded tape into an executable ``Schedule``.

        Builds the WSP graph, partitions it under ``cost_model`` with
        ``algorithm``, lowers the block lists into ordered
        :class:`BlockPlan`s, and — when the executor's ``lowering`` policy
        is given — annotates each work block with its backend decision
        (stage 5).  ``topology`` is the executor's device/mesh key so
        cached partitions are never reused across different placements;
        the policy's backend names are part of the key too, so decisions
        made for one backend stack never leak into another.  On an
        in-memory merge-cache hit the entry's schedule is replayed
        (:meth:`PlanEntry.replay`): steady-state flushes skip partitioning,
        the per-block schedule analysis and backend probing alike
        (``Schedule.result`` is ``None`` and ``Schedule.replayed`` true; the
        ``stage.schedule`` span times the rebind and no ``stage.lower``
        opens).  A plan-store hit rebuilds the schedule once from the
        stored blocks and decisions and is replayed from memory after.
        ``Schedule.stats`` carries per-stage timings.

        ``partition_backend='ilp'`` solves the partition as an anytime
        integer program warm-started from greedy (``algorithms.partition``;
        ``time_budget_s`` caps the solver wall clock).  The backend is part
        of the merge-cache / plan-store key: a store populated by greedy is
        a clean miss for ilp and vice versa."""
        stats: Dict[str, float] = {}
        blocks: Optional[Tuple[Tuple[int, ...], ...]] = None
        decisions: Optional[Tuple] = None
        key: Optional[Tuple] = None
        canon: Tuple[int, ...] = ()
        digest: Optional[str] = None
        if use_cache:
            with trace.span("plan.lookup") as sp:
                key, canon = tape_key(
                    tape, algorithm, cost_model, topology=topology,
                    backends=lowering.key() if lowering else (),
                    cost_token=model_cache_token(cost_model),
                    partition_backend=partition_backend)
                entry = self.cache.get(key)
                hit = "memory"
                if entry is None:
                    hit = "miss"
                    if self.plan_store is not None:
                        stored = self.plan_store.load(key)
                        if stored is not None:
                            hit = "disk"
                            blocks, decisions = stored
                if trace.active() is not None:
                    digest = entry.digest if entry is not None else None
                    if digest is None:
                        from .tuning.profile import signature_digest
                        digest = signature_digest(key)
                        if entry is not None:
                            entry.digest = digest
                    sp.set(hit=hit, key=digest)
            if entry is not None:
                t0 = time.perf_counter()
                with trace.span("stage.schedule",
                                n_blocks=len(entry.template), cached=True,
                                replayed=True):
                    plans = entry.replay(canon)
                stats["t_schedule_s"] = time.perf_counter() - t0
                return Schedule(tape=list(tape), blocks=plans, stats=stats,
                                key=key, replayed=True)
        cached = blocks is not None
        result = None
        if blocks is None:
            result = partition(tape, algorithm=algorithm,
                               cost_model=cost_model,
                               node_budget=node_budget,
                               partition_backend=partition_backend,
                               time_budget_s=time_budget_s)
            blocks = tuple(tuple(b) for b in result.op_blocks())
            stats.update(result.stats)
        t0 = time.perf_counter()
        with trace.span("stage.schedule", n_blocks=len(blocks),
                        cached=cached, replayed=False):
            plans = plan_blocks(tape, blocks)
        stats["t_schedule_s"] = time.perf_counter() - t0
        if lowering is not None:
            t0 = time.perf_counter()
            with trace.span("stage.lower", cached=decisions is not None):
                if decisions is None:
                    decisions = lower_plans(tape, plans, lowering,
                                            make_cost_model(cost_model))
                plans = [replace(p, lowering=d) if d is not None else p
                         for p, d in zip(plans, decisions)]
            stats["t_lower_s"] = time.perf_counter() - t0
        if use_cache:
            # a disk hit is promoted too, so later flushes replay from memory
            self.cache.put(key, PlanEntry(plans, canon, digest))
            if self.plan_store is not None and not cached:
                self.plan_store.store(key, blocks, decisions)
        return Schedule(tape=list(tape), blocks=plans, result=result,
                        stats=stats, key=key)

    def plan_loop(self, schedule: Schedule, *, key: Tuple, io: Tuple,
                  mapping: Tuple, cost_model: str = "bohrium",
                  lowering: Optional[LoweringPolicy] = None,
                  unroll: int = 1) -> LoopPlan:
        """Plan the steady-state loop body for a recurring tape
        (DESIGN.md §16).

        ``schedule`` is the already-planned flush serving as the structural
        template, ``key`` its merge-cache key, ``io`` its tape-level
        ``cache.tape_io`` and ``mapping`` the ``cache.carried_state_mapping``
        proven stable by the recurrence detector.  Work blocks are
        *re-lowered* with the dispatch term amortized over ``unroll`` —
        inside a ``fori_loop`` launch overhead is paid once per loop, so a
        backend that only lost on launch cost may win back the block.  The
        product is cached beside the block plan under ``("loop",) + key``:
        a steady-state program plans its loop exactly once."""
        loop_key = ("loop", key, tuple(mapping), unroll)
        entry = self.cache.get(loop_key)
        if entry is not None:
            return entry
        tape = schedule.tape
        plans: Sequence[BlockPlan] = schedule.blocks
        if lowering is not None:
            decisions = lower_plans(tape, plans, lowering,
                                    make_cost_model(cost_model),
                                    amortize=unroll)
            plans = [replace(p, lowering=d) if d is not None else p
                     for p, d in zip(plans, decisions)]
        lp = LoopPlan(tape=tuple(tape), plans=tuple(plans),
                      tape_inputs=tuple(io[0]), tape_outputs=tuple(io[1]),
                      input_sources=tuple(mapping),
                      key=(key, tuple(mapping), unroll))
        self.cache.put(loop_key, lp)
        return lp
