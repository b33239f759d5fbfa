"""General tiled Pallas code generator for WSP partition blocks.

This is the TPU-native realization of the paper's per-block JIT kernels
(§III final phase, Fig. 1d): a fused block becomes ONE ``pl.pallas_call``
over a multi-dimensional ``BlockSpec`` grid, and contracted arrays
(``new ∩ del``) live entirely in VMEM/VREGs — array contraction with the
VMEM tile as the "register".

The generator canonicalizes the block's common iteration domain ``D``
(guaranteed by fusion legality: every work op in a block shares one domain)
to a 2-D ``(R, C)`` space — ``C`` is the innermost domain axis (lanes),
``R`` the product of the leading axes (sublanes × grid) — and tiles it as a
1-D grid of ``(TR, C)`` row slabs.  On top of that it supports:

* **elementwise chains** over arbitrary-rank bases (the old flat tiler
  handled only rank-agnostic whole-base views);
* **in-kernel reductions** (``reduce_sum/max/min/prod``): trailing-axis
  reductions reduce each row slab in-register, full (1-D) and leading-axis
  (2-D) reductions are grid-accumulated into a VMEM accumulator block that
  every grid step revisits (constant index map), with identity-masked
  padding;
* **regularly-strided / partial views**: the per-view ``_slice_plan`` from
  ``core.executor`` lowers the view to ``reshape + static slice`` of the
  flat base — gather-free — both for operand extraction and for
  read-modify-write outputs, which are computed in-kernel and scattered
  into their base by a single static-slice epilogue;
* **scalar / row / column broadcasts** (stride-0 view axes): the operand is
  streamed as a ``(1, 1)``, ``(1, C)`` or ``(TR, 1)`` block and broadcast
  in-register, never materialized at domain size;
* **``range`` / ``random`` ops**: ``range`` becomes an in-kernel iota over
  the global flat index; ``random`` values are drawn in an XLA prologue
  with the exact ``fold_in(PRNGKey(seed), salt)`` scheme of the fallback
  path, so results stay bit-identical and partition-invariant;
* **``gather`` ops** (1-D whole-base table, axis 0, index-shaped output):
  the table streams in as a ``"table"`` operand — a constant-index-map
  block holding the WHOLE table, revisited by every grid step and counted
  at full size in the VMEM budget — and the kernel computes
  ``jnp.take(table, idx.astype(int32), axis=0)``, the exact expression of
  the XLA fallback, so the in-kernel index load stays bit-identical.
  Other gather forms (multi-axis tables, partial table views) raise the
  ``gather_form`` slug.

``FusedBlockUnsupported`` is now reserved for the truly inexpressible
cases; each raise carries a machine-readable ``reason`` slug (see
``REASONS``) that the executor counts per-reason in its stats and
DESIGN.md §13 documents.  The analysis layer (``_analyze`` /
``block_lower_reason``) is deliberately independent of DEL/SYNC placement
(it looks only at opcodes, domains, views and axes), so the ``tpu*`` cost
models can use it to price kernel expressibility while staying monotone
under block merges.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import resolve_interpret

# the kernel body evaluates ops with the SAME jnp tables as the XLA
# fallback (make_block_fn) — importing them is what makes the bit-identity
# contract a structural property rather than a convention to maintain
from ...core.executor import (_BINARY, _REDUCE as _REDUCE_FN, _UNARY, _read,
                              _slice_plan, _write, block_io)
from ...core.ir import COMM_OPS, REDUCTIONS, Op, View

LANE = 128                    # VPU lane count
SUBLANE = 8                   # f32 sublane count
ONE_D_COLS = 4 * LANE         # lane width when flattening a 1-D domain
TILE_ELEMS = 8 * SUBLANE * LANE   # target elements per (TR, C) slab
VMEM_BUDGET = 8 * 1024 * 1024     # conservative half of v5e's 16 MiB VMEM

_COMBINE = {
    "reduce_sum": jnp.add, "reduce_max": jnp.maximum,
    "reduce_min": jnp.minimum, "reduce_prod": jnp.multiply,
}

#: fallback reason slugs (DESIGN.md §13 documents the semantics of each)
REASONS = (
    "system_only",      # no work ops — nothing to compile
    "empty_domain",     # zero-size iteration domain
    "comm",             # COMM op: a placement change, never a compute kernel
    "opcode",           # opaque opcode (matmul, unknown)
    "mixed_domain",     # work ops disagree on the iteration domain
    "irregular_view",   # view is not whole-base / slice-plannable
    "gather_form",      # gather not in the supported 1-D axis-0 whole-table form
    "reduction_axis",   # reduction axis not full/leading/trailing
    "reduction_out",    # reduction output is not a whole contiguous base
    "view_conflict",    # in-block read overlaps a non-identical prior write
    "vmem",             # an 8-row (or whole) slab set still exceeds the budget
    "error",            # defensive: analysis itself failed
    # compiled (non-interpret) lowering only — see mosaic_reason
    "mosaic_x64",       # 64-bit element type: Mosaic has no 64-bit vectors
    "mosaic_gather",    # in-kernel gather: Mosaic lowers only 2-D gathers
    "mosaic_opcode",    # opcode with no Pallas TPU lowering (MOSAIC_OPCODES)
)

#: opcodes the interpreter runs but Pallas cannot lower to Mosaic
#: (``NotImplementedError: Unimplemented primitive in Pallas TPU lowering``)
MOSAIC_OPCODES = frozenset({"erf", "reduce_prod"})


class FusedBlockUnsupported(Exception):
    """Block not expressible as ONE tiled Pallas kernel.

    ``reason`` is a stable slug from :data:`REASONS`; the executor exposes
    per-reason counters as ``stats["pallas_fallbacks"]``.
    """

    def __init__(self, reason: str, detail: str = ""):
        self.reason = reason
        super().__init__(f"{reason}: {detail}" if detail else reason)


# ---------------------------------------------------------------------------
# Analysis — pure metadata, no tracing.  Everything here depends only on the
# work ops' opcodes/domains/views/axes (NOT on DEL/SYNC placement), so the
# expressibility answer is stable under merging system ops into a block —
# the property the cost-model alignment relies on for monotonicity.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _Operand:
    """One kernel input stream."""

    key: Tuple
    kind: str                 # "dense" | "row" | "col" | "scalar" | "table"
    source: str               # "buffer" | "zeros" | "random"
    base_uid: int = -1
    core: Optional[View] = None      # view materialized outside the kernel
    bcast_dims: Tuple[int, ...] = ()  # broadcast axes (mixed dense case)
    rand_pos: int = -1               # index into the block's random ops


@dataclass(frozen=True)
class _Slot:
    """One kernel output stream."""

    kind: str                 # "dense" | "window" | "red_full" | "red_row" | "red_col"
    dtype: np.dtype
    base_uid: int
    view: Optional[View] = None      # window scatter target


@dataclass
class _Node:
    """One work op, resolved against operands/earlier nodes."""

    opcode: str
    terms: Tuple              # ("lit", x) | ("op", operand_idx) | ("val", node_idx)
    out_dtype: np.dtype
    red_kind: Optional[str] = None   # "full" | "row" | "col"
    out_slot: Optional[int] = None


@dataclass
class _Plan:
    domain: Tuple[int, ...]
    N: int
    R: int
    C: int
    TR: int
    G: int
    one_d: bool
    operands: List[_Operand] = field(default_factory=list)
    slots: List[_Slot] = field(default_factory=list)
    nodes: List[_Node] = field(default_factory=list)
    rand_shapes: List[Tuple[Tuple[int, ...], np.dtype]] = field(default_factory=list)
    # output base uid -> ordered write list: ("whole"|"window", slot, view)
    epilogue: Dict[int, List[Tuple[str, int, Optional[View]]]] = field(default_factory=dict)
    inputs: List[int] = field(default_factory=list)
    outputs: List[int] = field(default_factory=list)
    #: base uid -> (shape, dtype): outputs leave in the base's shape
    base_meta: Dict[int, Tuple[Tuple[int, ...], np.dtype]] = field(
        default_factory=dict)

    @property
    def R_pad(self) -> int:
        return self.G * self.TR


def _whole(v: View) -> bool:
    return v.offset == 0 and v.size == v.base.size and v.is_contiguous()


def _plannable(v: View) -> bool:
    return _whole(v) or _slice_plan(v) is not None


def _classify(v: View, domain: Tuple[int, ...]):
    """Map a domain-shaped view to (kind, core_view, bcast_dims).

    ``core_view`` is what is extracted from the flat base outside the
    kernel; ``kind`` is how it streams into the kernel.  Raises for views
    that would need a gather.
    """
    sh, st = v.shape, v.strides
    if len(domain) == 0 or v.size == 1:
        core = View(v.base, v.offset, (1,), (1,))
        return "scalar", core, ()
    bdims = tuple(j for j in range(len(sh)) if st[j] == 0 and sh[j] > 1)
    real = tuple(j for j in range(len(sh)) if sh[j] > 1)
    if not bdims:
        kind, core = "dense", v
    elif len(bdims) == len(real):
        kind, core = "scalar", View(v.base, v.offset, (1,), (1,))
    elif len(sh) >= 2 and set(bdims) == {j for j in real if j < len(sh) - 1}:
        kind, core = "row", View(v.base, v.offset, (sh[-1],), (st[-1],))
    elif len(sh) >= 2 and bdims == (len(sh) - 1,):
        kind, core = "col", View(v.base, v.offset, sh[:-1], st[:-1])
    else:   # partial broadcast over ≥3-D: extract core, broadcast outside
        keep = tuple(j for j in range(len(sh)) if j not in bdims)
        core = View(v.base, v.offset, tuple(sh[j] for j in keep),
                    tuple(st[j] for j in keep))
        if not _plannable(core):
            raise FusedBlockUnsupported("irregular_view", repr(v))
        return "dense", core, bdims
    if not _plannable(core):
        raise FusedBlockUnsupported("irregular_view", repr(v))
    return kind, core, ()


def _analyze(ops: Sequence[Op]) -> _Plan:
    work = [op for op in ops if not op.is_system()]
    if not work:
        raise FusedBlockUnsupported("system_only")
    for op in work:
        oc = op.opcode
        if oc in COMM_OPS:
            raise FusedBlockUnsupported("comm", oc)
        if (oc not in _UNARY and oc not in _BINARY and oc not in REDUCTIONS
                and oc not in ("where", "random", "range", "gather")):
            raise FusedBlockUnsupported("opcode", oc)
    domain = work[0].domain
    for op in work:
        if op.domain != domain:
            raise FusedBlockUnsupported(
                "mixed_domain", f"{op.domain} vs {domain}")
        ivs = op.in_views()
        if op.opcode == "gather":
            # supported form: 1-D whole-base table, axis 0 (or None), output
            # shaped like the index — each output element loads exactly one
            # table element, so the iteration domain is the INDEX view and
            # the table streams in whole (constant-index-map block).  The
            # table view is therefore exempt from the domain-shape check.
            tv = op.inputs[0] if op.inputs else None
            iv = op.inputs[1] if len(op.inputs) > 1 else None
            axis = op.axis
            if not isinstance(tv, View) or not isinstance(iv, View):
                raise FusedBlockUnsupported("gather_form", "literal operand")
            if axis not in (0, None) or len(tv.shape) != 1:
                raise FusedBlockUnsupported(
                    "gather_form", f"axis={axis} table={tv.shape}")
            if not _whole(tv):
                raise FusedBlockUnsupported(
                    "gather_form", f"partial table view {tv!r}")
            if op.out.shape != iv.shape:
                raise FusedBlockUnsupported(
                    "gather_form", f"out {op.out.shape} vs idx {iv.shape}")
            ivs = tuple(v for v in ivs if v is not tv)
        for v in ivs:
            if v.shape != domain:       # frontend broadcasts; hand tapes may not
                raise FusedBlockUnsupported(
                    "mixed_domain", f"input {v.shape} vs domain {domain}")
    N = math.prod(domain) if domain else 1
    if N == 0:
        raise FusedBlockUnsupported("empty_domain")
    if N >= 2 ** 31:
        raise FusedBlockUnsupported("vmem", "domain exceeds 32-bit indexing")

    one_d = len(domain) == 1
    if len(domain) == 0:
        R, C = 1, 1
    elif one_d:
        C = min(ONE_D_COLS, _round_up(N, LANE))
        R = -(-N // C)
    else:
        C = domain[-1]
        R = N // C

    inputs, outputs, _contracted = block_io(ops)
    input_set, output_set = set(inputs), set(outputs)
    plan = _Plan(domain=domain, N=N, R=R, C=C, TR=1, G=1, one_d=one_d,
                 inputs=list(inputs), outputs=list(outputs))
    for op in work:
        for v in (*op.in_views(), *op.out_views()):
            plan.base_meta[v.base.uid] = (v.base.shape, v.base.dtype)

    op_index: Dict[Tuple, int] = {}
    dense_slot: Dict[int, int] = {}             # output base -> shared slot
    writes: Dict[int, List[Tuple[View, int, bool]]] = {}
    n_written = set()                           # bases written by any node

    def operand_for(v: View, source: str, rand_pos: int = -1) -> int:
        kind, core, bdims = _classify(v, domain)
        key = (source, v.base.uid if source != "random" else rand_pos,
               v.offset, v.shape, v.strides)
        idx = op_index.get(key)
        if idx is None:
            idx = len(plan.operands)
            plan.operands.append(_Operand(
                key=key, kind=kind, source=source, base_uid=v.base.uid,
                core=core, bcast_dims=bdims, rand_pos=rand_pos))
            op_index[key] = idx
        return idx

    def table_operand_for(v: View) -> int:
        # the gather's table: streamed WHOLE into every grid step (constant
        # index map) — never tiled by the domain, so it bypasses _classify.
        # Fusion legality guarantees no in-block write overlaps it.
        key = ("table", v.base.uid, v.offset, v.shape, v.strides)
        idx = op_index.get(key)
        if idx is None:
            idx = len(plan.operands)
            source = "buffer" if v.base.uid in input_set else "zeros"
            plan.operands.append(_Operand(
                key=key, kind="table", source=source, base_uid=v.base.uid,
                core=v))
            op_index[key] = idx
        return idx

    def resolve_read(v: View) -> Tuple:
        u = v.base.uid
        for wview, nidx, is_red in reversed(writes.get(u, [])):
            if wview.identical(v):
                if is_red:
                    raise FusedBlockUnsupported(
                        "view_conflict", "read of in-block reduction output")
                return ("val", nidx)
            if wview.overlaps(v):
                raise FusedBlockUnsupported(
                    "view_conflict", f"read {v!r} overlaps prior write {wview!r}")
        source = "buffer" if u in input_set else "zeros"
        return ("op", operand_for(v, source))

    for op in work:
        oc = op.opcode
        nidx = len(plan.nodes)
        ov = op.out

        if oc == "random":
            rand_pos = len(plan.rand_shapes)
            plan.rand_shapes.append((ov.shape, ov.dtype))
            terms = (("op", operand_for(ov, "random", rand_pos)),)
        elif oc == "range":
            terms = ()
        elif oc in REDUCTIONS:
            terms = (resolve_read(op.in_views()[0]),)
        elif oc == "gather":
            terms = (("op", table_operand_for(op.inputs[0])),
                     resolve_read(op.inputs[1]))
        else:
            # literals pass through unconverted: make_block_fn feeds the raw
            # Python scalar to jnp, so coercing (e.g. int -> float) here
            # would change type promotion and break bit-identity
            terms = tuple(
                resolve_read(t) if isinstance(t, View) else ("lit", t)
                for t in op.inputs)

        node = _Node(opcode=oc, terms=terms, out_dtype=ov.dtype)
        u = ov.base.uid

        if oc in REDUCTIONS:
            axis = op.axis
            if axis is not None and axis < 0:
                axis += len(domain)
            if len(domain) == 1 and axis in (0, None):
                kind = "full"
            elif len(domain) >= 2 and axis == len(domain) - 1:
                kind = "col"
            elif len(domain) == 2 and axis == 0:
                kind = "row"
            else:
                raise FusedBlockUnsupported(
                    "reduction_axis", f"axis={axis} over domain {domain}")
            if not _whole(ov) or (kind == "col" and ov.shape != domain[:-1]) \
                    or (kind == "row" and ov.shape != domain[1:]) \
                    or (kind == "full" and ov.size != 1):
                raise FusedBlockUnsupported("reduction_out", repr(ov))
            node.red_kind = kind
            if u in output_set:
                node.out_slot = len(plan.slots)
                # accumulate in the INPUT dtype; the epilogue casts once to
                # the output base dtype, exactly like the XLA path's
                # reduce-then-write (premature per-slab narrowing would
                # exceed the documented reassociation tolerance)
                plan.slots.append(_Slot(
                    kind=f"red_{kind}", dtype=op.in_views()[0].dtype,
                    base_uid=u))
                plan.epilogue.setdefault(u, []).append(
                    ("whole", node.out_slot, None))
            writes.setdefault(u, []).append((ov, nidx, True))
        else:
            if _whole(ov):
                if u in output_set:
                    slot = dense_slot.get(u)
                    if slot is None:
                        slot = len(plan.slots)
                        plan.slots.append(_Slot(kind="dense", dtype=ov.dtype,
                                                base_uid=u))
                        dense_slot[u] = slot
                    node.out_slot = slot
                    plan.epilogue.setdefault(u, []).append(("whole", slot, None))
            else:
                if any(s == 0 and n > 1 for n, s in zip(ov.shape, ov.strides)) \
                        or not _plannable(ov):
                    raise FusedBlockUnsupported("irregular_view", repr(ov))
                # window write: computed in-kernel, scattered by the epilogue.
                # Slot created even for contracted bases so expressibility
                # stays DEL-insensitive; unused slots cost one dead store.
                node.out_slot = len(plan.slots)
                plan.slots.append(_Slot(kind="window", dtype=ov.dtype,
                                        base_uid=u, view=ov))
                if u in output_set:
                    plan.epilogue.setdefault(u, []).append(
                        ("window", node.out_slot, ov))
            writes.setdefault(u, []).append((ov, nidx, False))
        n_written.add(u)
        plan.nodes.append(node)

    # -- layout: a block without row structure (no broadcast rows/columns,
    # no reductions) computes on the flat domain in lane-dense rows, like a
    # 1-D domain.  A minor dim that is not a multiple of LANE would make
    # XLA relayout every operand into padded (R, C) tiles, and the TPU
    # compiler's time for such a relayout grows with its size.
    if (len(domain) >= 2 and plan.C % LANE
            and all(o.kind in ("dense", "scalar") and not o.bcast_dims
                    for o in plan.operands)
            and not any(nd.red_kind for nd in plan.nodes)):
        plan.one_d = True
        plan.C = min(ONE_D_COLS, _round_up(N, LANE))
        plan.R = -(-N // plan.C)

    # -- tiling: shrink the row slab until one grid step fits VMEM ---------
    itemsize = max((np.dtype(dt).itemsize
                    for _, dt in plan.base_meta.values()), default=8)
    R, C = plan.R, plan.C

    def step_blocks(tr: int) -> List[Tuple[int, int]]:
        blocks = []
        for o in plan.operands:
            if o.kind == "table":       # whole table resident per grid step
                blocks.append((1, o.core.size))
            else:
                blocks.append({"dense": (tr, C), "row": (1, C),
                               "col": (tr, 1), "scalar": (1, 1)}[o.kind])
        for sl in plan.slots:
            blocks.append({"dense": (tr, C), "window": (tr, C),
                           "red_full": (1, 1), "red_row": (1, C),
                           "red_col": (tr, 1)}[sl.kind])
        return blocks

    plan.TR = row_tile(R, C, step_blocks, len(plan.nodes), itemsize)
    plan.G = -(-R // plan.TR)
    return plan


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of one ``(rows, cols)`` block: Mosaic lays the last two
    dims out in whole ``(SUBLANE, LANE)`` tiles."""
    return _round_up(rows, SUBLANE) * _round_up(cols, LANE) * itemsize


def step_vmem_bytes(tr: int, C: int, step_blocks, n_live: int,
                    itemsize: int) -> int:
    """VMEM one grid step needs: every pipelined in/out block twice (Pallas
    double-buffers them so the next step's DMA overlaps this step's
    compute) plus ``n_live`` slab-shaped values live in the kernel body."""
    io = sum(_tile_bytes(r, c, itemsize) for r, c in step_blocks(tr))
    return 2 * io + n_live * _tile_bytes(tr, C, itemsize)


def row_tile(R: int, C: int, step_blocks, n_live: int, itemsize: int) -> int:
    """Rows per grid step of an ``(R, C)`` domain tiled as ``(TR, C)`` slabs.

    Mosaic accepts a block whose second-minor dim is a multiple of
    ``SUBLANE`` or the whole array dim, so TR is one or the other: about
    ``TILE_ELEMS`` elements per slab, halved (in whole sublane groups)
    until :func:`step_vmem_bytes` fits ``VMEM_BUDGET``.  Raises the
    ``vmem`` slug when even an 8-row slab set does not fit — the columns
    would have to be tiled, which this codegen does not do."""
    tr = max(SUBLANE, (TILE_ELEMS // max(C, 1)) // SUBLANE * SUBLANE)
    if tr >= R:
        tr = R

    def fits(t: int) -> bool:
        return step_vmem_bytes(t, C, step_blocks, n_live,
                               itemsize) <= VMEM_BUDGET

    while not fits(tr):
        if tr <= SUBLANE:
            need = step_vmem_bytes(tr, C, step_blocks, n_live, itemsize)
            raise FusedBlockUnsupported(
                "vmem", f"{need} bytes at TR={tr}, C={C}")
        tr = max(SUBLANE, (tr // 2) // SUBLANE * SUBLANE)
    return tr


def block_lower_reason(ops: Sequence[Op]) -> Optional[str]:
    """``None`` when the block lowers through the Pallas codegen, else the
    fallback reason slug.  Pure analysis — never traces, never raises — so
    cost models can call it while pricing candidate merges."""
    try:
        _analyze(ops)
        return None
    except FusedBlockUnsupported as e:
        return e.reason
    except Exception:               # defensive: analysis bug != crash
        return "error"


def mosaic_reason(ops: Sequence[Op]) -> Optional[str]:
    """Slug for what Mosaic, the TPU kernel compiler, refuses although the
    interpreter runs it: 64-bit element types (``mosaic_x64``), the
    in-kernel gather (``mosaic_gather``) and :data:`MOSAIC_OPCODES`
    (``mosaic_opcode``).  ``None`` when none occurs.  The Pallas backends
    decline with it whenever they build compiled kernels, so such blocks
    run on XLA by decision, never by a crash."""
    work = [op for op in ops if not op.is_system()]
    if any(op.opcode == "gather" for op in work):
        return "mosaic_gather"
    if any(op.opcode in MOSAIC_OPCODES for op in work):
        return "mosaic_opcode"
    for op in work:
        for v in (*op.in_views(), *op.out_views()):
            if v.base.dtype.itemsize == 8:
                return "mosaic_x64"
    return None


# ---------------------------------------------------------------------------
# Builder
# ---------------------------------------------------------------------------

def row_map(i):
    """Index map of a row-slab block: grid step ``i`` owns slab ``i``.
    The block indices are int32 — under x64 a Python ``0`` would become an
    int64 that Mosaic cannot legalize."""
    return i, jnp.int32(0)


def fixed_map(i):
    """Index map of a block every grid step revisits (broadcast operands,
    whole tables, reduction accumulators)."""
    return jnp.int32(0), jnp.int32(0)


def lift_literals(node, args: List) -> List:
    """Kernel-body operands of one node: a value operand that is a Python
    literal takes the node's dtype when no array operand would give it
    one.  Under x64 a weak literal alone (``copy 100.0``,
    ``where(m, 1.0, 0.0)``) is float64 in-kernel, which aborts Mosaic's
    layout pass; lifting it changes no bits, because the XLA path's only
    arithmetic on such a value is the final cast to the output dtype.
    Literals next to an array operand are left to weak-type promotion,
    exactly as in ``make_block_fn``."""
    first = 1 if node.opcode == "where" else 0      # the mask is no value
    if any(tag != "lit" for tag, _ in node.terms[first:]):
        return args
    return [jnp.asarray(a, node.out_dtype)
            if k >= first and tag == "lit" else a
            for k, ((tag, _), a) in enumerate(zip(node.terms, args))]


def _red_identity(oc: str, dtype) -> jnp.ndarray:
    dt = np.dtype(dtype)
    if oc == "reduce_sum":
        return jnp.asarray(0, dt)
    if oc == "reduce_prod":
        return jnp.asarray(1, dt)
    big = (np.inf if dt.kind == "f"
           else np.iinfo(dt).max if dt.kind in "iu" else True)
    small = (-np.inf if dt.kind == "f"
             else np.iinfo(dt).min if dt.kind in "iu" else False)
    return jnp.asarray(small if oc == "reduce_max" else big, dt)


def build_block_kernel(ops: Sequence[Op], *, seed: int = 0,
                       interpret: Optional[bool] = None):
    """Compile a WSP block into one tiled Pallas kernel.

    Returns ``(fn, input_uids, output_uids)`` where
    ``fn(*flat_input_bufs, salts) -> tuple(flat_output_bufs)`` mirrors the
    :func:`repro.core.executor.make_block_fn` calling convention (``salts``
    feeds any ``random`` ops).  Raises :class:`FusedBlockUnsupported` (with
    a ``reason`` slug) for blocks the tiler cannot express."""
    p = _analyze(ops)
    R, C, TR, G, N = p.R, p.C, p.TR, p.G, p.N
    R_pad = p.R_pad
    n_in = len(p.operands)
    input_set = set(p.inputs)

    in_specs, out_specs, out_shapes = [], [], []
    for o in p.operands:
        if o.kind == "table":
            # the whole table in one constant-index-map block: every grid
            # step sees the full array (full VMEM residency, priced by the
            # budget check above and the cost models' gather term)
            shape, idx = (1, o.core.size), fixed_map
        else:
            shape, idx = {
                "dense": ((TR, C), row_map),
                "row": ((1, C), fixed_map),
                "col": ((TR, 1), row_map),
                "scalar": ((1, 1), fixed_map),
            }[o.kind]
        in_specs.append(pl.BlockSpec(shape, idx))
    for s in p.slots:
        shape, idx, full = {
            "dense": ((TR, C), row_map, (R_pad, C)),
            "window": ((TR, C), row_map, (R_pad, C)),
            "red_full": ((1, 1), fixed_map, (1, 1)),
            "red_row": ((1, C), fixed_map, (1, C)),
            "red_col": ((TR, 1), row_map, (R_pad, 1)),
        }[s.kind]
        out_specs.append(pl.BlockSpec(shape, idx))
        out_shapes.append(jax.ShapeDtypeStruct(full, s.dtype))

    def kernel(*refs):
        i = pl.program_id(0)
        loaded = [r[...] for r in refs[:n_in]]
        out_refs = refs[n_in:]
        vals: Dict[int, jnp.ndarray] = {}

        def resolve(term):
            tag, x = term
            if tag == "lit":
                return x
            if tag == "op":
                return loaded[x]
            return vals[x]

        for k, node in enumerate(p.nodes):
            oc = node.opcode
            args = lift_literals(node, [resolve(t) for t in node.terms])
            if node.red_kind is not None:
                x = jnp.broadcast_to(args[0], (TR, C))
                if node.red_kind == "col":
                    part = _REDUCE_FN[oc](x, axis=1)
                    if node.out_slot is not None:
                        out_refs[node.out_slot][...] = part.reshape(TR, 1) \
                            .astype(p.slots[node.out_slot].dtype)
                else:
                    padded = (R_pad * C != N) if node.red_kind == "full" \
                        else (R_pad != R)
                    if padded:
                        rows = jax.lax.broadcasted_iota(jnp.int32, (TR, C), 0)
                        cols = jax.lax.broadcasted_iota(jnp.int32, (TR, C), 1)
                        if node.red_kind == "full":
                            valid = (i * TR + rows) * C + cols < N
                        else:
                            valid = (i * TR + rows) < R
                        x = jnp.where(valid, x, _red_identity(oc, x.dtype))
                    if node.red_kind == "full":
                        part = _REDUCE_FN[oc](x).reshape(1, 1)
                    else:
                        part = _REDUCE_FN[oc](x, axis=0).reshape(1, C)
                    if node.out_slot is not None:
                        part = part.astype(p.slots[node.out_slot].dtype)
                        oref = out_refs[node.out_slot]
                        if G == 1:
                            oref[...] = part
                        else:
                            @pl.when(i == 0)
                            def _init(oref=oref, part=part):
                                oref[...] = part

                            @pl.when(i > 0)
                            def _acc(oref=oref, part=part, oc=oc):
                                oref[...] = _COMBINE[oc](oref[...], part)
                continue
            if oc == "range":
                rows = jax.lax.broadcasted_iota(jnp.int32, (TR, C), 0)
                cols = jax.lax.broadcasted_iota(jnp.int32, (TR, C), 1)
                val = (i * TR + rows) * C + cols
            elif oc == "gather":
                # same expression as the XLA fallback (executor.make_block_fn)
                # so the in-kernel index load stays bit-identical; padded
                # index lanes read table[0] harmlessly (epilogue keeps [:N])
                tbl = args[0].reshape(-1)
                idxs = jnp.broadcast_to(args[1], (TR, C)).astype(jnp.int32)
                val = jnp.take(tbl, idxs, axis=0)
            elif oc == "random":
                val = args[0]
            elif oc in _UNARY:
                val = _UNARY[oc](*args)
            elif oc in _BINARY:
                val = _BINARY[oc](*args)
            else:
                val = jnp.where(*args)
            val = jnp.broadcast_to(val, (TR, C)).astype(node.out_dtype)
            vals[k] = val
            if node.out_slot is not None:
                out_refs[node.out_slot][...] = val

    call = pl.pallas_call(kernel, grid=(G,), in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shapes,
                          interpret=resolve_interpret(interpret))

    def _shape_operand(o: _Operand, store, rvals) -> jnp.ndarray:
        if o.source == "random":
            core = rvals[o.rand_pos].reshape(-1)
        elif o.source == "zeros":
            size, dt = o.core.size, o.core.dtype
            core = jnp.zeros((size,), dt).reshape(o.core.shape)
        else:
            # analysis checked _plannable(core), so _read never takes its
            # gather branch here — whole-base reshape or reshape+slice only
            core = _read(store[o.base_uid], o.core)
        if o.kind == "table":
            return core.reshape(1, -1)
        if o.kind == "scalar":
            return core.reshape(1, 1)
        if o.kind == "row":
            return core.reshape(1, C)
        if o.kind == "col":
            flat = core.reshape(-1)
            return jnp.pad(flat, (0, R_pad - R)).reshape(R_pad, 1)
        if o.bcast_dims:                        # mixed partial broadcast
            core = jnp.expand_dims(core, o.bcast_dims)
            core = jnp.broadcast_to(core, p.domain)
        flat = core.reshape(-1)
        return jnp.pad(flat, (0, R_pad * C - flat.shape[0])).reshape(R_pad, C)

    def fn(*bufs_and_salts):
        *bufs, salts = bufs_and_salts
        store = dict(zip(p.inputs, bufs))
        rvals = []
        for j, (shape, dt) in enumerate(p.rand_shapes):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), salts[j])
            rvals.append(jax.random.uniform(key, shape, dtype=dt))
        outs = call(*[_shape_operand(o, store, rvals) for o in p.operands])
        final: Dict[int, jnp.ndarray] = {}
        for u in p.outputs:
            shape, dt = p.base_meta[u]
            cur = store[u] if u in input_set else jnp.zeros(shape, dt)
            for wkind, slot, view in p.epilogue.get(u, []):
                raw = outs[slot].reshape(-1)
                if wkind == "whole":
                    # reductions accumulate in input dtype; cast once here
                    cur = raw[:math.prod(shape)].astype(dt).reshape(shape)
                else:
                    cur = _write(cur, view, raw[:N].reshape(p.domain))
            final[u] = cur
        return tuple(final[u] for u in p.outputs)

    return fn, list(p.inputs), list(p.outputs)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
