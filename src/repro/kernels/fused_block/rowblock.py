"""Row-replay Pallas codegen — the lowering engine behind the ``rowblock``
backend (``core/backends/lm.py``).

The generic tiler (``codegen.py``) refuses any block that READS an
in-block reduction output (``view_conflict``): its grid may split a
reduction across slabs, so the reduced value is not available in-register
when a later op wants it.  But the LM blocks ``rowblock`` claims —
masked softmax, rmsnorm, exponential scans — are exactly reductions whose
results feed later ops *in the same block* (``exp(x - max)``,
``x * rsqrt(mean)``).  This generator closes that gap for the one shape
those blocks share: a **trailing-axis** reduction over a 2-D+ domain,
consumed at domain shape through a stride-0 broadcast of the reduced
value.

The key observation: canonicalize the domain to ``(R, C)`` with ``C`` the
full innermost axis, tile as ``(TR, C)`` row slabs, and every reduction
row is COMPLETE within its slab — ``jnp.max/sum(x, axis=1)`` yields the
finished ``(TR, 1)`` value in-register, no cross-slab accumulator, no
identity-masked padding (padded rows compute garbage the epilogue
discards).  A later read of the reduction output resolves to
``jnp.broadcast_to(val, (TR, C))`` when its view is the reduction's write
view with a stride-0 axis appended — exactly the
``var.reshape(b, s, 1).broadcast_to((b, s, d))`` pattern the lazy
frontend records — replaying the same jnp ops the XLA fallback
(``make_block_fn``) runs, in the same per-row order, so results agree
with the fallback's up to float32 rounding.

Everything else (operand classification, slice-planned views, VMEM
budgeting, the ``fn(*bufs, salts)`` calling convention) is shared with
``codegen.py``; unsupported shapes raise :class:`FusedBlockUnsupported`
with the same reason slugs so backend decline stats stay comparable.
Deliberately NOT supported (the generic tiler or XLA handle them):
``random``/``range``/``gather``/comm ops, window (partial-view) writes,
1-D domains, non-trailing reduction axes.
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
from ...core.executor import (_BINARY, _REDUCE as _REDUCE_FN, _UNARY, _read,
                              block_io)
from ...core.ir import COMM_OPS, REDUCTIONS, Op, View
from .codegen import (FusedBlockUnsupported, _Operand, _classify, _whole,
                      fixed_map, lift_literals, row_map, row_tile)


@dataclass
class _Node:
    """One work op, resolved against operands / earlier nodes."""

    opcode: str
    # ("lit", x) | ("op", operand_idx) | ("val", node_idx) | ("red", node_idx)
    terms: Tuple
    out_dtype: np.dtype
    is_red: bool = False
    out_slot: Optional[int] = None


@dataclass
class _RowPlan:
    domain: Tuple[int, ...]
    N: int
    R: int
    C: int
    TR: int = 1
    G: int = 1
    operands: List[_Operand] = field(default_factory=list)
    # (kind, dtype, base_uid): kind "dense" (TR, C) or "red" (TR, 1)
    slots: List[Tuple[str, np.dtype, int]] = field(default_factory=list)
    nodes: List[_Node] = field(default_factory=list)
    inputs: List[int] = field(default_factory=list)
    outputs: List[int] = field(default_factory=list)
    #: base uid -> (shape, dtype): outputs leave in the base's shape
    base_meta: Dict[int, Tuple[Tuple[int, ...], np.dtype]] = field(
        default_factory=dict)

    @property
    def R_pad(self) -> int:
        return self.G * self.TR


def _analyze(ops: Sequence[Op]) -> _RowPlan:
    work = [op for op in ops if not op.is_system()]
    if not work:
        raise FusedBlockUnsupported("system_only")
    for op in work:
        oc = op.opcode
        if oc in COMM_OPS:
            raise FusedBlockUnsupported("comm", oc)
        if (oc not in _UNARY and oc not in _BINARY
                and oc not in REDUCTIONS and oc != "where"):
            raise FusedBlockUnsupported("opcode", oc)
    domain = work[0].domain
    if len(domain) < 2:
        raise FusedBlockUnsupported(
            "reduction_axis", f"row codegen needs a 2-D+ domain, got {domain}")
    for op in work:
        if op.domain != domain:
            raise FusedBlockUnsupported(
                "mixed_domain", f"{op.domain} vs {domain}")
        for v in op.in_views():
            if v.shape != domain:
                raise FusedBlockUnsupported(
                    "mixed_domain", f"input {v.shape} vs domain {domain}")
    N = math.prod(domain)
    if N == 0:
        raise FusedBlockUnsupported("empty_domain")
    if N >= 2 ** 31:
        raise FusedBlockUnsupported("vmem", "domain exceeds 32-bit indexing")
    C = domain[-1]
    R = N // C

    inputs, outputs, _ = block_io(ops)
    input_set, output_set = set(inputs), set(outputs)
    plan = _RowPlan(domain=domain, N=N, R=R, C=C,
                    inputs=list(inputs), outputs=list(outputs))
    for op in work:
        for v in (*op.in_views(), *op.out_views()):
            plan.base_meta[v.base.uid] = (v.base.shape, v.base.dtype)

    op_index: Dict[Tuple, int] = {}
    dense_slot: Dict[int, int] = {}
    writes: Dict[int, List[Tuple[View, int, bool]]] = {}

    def operand_for(v: View, source: str) -> int:
        kind, core, bdims = _classify(v, domain)
        key = (source, v.base.uid, v.offset, v.shape, v.strides)
        idx = op_index.get(key)
        if idx is None:
            idx = len(plan.operands)
            plan.operands.append(_Operand(
                key=key, kind=kind, source=source, base_uid=v.base.uid,
                core=core, bcast_dims=bdims))
            op_index[key] = idx
        return idx

    def resolve_read(v: View) -> Tuple:
        u = v.base.uid
        for wview, nidx, is_red in reversed(writes.get(u, [])):
            if is_red:
                # the ONE consumption form this generator exists for: the
                # reduced (TR, 1) value broadcast back over the reduced axis
                stripped = View(v.base, v.offset, v.shape[:-1], v.strides[:-1])
                if (v.shape == domain and v.strides[-1] == 0
                        and stripped.identical(wview)):
                    return ("red", nidx)
                raise FusedBlockUnsupported(
                    "view_conflict",
                    f"read {v!r} of in-block reduction output {wview!r} "
                    "is not a trailing-axis broadcast of it")
            if wview.identical(v):
                return ("val", nidx)
            if wview.overlaps(v):
                raise FusedBlockUnsupported(
                    "view_conflict",
                    f"read {v!r} overlaps prior write {wview!r}")
        source = "buffer" if u in input_set else "zeros"
        return ("op", operand_for(v, source))

    for op in work:
        oc = op.opcode
        nidx = len(plan.nodes)
        ov = op.out
        u = ov.base.uid

        if oc in REDUCTIONS:
            axis = op.axis
            if axis is not None and axis < 0:
                axis += len(domain)
            if axis != len(domain) - 1:
                raise FusedBlockUnsupported(
                    "reduction_axis",
                    f"axis={op.axis} over domain {domain} (trailing only)")
            if not _whole(ov) or ov.shape != domain[:-1]:
                raise FusedBlockUnsupported("reduction_out", repr(ov))
            node = _Node(opcode=oc, terms=(resolve_read(op.in_views()[0]),),
                         out_dtype=ov.dtype, is_red=True)
            if u in output_set:
                node.out_slot = len(plan.slots)
                plan.slots.append(("red", ov.dtype, u))
            writes.setdefault(u, []).append((ov, nidx, True))
        else:
            terms = tuple(
                resolve_read(t) if isinstance(t, View) else ("lit", t)
                for t in op.inputs)
            node = _Node(opcode=oc, terms=terms, out_dtype=ov.dtype)
            if not _whole(ov):
                raise FusedBlockUnsupported("irregular_view", repr(ov))
            if u in output_set:
                slot = dense_slot.get(u)
                if slot is None:
                    slot = len(plan.slots)
                    plan.slots.append(("dense", ov.dtype, u))
                    dense_slot[u] = slot
                node.out_slot = slot
            writes.setdefault(u, []).append((ov, nidx, False))
        plan.nodes.append(node)

    # -- tiling: whole rows per slab, shrink until one grid step fits VMEM --
    itemsize = max((np.dtype(dt).itemsize
                    for _, dt in plan.base_meta.values()), default=8)

    def step_blocks(tr: int) -> List[Tuple[int, int]]:
        blocks = [{"dense": (tr, C), "row": (1, C), "col": (tr, 1),
                   "scalar": (1, 1)}[o.kind] for o in plan.operands]
        blocks += [(tr, C) if kind == "dense" else (tr, 1)
                   for kind, _, _ in plan.slots]
        return blocks

    plan.TR = row_tile(R, C, step_blocks, len(plan.nodes), itemsize)
    plan.G = -(-R // plan.TR)
    return plan


def rowblock_lower_reason(ops: Sequence[Op]) -> Optional[str]:
    """``None`` when the block lowers through the row-replay codegen, else
    the reason slug.  Pure analysis — never traces, never raises."""
    try:
        _analyze(ops)
        return None
    except FusedBlockUnsupported as e:
        return e.reason
    except Exception:               # defensive: analysis bug != crash
        return "error"


def build_rowblock_kernel(ops: Sequence[Op], *, seed: int = 0,
                          interpret: Optional[bool] = None):
    """Compile a reduction-consuming block into one row-tiled Pallas kernel.

    Returns ``(fn, input_uids, output_uids)`` with the ``make_block_fn``
    calling convention ``fn(*flat_input_bufs, salts) -> output_bufs``
    (``salts`` is accepted for uniformity and ignored — ``random`` ops are
    not claimed).  Raises :class:`FusedBlockUnsupported` for blocks the
    row tiler cannot express."""
    del seed  # no random ops — uniform signature with build_block_kernel
    p = _analyze(ops)
    R, C, TR, G = p.R, p.C, p.TR, p.G
    R_pad = p.R_pad
    n_in = len(p.operands)
    input_set = set(p.inputs)

    in_specs, out_specs, out_shapes = [], [], []
    for o in p.operands:
        shape, idx = {
            "dense": ((TR, C), row_map),
            "row": ((1, C), fixed_map),
            "col": ((TR, 1), row_map),
            "scalar": ((1, 1), fixed_map),
        }[o.kind]
        in_specs.append(pl.BlockSpec(shape, idx))
    for kind, dt, _ in p.slots:
        if kind == "dense":
            out_specs.append(pl.BlockSpec((TR, C), row_map))
            out_shapes.append(jax.ShapeDtypeStruct((R_pad, C), dt))
        else:                       # "red": the finished (TR, 1) row values
            out_specs.append(pl.BlockSpec((TR, 1), row_map))
            out_shapes.append(jax.ShapeDtypeStruct((R_pad, 1), dt))

    def kernel(*refs):
        loaded = [r[...] for r in refs[:n_in]]
        out_refs = refs[n_in:]
        vals: Dict[int, jnp.ndarray] = {}

        def resolve(term):
            tag, x = term
            if tag == "lit":
                return x
            if tag == "op":
                return loaded[x]
            if tag == "red":
                return jnp.broadcast_to(vals[x], (TR, C))
            return vals[x]

        for k, node in enumerate(p.nodes):
            oc = node.opcode
            args = lift_literals(node, [resolve(t) for t in node.terms])
            if node.is_red:
                x = jnp.broadcast_to(args[0], (TR, C))
                # rows are complete within the slab: the reduction finishes
                # here, in the same per-row order as the XLA fallback's
                # axis=-1 reduce (padded rows yield garbage the epilogue
                # drops — no identity masking needed)
                val = _REDUCE_FN[oc](x, axis=1).reshape(TR, 1) \
                    .astype(node.out_dtype)
            elif oc in _UNARY:
                val = _UNARY[oc](*args)
            elif oc in _BINARY:
                val = _BINARY[oc](*args)
            else:
                val = jnp.where(*args)
            if not node.is_red:
                val = jnp.broadcast_to(val, (TR, C)).astype(node.out_dtype)
            vals[k] = val
            if node.out_slot is not None:
                out_refs[node.out_slot][...] = val

    call = pl.pallas_call(kernel, grid=(G,), in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shapes,
                          interpret=resolve_interpret(interpret))

    def _shape_operand(o: _Operand, store) -> jnp.ndarray:
        if o.source == "zeros":
            core = jnp.zeros((o.core.size,), o.core.dtype) \
                .reshape(o.core.shape)
        else:
            core = _read(store[o.base_uid], o.core)
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
        *bufs, _salts = bufs_and_salts
        store = dict(zip(p.inputs, bufs))
        outs = call(*[_shape_operand(o, store) for o in p.operands])
        final: Dict[int, jnp.ndarray] = {}
        for slot, (kind, _, u) in enumerate(p.slots):
            shape, dt = p.base_meta[u]
            final[u] = (outs[slot].reshape(-1)[:math.prod(shape)].astype(dt)
                        .reshape(shape))
        return tuple(final[u] for u in p.outputs)

    return fn, list(p.inputs), list(p.outputs)
