"""The ``shard_map`` lowering backend — multi-device blocks with real
collectives (DESIGN.md §12, §14).

Extracted from the former ``DistBlockExecutor`` subclass so the distributed
path is a *peer* backend the lower stage selects per block: blocks that
touch sharded bases lower through ``jax.shard_map`` over a 1-D device mesh
— sharded bases enter as per-device chunks (``P(axis)`` on the buffer's
leading dimension; dim-0 block sharding keeps chunks contiguous, and each
is flattened on entry and leaves in its base's shape), replicated bases
enter whole, and COMM ops become real collectives (``all_gather`` for
allgather/ppermute resharding, shard-local slices for placement casts).
Identical COMM ops inside one block execute as ONE collective — the
backend realizes the elision the ``comm`` cost model priced.

``claims`` is the static eligibility check: blocks the shard tiler cannot
express (strided/partial views, reductions, opaque ops, foreign shardings)
and purely replicated blocks are declined with a reason slug and fall to
the next backend in the policy, where COMM ops execute as local identity
copies — results are bit-identical to the single-device path by
construction.

All dist-layer imports are function-local: the backends package must stay
importable from ``core.executor`` without touching ``core.dist`` (whose
package init imports the executor back).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

from .base import LoweringBackend, LoweringContext

#: claim-failure slugs (per-backend fallback stats; DESIGN.md §14)
REASONS = (
    "no_mesh",         # executor has no device mesh
    "system_only",     # nothing to dispatch
    "opcode",          # op outside the shard tiler's elementwise/COMM set
    "irregular_view",  # strided/partial view: chunks not contiguous
    "placement",       # foreign/misaligned sharding, or purely replicated
)


def shard_specs(work: Sequence, n_dev: int) -> Tuple[Optional[Dict], Optional[str]]:
    """Static eligibility check; returns ``({base uid: ShardSpec|None},
    None)`` when the block is expressible as one shard_map program, else
    ``(None, reason)``."""
    from ..executor import _BINARY, _UNARY
    from ..ir import COMM_OPS
    from ..dist.spec import spec_of

    if not work:
        return None, "system_only"
    specs: Dict[int, object] = {}
    any_sharded = False
    for op in work:
        oc = op.opcode
        if oc not in _UNARY and oc not in _BINARY and oc != "where" \
                and oc not in COMM_OPS:
            return None, "opcode"
        for v in (*op.in_views(), *op.out_views()):
            if not (v.offset == 0 and v.size == v.base.size
                    and v.is_contiguous()):
                return None, "irregular_view"
            s = spec_of(v.base)
            if s is not None:
                if (s.sharded_dim != 0 or not s.divides()
                        or s.n_shards != n_dev
                        or not v.base.shape
                        or v.base.shape[0] % n_dev != 0):
                    return None, "placement"
                any_sharded = True
            specs[v.base.uid] = s
    if not any_sharded:
        return None, "placement"
    for op in work:              # replicated outputs need replicated inputs
        if op.opcode in COMM_OPS:
            continue
        so = specs[op.out.base.uid]
        for v in op.in_views():
            si = specs[v.base.uid]
            if si is not None and (so is None or si.placement_key()
                                   != so.placement_key()):
                return None, "placement"  # reshard pass normally prevents
    return specs, None


class ShardMapBackend(LoweringBackend):
    name = "shard_map"
    donates = True

    def claims(self, ops: Sequence, plan, ctx: LoweringContext) -> Optional[str]:
        if ctx.mesh is None:
            return "no_mesh"
        work = [op for op in ops if not op.is_system()]
        _, reason = shard_specs(work, ctx.n_dev)
        return reason

    def cache_token(self, ops: Sequence, plan, ctx: LoweringContext) -> Tuple:
        from ..dist.spec import placement_digest
        return (placement_digest(ops),)

    def build(self, ops: Sequence, plan, ctx: LoweringContext):
        import jax
        import jax.numpy as jnp
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        from ..executor import _BINARY, _UNARY, _base_meta, block_io
        from ..ir import COMM_OPS, View
        from ..dist.reshard import _comm_key

        work = [op for op in ops if not op.is_system()]
        specs, reason = shard_specs(work, ctx.n_dev)
        assert specs is not None, f"build without claim: {reason}"
        inputs, outputs, _ = block_io(ops)
        meta = _base_meta(work)
        n_dev, axis = ctx.n_dev, ctx.axis
        chunk = {u: math.prod(shape) // n_dev
                 for u, (shape, _) in meta.items()}

        def local_shape(u):
            """A device's part of base ``u``'s buffer (its shape, or its
            leading dimension's chunk when sharded)."""
            shape = meta[u][0]
            if specs.get(u) is None:
                return shape
            return (shape[0] // n_dev,) + tuple(shape[1:])

        def shard_of(val, u):
            idx = jax.lax.axis_index(axis)
            return jax.lax.dynamic_slice_in_dim(val, idx * chunk[u], chunk[u])

        def pershard(*bufs):
            env: Dict[int, jnp.ndarray] = {u: b.reshape(-1)
                                           for u, b in zip(inputs, bufs)}
            for u, (shape, dt) in meta.items():
                if u not in env:
                    env[u] = jnp.zeros((math.prod(local_shape(u)),), dt)
            issued: Dict[Tuple, jnp.ndarray] = {}
            for op in work:
                oc = op.opcode
                ou = op.out.base.uid
                dt = meta[ou][1]
                if oc in COMM_OPS:
                    key = _comm_key(op)
                    val = issued.get(key)
                    if val is None:           # ONE collective per identity
                        su = op.in_views()[0].base.uid
                        if oc == "comm_allgather":
                            val = jax.lax.all_gather(env[su], axis, tiled=True)
                        elif oc == "comm_ppermute":
                            full = jax.lax.all_gather(env[su], axis, tiled=True)
                            val = shard_of(full, ou)
                        else:                 # reduce_scatter placement cast
                            val = shard_of(env[su], ou)
                        issued[key] = val
                    env[ou] = val.astype(dt)
                    continue
                sharded_out = specs.get(ou) is not None
                ins = []
                for v in op.inputs:
                    if not isinstance(v, View):
                        ins.append(v)
                        continue
                    x = env[v.base.uid]
                    if sharded_out and specs.get(v.base.uid) is None:
                        x = shard_of(x, v.base.uid)   # replicated → my chunk
                    ins.append(x)
                if oc in _UNARY:
                    val = _UNARY[oc](*ins)
                elif oc in _BINARY:
                    val = _BINARY[oc](*ins)
                else:
                    val = jnp.where(*ins)
                local = math.prod(local_shape(ou))
                env[ou] = jnp.broadcast_to(jnp.asarray(val, dt), (local,))
            return tuple(env[u].reshape(local_shape(u)) for u in outputs)

        pspec = lambda u: P(axis) if specs.get(u) is not None else P()  # noqa: E731
        mapped = shard_map(pershard, mesh=ctx.mesh,
                           in_specs=tuple(pspec(u) for u in inputs),
                           out_specs=tuple(pspec(u) for u in outputs),
                           check_rep=False)
        return lambda *a: mapped(*a[:-1])     # drop the RNG salts argument

    def post_dispatch(self, ops: Sequence, plan, ctx: LoweringContext,
                      stats: Dict) -> None:
        """Collectives/fabric bytes are counted only for dispatches that
        actually lowered through shard_map — on other backends COMM ops
        execute as local identity copies and move nothing."""
        from ..dist.reshard import _comm_key, block_comm_bytes
        from ..ir import COMM_OPS
        n_comms = len({_comm_key(op) for op in ops if op.opcode in COMM_OPS})
        if n_comms:
            # atomic inc on the live StatsView when available (concurrent
            # flushes, DESIGN.md §18); plain dicts keep the legacy idiom
            inc = getattr(stats, "inc", None)
            if inc is not None:
                inc("collectives", n_comms)
                inc("interconnect_bytes", block_comm_bytes(ops))
            else:
                stats["collectives"] = stats.get("collectives", 0) + n_comms
                stats["interconnect_bytes"] = (
                    stats.get("interconnect_bytes", 0.0)
                    + block_comm_bytes(ops))
