"""Per-block JIT execution — the Bohrium backend analogue (paper §III final
phase: "the hardware specific backend JIT-compiles each block of array
operations and executes them").

Each partition block becomes ONE executable: `ext` arrays cross the block
boundary as function inputs/outputs (exactly the paper's cost), while
contracted arrays (``new∩del``) are local temporaries that never leave fast
memory — array contraction.  *Which* executable a block becomes is a
per-block lowering decision over the pluggable backend registry
(``repro.core.backends``, DESIGN.md §14): ``xla`` (the ``make_block_fn``
floor below), ``pallas`` (the tiled fused-block codegen) or ``shard_map``
(multi-device collectives).  ``BlockExecutor`` is the thin dispatch engine
over that registry.

Compiled block functions are cached on ``(backend, canonical structural
signature)``, so iterative workloads (the paper's merge-cache scenario,
§IV-F) re-dispatch the same executables every iteration.
"""

from __future__ import annotations

import os
import threading
import time
from collections.abc import Mapping
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# block_signature moved to ``repro.core.cache`` (memoized per-op templates);
# re-exported here because it began life as the executable-cache key and
# callers historically import it from the executor.
from .cache import block_signature                              # noqa: F401
from .ir import COMM_OPS, Op, View
from .obs import trace
from .obs.metrics import MetricsRegistry, StatsView

_UNARY = {
    "copy": lambda x: x, "sqrt": jnp.sqrt, "exp": jnp.exp, "log": jnp.log,
    "abs": jnp.abs, "neg": jnp.negative, "sin": jnp.sin, "cos": jnp.cos,
    "erf": jax.scipy.special.erf, "sign": jnp.sign, "rsqrt": jax.lax.rsqrt,
    "tanh": jnp.tanh, "square": jnp.square, "reciprocal": lambda x: 1.0 / x,
    "floor": jnp.floor, "sigmoid": jax.nn.sigmoid,
}
_BINARY = {
    "add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
    "div": jnp.divide, "pow": jnp.power, "maximum": jnp.maximum,
    "minimum": jnp.minimum, "greater": jnp.greater, "less": jnp.less,
    "mod": jnp.mod,
}
_REDUCE = {
    "reduce_sum": jnp.sum, "reduce_max": jnp.max, "reduce_min": jnp.min,
    "reduce_prod": jnp.prod,
}


#: opaque opcodes whose blocks ``executor.stats`` counts as
#: ``<opcode>_blocks``
COUNTED_OPCODES = ("argsort", "ragged_matmul")


def _static_rows(op: Op) -> int:
    """Rows an ``argsort`` sorts (its output's size over the sorted axis)
    or a ``ragged_matmul`` can take (its output's rows, routed or not)."""
    if op.opcode == "argsort":
        return op.out.size // op.out.shape[op.axis]
    return op.out.shape[0]


def _view_index(v: View) -> Optional[np.ndarray]:
    """Static flat element indices of a view into its base, or None when the
    view is the whole contiguous base (fast path: pure reshape)."""
    if v.offset == 0 and v.size == v.base.size and v.is_contiguous():
        return None
    idx = np.full((), v.offset, dtype=np.int64)
    for s, st in zip(v.shape, v.strides):
        idx = idx[..., None] + np.arange(s, dtype=np.int64) * st
    return idx.reshape(-1).astype(np.int32)


def _slice_plan(v: View) -> Optional[Tuple[Tuple[int, ...], Tuple[int, ...],
                                           Tuple[int, ...]]]:
    """Lower a regularly-strided view to one static slice: returns
    ``(dims, starts, sizes)`` such that reshaping the flat base to ``dims``
    and slicing ``starts:starts+sizes`` yields the view's elements (in view
    order), or None when the strides are not a nested row-major pattern.

    This keeps the O(size) gather-index constants of ``_view_index`` out of
    block jaxprs for the common single-slice case (slices, shifted stencil
    windows, strided 1-D subsampling): XLA sees ``reshape + slice`` instead
    of a materialized int32 index array.
    """
    if v.size == 0:
        return None
    # drop size-1 dims (their strides are arbitrary); remember nothing —
    # callers reshape to v.shape at the end anyway.
    sh = [s for s, st in zip(v.shape, v.strides) if s != 1]
    st = [st for s, st in zip(v.shape, v.strides) if s != 1]
    if any(s <= 0 for s in st):
        return None                       # broadcast or reversed
    if st and st[-1] != 1:                # strided innermost dim: view the
        sh.append(1)                      # base as (..., step) and take one
        st.append(1)                      # column of it
    dims: List[int] = []
    for i in range(len(st) - 1, 0, -1):
        if st[i - 1] % st[i]:
            return None
        d = st[i - 1] // st[i]
        if d < sh[i]:
            return None                   # rows would overlap/wrap
        dims.append(d)
    if not st:
        sh, st = [1], [1]
        dims.append(v.base.size)
    else:
        if v.base.size % st[0]:
            return None
        dims.append(v.base.size // st[0])
    dims.reverse()
    starts, rem = [], v.offset
    for d, s in zip(dims, st):            # st are the row-major strides of
        starts.append(rem // s)           # dims by construction
        rem -= starts[-1] * s
    if rem:
        return None
    if any(a + n > d for a, d, n in zip(starts, dims, sh)):
        return None
    return tuple(dims), tuple(starts), tuple(sh)


def _permute_plan(v: View, write: bool = False) -> Optional[Tuple]:
    """Lower a transposed and/or broadcast view to one static slice of a
    reordering of it: returns ``(perm, plan)`` where ``perm`` lists the
    view's axes that address data (non-zero stride, or size 1) by stride,
    descending, and ``plan`` is the ``_slice_plan`` of the view so
    reordered; None when no such reordering is a nested row-major slice
    (negative strides, overlapping or wrapping rows).  A write has no
    lowering through a broadcast (stride-0) axis."""
    if v.size == 0:
        return None
    kept = [i for i, (s, st) in enumerate(zip(v.shape, v.strides))
            if st != 0 or s == 1]
    if write and len(kept) < len(v.shape):
        return None
    perm = tuple(sorted(kept, key=lambda i: -v.strides[i]))
    plan = _slice_plan(View(v.base, v.offset,
                            tuple(v.shape[i] for i in perm),
                            tuple(v.strides[i] for i in perm)))
    return None if plan is None else (perm, plan)


def _view_lowering(v: View, write: bool = False) -> Tuple[str, object]:
    """How ``_read`` (``write=False``) or ``_write`` lowers a view,
    ``"stored" | "whole" | "slice" | "permute" | "gather"``, and the static
    plan of that lowering, tried in this order:

    * ``stored``: the contiguous base in its buffer's own shape
      (``BaseArray.shape``), the buffer as it is;
    * ``whole``: the contiguous base in another shape, a reshape;
    * ``slice``: ``_slice_plan``, a reshape and one static slice;
    * ``permute``: ``_permute_plan``, that slice of the view's axes
      reordered by stride, then a transpose back and a broadcast over the
      stride-0 axes;
    * ``gather``: a static int32 index array (``_view_index``) for what no
      reordering of a nested row-major slice describes.
    """
    if v.offset == 0 and v.size == v.base.size and v.is_contiguous():
        return ("stored" if v.shape == v.base.shape else "whole"), None
    plan = _slice_plan(v)
    if plan is not None:
        return "slice", plan
    plan = _permute_plan(v, write)
    if plan is not None:
        return "permute", plan
    return "gather", None


def _sliced(buf, plan):
    dims, starts, sizes = plan
    return jax.lax.slice(buf.reshape(dims), starts,
                         tuple(a + n for a, n in zip(starts, sizes)))


def _read(buf, v: View):
    """The elements of ``v`` in its shape, from ``buf``, a store buffer of
    ``v.base.shape``; the plans index the base's flat row-major order,
    which a reshape of any shape of the base's size gives."""
    how, plan = _view_lowering(v)
    if how == "stored":
        return buf
    if how == "whole":
        return buf.reshape(v.shape)
    if how == "slice":
        return _sliced(buf, plan).reshape(v.shape)
    if how == "permute":
        perm, plan = plan
        sub = _sliced(buf, plan).reshape(tuple(v.shape[i] for i in perm))
        sub = jnp.transpose(sub, tuple(np.argsort(perm)))
        kept = set(perm)
        sub = sub.reshape(tuple(s if i in kept else 1
                                for i, s in enumerate(v.shape)))
        return jnp.broadcast_to(sub, v.shape)
    return buf.reshape(-1)[_view_index(v)].reshape(v.shape)


def _write(buf, v: View, val):
    """``buf`` with ``val`` written through ``v``, returned in
    ``v.base.shape`` (the store invariant, ``ir.BaseArray``)."""
    val = jnp.broadcast_to(jnp.asarray(val, buf.dtype), v.shape)
    how, plan = _view_lowering(v, write=True)
    if how in ("stored", "whole"):
        return val.reshape(v.base.shape)
    if how == "permute":
        perm, plan = plan
        val = jnp.transpose(val, perm)
    if how in ("slice", "permute"):
        dims, starts, sizes = plan
        window = tuple(slice(a, a + n) for a, n in zip(starts, sizes))
        out = buf.reshape(dims).at[window].set(val.reshape(sizes))
        return out.reshape(v.base.shape)
    out = buf.reshape(-1).at[_view_index(v)].set(val.reshape(-1))
    return out.reshape(v.base.shape)


def block_dead_bases(ops: Sequence[Op]) -> set:
    """Bases destroyed inside a block and not SYNC'd: no later block (or the
    host) may observe them.  The single definition of the del−sync rule,
    shared by ``block_io`` and the scheduler's donation analysis."""
    deleted, synced = set(), set()
    for op in ops:
        for b in op.del_bases:
            deleted.add(b.uid)
        for b in op.sync_bases:
            synced.add(b.uid)
    return deleted - synced


def block_io(ops: Sequence[Op]) -> Tuple[List[int], List[int], List[int]]:
    """(input base uids, output base uids, contracted base uids) of a block.

    inputs  = bases observed before being fully defined inside the block,
    outputs = bases written here that outlive the block,
    contracted = new∩del — never materialized outside the block (the paper's
    array contraction; these become XLA temporaries / Pallas VMEM scratch).
    """
    new, read, written = set(), set(), set()
    inputs: List[int] = []
    order: List[int] = []
    for op in ops:
        for b in (*op.new_bases,):
            new.add(b.uid)
        for v in op.in_views():
            u = v.base.uid
            if u not in new and u not in written and u not in inputs:
                inputs.append(u)
            read.add(u)
            if u not in order:
                order.append(u)
        for v in op.out_views():
            u = v.base.uid
            # partial write of a pre-existing base is a read-modify-write
            if (u not in new and u not in written and u not in inputs
                    and not (v.offset == 0 and v.size == v.base.size)):
                inputs.append(u)
            written.add(u)
            if u not in order:
                order.append(u)
    dead = block_dead_bases(ops)     # SYNC'd bases stay observable
    contracted = [u for u in order if u in new and u in dead]
    outputs = [u for u in order if u in written and u not in dead]
    return inputs, outputs, contracted


def _base_meta(ops: Sequence[Op]) -> Dict[int, Tuple[Tuple[int, ...],
                                                   np.dtype]]:
    """Base uid -> ``(shape, dtype)`` of every base the ops touch."""
    meta: Dict[int, Tuple[Tuple[int, ...], np.dtype]] = {}
    for op in ops:
        for v in (*op.in_views(), *op.out_views()):
            meta[v.base.uid] = (v.base.shape, v.base.dtype)
    return meta


def _named(fn, name: str):
    """``fn`` under the function name ``name``, which ``jax.jit`` gives
    the XLA module it compiles."""
    def block(*args):
        return fn(*args)
    block.__name__ = block.__qualname__ = name
    return block


def make_block_fn(ops: Sequence[Op], seed: int = 0):
    """Build the fused function for one block.

    Returns ``(fn, input_uids, output_uids)`` where ``fn(*input_bufs) ->
    output_bufs`` is pure and jittable.  All view indices are static
    constants, so XLA sees one straight-line fused program per block — the
    fusion boundary is exactly what WSP chose.  ``fn.view_lowerings``
    counts the block's view reads and writes by ``_view_lowering``;
    ``fn.stored_bytes`` sums the bytes of the distinct views of the block's
    input bases that it reads as ``"stored"``, its buffers taken as they
    are.
    """
    work = [op for op in ops if not op.is_system()]
    inputs, outputs, contracted = block_io(ops)   # DEL/SYNC drive contraction
    meta = _base_meta(work)
    lowerings = dict.fromkeys(("stored", "whole", "slice", "permute",
                               "gather"), 0)
    stored = {}
    input_set = set(inputs)
    for op in work:
        for v in op.inputs:
            if isinstance(v, View):
                how = _view_lowering(v)[0]
                lowerings[how] += 1
                if how == "stored" and v.base.uid in input_set:
                    stored[v.base.uid] = v.nbytes
        if op.out is not None:
            lowerings[_view_lowering(op.out, write=True)[0]] += 1

    def fn(*bufs_and_salt):
        *bufs, salts = bufs_and_salt
        env: Dict[int, jnp.ndarray] = {u: b for u, b in zip(inputs, bufs)}
        n_rand = 0
        for u in meta:
            if u not in env:
                shape, dtype = meta[u]
                env[u] = jnp.zeros(shape, dtype=dtype)
        for op in work:
            ins = [(_read(env[v.base.uid], v) if isinstance(v, View) else v)
                   for v in op.inputs]
            oc = op.opcode
            if oc in _UNARY:
                val = _UNARY[oc](*ins)
            elif oc in COMM_OPS:
                # single-device semantics of a placement cast: identity —
                # only the DistBlockExecutor lowers these to collectives
                val = ins[0]
            elif oc in _BINARY:
                val = _BINARY[oc](*ins)
            elif oc == "where":
                val = jnp.where(*ins)
            elif oc in _REDUCE:
                val = _REDUCE[oc](ins[0], axis=op.axis)
            elif oc == "matmul":
                val = jnp.matmul(ins[0], ins[1])
            elif oc == "random":
                # per-op salts are call-time arguments: structurally-
                # identical blocks (shared executable) draw fresh values,
                # and the drawn values are PARTITION-INVARIANT (the salt is
                # the op's own uid, not a block property)
                key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                         salts[n_rand])
                n_rand += 1
                val = jax.random.uniform(key, op.out.shape,
                                         dtype=op.out.dtype)
            elif oc == "range":
                val = jnp.arange(op.out.size, dtype=op.out.dtype).reshape(op.out.shape)
            elif oc == "gather":
                val = jnp.take(ins[0], ins[1].astype(jnp.int32), axis=op.axis or 0)
            elif oc == "argsort":
                val = jnp.argsort(ins[0], axis=op.axis, stable=True)
            elif oc == "ragged_matmul":
                # at the ambient matmul precision, like ``matmul``; the
                # scope names the grouped product's ops in a device trace.
                # The TPU kernel leaves rows past the groups' sum unwritten
                # (the CPU's zeroes them), so they are zeroed here
                sizes = ins[2].astype(jnp.int32)
                with jax.named_scope("repro_ragged_matmul"):
                    val = jax.lax.ragged_dot(ins[0], ins[1], sizes)
                rows = jnp.arange(val.shape[0], dtype=jnp.int32)[:, None]
                val = jnp.where(rows < sizes.sum(), val, 0)
            else:
                raise NotImplementedError(f"opcode {oc!r}")
            ov = op.out
            if ov is not None:
                env[ov.base.uid] = _write(env[ov.base.uid], ov, val)
        return tuple(env[u] for u in outputs)

    fn.view_lowerings = lowerings
    fn.stored_bytes = sum(stored.values())
    return fn, inputs, outputs




#: the compile cache's place when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path in the checkout, since the path is part of each entry's key
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache for the executables the
    runtime compiles on a TPU.  JAX itself reads
    ``JAX_COMPILATION_CACHE_DIR`` when it is set, and this sets no other
    directory then (nor when the caller chose one); otherwise the cache
    goes to :data:`CHECKOUT_CACHE_DIR`.  Off the TPU it stays off: XLA:CPU
    executables loaded from another process may be built for other CPU
    features, and results that tests hold bitwise then drift.  Every
    dispatch path of :class:`BlockExecutor` calls it before compiling, so
    ``Runtime``, ``Server`` and ``LazyTransformer`` all reuse compiled
    blocks across processes; it works even after other compiles, since it
    re-initializes JAX's cache."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            or jax.config.jax_compilation_cache_dir \
            or jax.default_backend() != "tpu":
        return
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    compilation_cache.reset_cache()


def stats_delta(before: Mapping, after: Mapping) -> Dict:
    """Recursive ``after - before`` over (possibly nested) numeric stat
    mappings — the per-flush delta ``Runtime.flush`` records into history.

    Accepts plain dicts and the live :class:`~repro.core.obs.metrics
    .StatsView` alike, and always returns plain dicts.  Deltas are clamped
    at zero: ``reset_stats()`` between the two observations (e.g. mid-way
    through a deferred loop-fusion window) would otherwise make the next
    drain's delta negative, which no consumer can interpret.

    A live ``StatsView`` operand is first materialized under its registry
    lock (``StatsView.snapshot``): reading it key by key while another
    thread flushes would tear the view — counters observed at different
    instants — and silently misattribute increments (DESIGN.md §18)."""
    from .obs.metrics import StatsView
    if isinstance(before, StatsView):
        before = before.snapshot()
    if isinstance(after, StatsView):
        after = after.snapshot()
    out: Dict = {}
    for k, v in after.items():
        if isinstance(v, Mapping):
            out[k] = stats_delta(before.get(k, {}), v)
        else:
            d = v - before.get(k, 0)
            out[k] = d if d > 0 else 0
    return out


class BlockExecutor:
    """Stage 5 of the scheduler pipeline: a thin async dispatch engine over
    the lowering-backend registry (``repro.core.backends``, DESIGN.md §14).

    Each work block dispatches on the backend its ``BlockPlan.lowering``
    decision names (annotated by the scheduler's lower stage; decided here
    on the fly for legacy un-lowered schedules).  The engine owns what is
    common to every backend: the executable cache keyed by ``(backend,
    signature)`` (plus placement on a mesh), ``jax.jit`` wrapping, input
    donation for backends that opt in, RNG-salt plumbing, and uniform
    per-backend stats.

    Dispatch is asynchronous: nothing in the block loop forces a host sync,
    so block k+1 is enqueued while block k still runs on device; results
    only materialize at an explicit SYNC (``Runtime.materialize``).  When
    the platform supports buffer donation (GPU/TPU), inputs whose base dies
    inside the block are passed through ``jax.jit(donate_argnums=...)`` so
    XLA reuses their memory for the block's outputs."""

    def __init__(self, seed: int = 0, jit: bool = True,
                 backend="xla", donate="auto", mesh=None,
                 axis: Optional[str] = None, profiler=None):
        """``backend`` resolves to the preference-ordered candidate list of
        the lowering policy (``backends.default_stack``): ``"xla"`` runs
        everything as jitted XLA programs; ``"pallas"`` prefers the tiled
        fused-block Pallas codegen with per-reason XLA fallback; a
        tuple/list names an explicit stack.  ``mesh`` (a 1-D
        ``jax.sharding.Mesh``) prepends the ``shard_map`` backend so
        sharded blocks run with real collectives.  donate='auto' enables
        input donation on platforms that implement it (GPU/TPU); True
        forces it, False disables it.  ``profiler`` (a
        ``tuning.Profiler``) turns on per-block wall-time capture: warm
        dispatches are forced to completion and timed — measurement trades
        the async pipeline away, so attach one only to calibrate
        (DESIGN.md §15)."""
        from .backends import default_stack
        self.seed = seed
        self.jit = jit
        self.backend = backend            # policy shorthand, kept for repr
        self.donate = donate
        self.mesh = mesh
        self.profiler = profiler
        if mesh is not None:
            self.axis = axis or mesh.axis_names[0]
            self.n_dev = int(np.prod(mesh.devices.shape))
        else:
            self.axis = axis
            self.n_dev = 1
        self.backends: Tuple[str, ...] = default_stack(backend, mesh)
        self._cache: Dict[Tuple, Tuple] = {}
        self._decisions: Dict[Tuple, object] = {}
        #: executable name -> ``(opcode, static rows)`` of its block's op
        #: of ``COUNTED_OPCODES``
        self._counted: Dict[str, Tuple[str, int]] = {}
        #: guards the executable/decision caches under concurrent flushes
        #: (DESIGN.md §18).  Builds happen OUTSIDE the lock — two threads
        #: racing a cold key may both compile; last put wins, both work.
        self._lock = threading.RLock()
        self._empty_salts = None
        self.sync_store: Dict[int, jnp.ndarray] = {}
        #: group sizes of the ``ragged_matmul`` blocks run under tracing,
        #: by base uid, until ``emit_group_rows`` reads them
        self._group_sizes: Dict[int, jnp.ndarray] = {}
        #: the single backing store for every executor observation
        #: (DESIGN.md §17); ``stats`` is a legacy-dict-shaped live view
        self.metrics = MetricsRegistry()
        self.stats: StatsView = StatsView(self.metrics, prefix="executor")
        self.reset_stats()

    # -- stats ---------------------------------------------------------
    def reset_stats(self) -> None:
        """Zero every counter (compiled executables and cached lowering
        decisions are kept — resetting is observation, not state).

        Declares the legacy stat shape onto the metrics registry:
        ``backend_blocks[name]`` counts dispatches per backend;
        ``backend_fallbacks[name][reason]`` counts, per backend the policy
        preferred over the one that ran, why it declined.  The legacy
        ``pallas_*`` aliases keep their historical meaning: every
        dispatched work block under a pallas-bearing policy lands either in
        ``pallas_blocks`` or in ``pallas_fallback_blocks`` with the reason
        slug counted in ``pallas_fallbacks`` (``codegen.REASONS``,
        DESIGN.md §13), so ``pallas_blocks / (pallas_blocks +
        pallas_fallback_blocks)`` is the executed kernel coverage.

        The whole re-declaration happens under the registry lock: a
        ``snapshot_stats`` racing the reset sees either the old counters or
        the zeroed shape, never a half-cleared mix."""
        st = self.stats
        with self.metrics.lock:
            for key in ("blocks_run", "exec_cache_hits", "exec_cache_misses",
                        "donated_buffers", "pallas_blocks",
                        "pallas_fallback_blocks"):
                st.declare_scalar(key)
            st.declare_group("pallas_fallbacks", ("reason",))
            for key in ("loop_flushes", "loop_iterations"):
                st.declare_scalar(key)
            for oc in COUNTED_OPCODES:
                st.declare_scalar(f"{oc}_blocks")
            st.declare_group("backend_blocks", ("backend",),
                             presets=self.backends)
            st.declare_group("backend_fallbacks", ("backend", "reason"),
                             presets=self.backends)
            if "shard_map" in self.backends:
                st.declare_scalar("shard_map_blocks")
                st.declare_scalar("collectives")
                st.declare_scalar("interconnect_bytes", 0.0)
            else:
                for key in ("shard_map_blocks", "collectives",
                            "interconnect_bytes"):
                    st.drop(key)

    def snapshot_stats(self) -> Dict:
        """Plain nested-dict copy of the counters, for before/after flush
        deltas (``stats_delta``).  Taken under the registry lock so a
        snapshot racing a concurrent flush (or ``reset_stats``) is a
        consistent point-in-time view, never a torn one."""
        return self.stats.snapshot()

    # -- policy --------------------------------------------------------
    def donation_enabled(self) -> bool:
        if self.donate == "auto":
            return jax.default_backend() in ("gpu", "tpu", "cuda", "rocm")
        return bool(self.donate)

    def lowering_context(self):
        from .backends import LoweringContext
        # interpret mode resolves per platform (repro.kernels
        # .resolve_interpret): Mosaic kernels on a TPU, the interpreter
        # elsewhere
        return LoweringContext(seed=self.seed, jit=self.jit,
                               mesh=self.mesh, axis=self.axis,
                               n_dev=self.n_dev)

    def lowering_policy(self):
        """What ``Runtime.flush`` hands ``Scheduler.plan`` so the lower
        stage decides per block which of this executor's backends runs it."""
        from .backends import LoweringPolicy
        return LoweringPolicy(backends=self.backends,
                              ctx=self.lowering_context())

    def topology_key(self) -> Tuple:
        """Device/mesh identity mixed into the merge-cache key (empty on a
        single-device executor)."""
        if self.mesh is None:
            return ()
        from .dist.mesh import topology_key
        return topology_key(self.mesh)

    def _cache_key(self, ops: Sequence[Op], plan,
                   backend: Optional[str] = None, ctx=None) -> Tuple:
        """Executable-cache key: backend name x structural signature, plus
        whatever extra identity the backend's ``cache_token`` declares (the
        shard_map backend folds in per-base placement so one signature
        never serves two shardings).  With ``backend=None`` the key indexes
        the dispatch-time *decision* cache instead, which is placement-
        dependent on a mesh regardless of the backend chosen."""
        key: Tuple = (backend, plan.signature)
        if backend is not None:
            from .backends import get_backend
            return key + tuple(get_backend(backend).cache_token(
                ops, plan, ctx if ctx is not None
                else self.lowering_context()))
        if self.mesh is not None:
            from .dist.spec import placement_digest
            key += (placement_digest(ops),)
        return key

    def run(self, tape: Sequence[Op], op_blocks: Sequence[Sequence[int]],
            buffers: Dict[int, jnp.ndarray]) -> None:
        """Legacy front door: plan the blocks, then execute the schedule."""
        from .scheduler import Schedule, plan_blocks   # local: avoid cycle
        self.run_schedule(Schedule(tape=list(tape),
                                   blocks=plan_blocks(tape, op_blocks)),
                          buffers)

    # -- dispatch ------------------------------------------------------
    def _decide(self, ops: Sequence[Op], plan, ctx):
        """Lowering decision for a plan the scheduler did not annotate
        (legacy ``run``/hand-built schedules) — same selection rule, cached
        so steady-state dispatches skip the probing."""
        from .backends import select_lowering
        key = self._cache_key(ops, plan)
        with self._lock:
            d = self._decisions.get(key)
        if d is None:
            d = select_lowering(ops, plan, self.backends, ctx)
            with self._lock:
                self._decisions[key] = d
        return d

    def _executable(self, decision, ops: Sequence[Op], plan, ctx) -> Tuple:
        """Look up (or build) the jitted executable for one decided plan.
        Returns ``(fn, donates, name, views, warm)``; ``warm`` is True on
        a cache hit (the profiler times only warm dispatches — cold ones
        include trace+compile time).  ``views`` is ``(permutes, gathers,
        stored_bytes)``: the block's view reads and writes that its ``xla``
        lowering (``make_block_fn``) takes through a transpose or broadcast
        and through an index gather, and the bytes of the input buffers it
        reads as they are stored; 0, 0 and 0 on every other backend.
        ``name`` is the executable's stable name,
        ``repro_block_<backend>_<signature digest>``, which XLA's module
        takes (``jit_<name>``) so that a device trace names a block the
        same way in every run.  A builder failure raises
        :class:`~repro.core.backends.BackendBuildError` naming the backend:
        the block never silently runs elsewhere."""
        from .backends import build_block, get_backend
        key = self._cache_key(ops, plan, backend=decision.backend, ctx=ctx)
        with self._lock:
            cached = self._cache.get(key)
        if cached is not None:
            trace.instant("cache.exec", hit=True, backend=decision.backend)
            return (*cached, True)
        trace.instant("cache.exec", hit=False, backend=decision.backend)
        with trace.span("build", backend=decision.backend,
                        n_ops=len(ops)):
            from .tuning.profile import signature_digest
            be = get_backend(decision.backend)
            fn = build_block(decision.backend, ops, plan, ctx)
            lowerings = getattr(fn, "view_lowerings", {})
            views = (lowerings.get("permute", 0),
                     lowerings.get("gather", 0),
                     getattr(fn, "stored_bytes", 0))
            donate = (plan.donatable if self.jit and be.donates
                      and self.donation_enabled() else ())
            name = (f"repro_block_{decision.backend}_"
                    f"{signature_digest(plan.signature)[:8]}")
            if self.jit:
                fn = jax.jit(_named(fn, name), donate_argnums=donate)
        counted = next(((op.opcode, _static_rows(op)) for op in ops
                        if op.opcode in COUNTED_OPCODES), None)
        entry = (fn, bool(donate), name, views)
        with self._lock:
            self._cache[key] = entry
            if counted is not None:
                self._counted[name] = counted
        return (*entry, False)

    def _count_dispatch(self, warm: bool) -> None:
        """Count one executable dispatch in ``blocks_run`` and its cache
        lookup in ``exec_cache_hits`` or ``exec_cache_misses``, both under
        the registry lock, so ``hits + misses == blocks_run`` holds in
        every snapshot.  A loop or batch executable counts once, however
        many blocks it runs."""
        with self.metrics.lock:
            self.stats.inc("blocks_run")
            self.stats.inc("exec_cache_hits" if warm else "exec_cache_misses")

    def _account(self, decision, plan, donates: bool, warm: bool) -> None:
        """Uniform per-dispatch stats plus the legacy aliases.  Every update
        is an atomic ``StatsView.inc`` — concurrent session flushes
        (DESIGN.md §18) must not lose increments to read-modify-write
        races, and the stress suite asserts exact totals."""
        st = self.stats
        self._count_dispatch(warm)
        st.inc("backend_blocks", labels=(decision.backend,))
        for name, reason in decision.declined:
            st.inc("backend_fallbacks", labels=(name, reason))
        if decision.backend == "pallas":
            st.inc("pallas_blocks")
        else:
            pr = decision.reason_for("pallas")
            if pr is not None:
                st.inc("pallas_fallback_blocks")
                st.inc("pallas_fallbacks", labels=(pr,))
        if decision.backend == "shard_map":
            st.inc("shard_map_blocks")
        if donates:
            st.inc("donated_buffers", len(plan.donatable))

    def run_schedule(self, schedule, buffers: Dict[int, jnp.ndarray]) -> None:
        """Dispatch a planned flush (stage 6) against the buffer store.

        ``schedule`` is the :class:`repro.core.scheduler.Schedule` produced
        by ``Scheduler.plan``; ``buffers`` maps base uid -> device buffer
        of the base's shape and is updated in place with each block's
        outputs.  Per block: take the plan's lowering decision (or decide
        now), look up (or compile) the executable under ``(backend,
        signature)``, feed the external input buffers plus the RNG salts,
        then honor SYNC (snapshot into ``sync_store``) and DEL (free) in
        Bohrium order.
        Dispatch is async — nothing here blocks on device results."""
        from .backends import get_backend
        use_compile_cache()
        tape = schedule.tape
        ctx = self.lowering_context()
        if self._empty_salts is None:
            self._empty_salts = jnp.zeros((0,), dtype=jnp.int32)
        with trace.span("stage.execute", n_blocks=len(schedule.blocks)):
            for plan in schedule.blocks:
                ops = [tape[i] for i in plan.op_indices]
                if plan.has_work:
                    decision = getattr(plan, "lowering", None)
                    if decision is None:
                        decision = self._decide(ops, plan, ctx)
                    # plan inputs/outputs are uid lists of THIS flush; the
                    # canonical signature guarantees positional
                    # correspondence with the cached executable across
                    # flushes.
                    fn, donates, name, views, warm = self._executable(
                        decision, ops, plan, ctx)
                    self._account(decision, plan, donates, warm)
                    counted = self._counted.get(name)
                    extra = {}
                    if counted is not None:
                        self.stats.inc(f"{counted[0]}_blocks")
                        extra = {"opcode": counted[0], "rows": counted[1]}
                        if counted[0] == "ragged_matmul" and trace.active():
                            self._keep_group_sizes(ops, buffers)
                    in_bufs = []
                    for u in plan.inputs:
                        if u not in buffers:
                            raise RuntimeError(
                                f"base {u} read before definition")
                        in_bufs.append(buffers[u])
                    salt_list = [getattr(op, "salt", op.uid) % (2**31 - 1)
                                 for op in ops
                                 if not op.is_system()
                                 and op.opcode == "random"]
                    salts = (jnp.asarray(salt_list, dtype=jnp.int32)
                             if salt_list else self._empty_salts)
                    timing = warm and self.profiler is not None
                    with trace.span("block", backend=decision.backend,
                                    n_ops=len(plan.op_indices), name=name,
                                    cold=not warm, permutes=views[0],
                                    gathers=views[1],
                                    stored_bytes=views[2], **extra):
                        if timing:
                            jax.block_until_ready(in_bufs)  # drain queued
                            t0 = time.perf_counter()   # work so the clock
                        out_bufs = fn(*in_bufs, salts)  # sees ONE block
                        if timing:
                            jax.block_until_ready(out_bufs)
                            self.profiler.record(decision.backend, ops, plan,
                                                 ctx,
                                                 time.perf_counter() - t0)
                    for u, b in zip(plan.outputs, out_bufs):
                        buffers[u] = b
                    get_backend(decision.backend).post_dispatch(
                        ops, plan, ctx, self.stats)
                for op in ops:  # SYNC snapshots before DEL (Bohrium order)
                    for b in op.sync_bases:
                        if b.uid in buffers:
                            self.sync_store[b.uid] = buffers[b.uid]
                    for b in op.del_bases:
                        buffers.pop(b.uid, None)

    def _keep_group_sizes(self, ops: Sequence[Op], buffers) -> None:
        """Under tracing: keep a device copy of a ``ragged_matmul`` block's
        group sizes (a later block may donate the buffer), once per base,
        for :meth:`emit_group_rows`."""
        (op,) = [o for o in ops if o.opcode == "ragged_matmul"]
        v = op.inputs[2]
        if v.base.uid not in self._group_sizes:
            self._group_sizes[v.base.uid] = jnp.copy(
                _read(buffers[v.base.uid], v))

    def emit_group_rows(self) -> None:
        """Read back the group sizes kept since the last call and emit one
        ``moe.rows`` span: ``rows`` (all groups' rows, each group-size array
        counted once: the rows routed to the held experts, over all
        layers), ``max_rows`` (the largest group), ``groups`` (groups read)
        and ``arrays`` (group-size arrays read).  Nothing is kept, so
        nothing is read, while no tracer records."""
        if not self._group_sizes:
            return
        kept, self._group_sizes = self._group_sizes, {}
        with trace.span("moe.rows") as sp:
            sizes = [np.asarray(b) for b in kept.values()]
            sp.set(rows=int(sum(float(s.sum()) for s in sizes)),
                   max_rows=int(max(float(s.max()) for s in sizes)),
                   groups=int(sum(s.size for s in sizes)),
                   arrays=len(sizes))

    def run_loop(self, loop_plan, state: Sequence, invariants: Sequence,
                 salts, n: int) -> Tuple:
        """Dispatch ONE fused steady-state loop executable (DESIGN.md §16).

        ``loop_plan`` is the scheduler's :class:`~repro.core.scheduler
        .LoopPlan`; ``state`` holds the carried buffers (one per tape-level
        output, canonical order, initialized from the last executed flush's
        outputs), ``invariants`` the loop-invariant input buffers,
        ``salts`` the stacked per-iteration RNG salt matrix padded to the
        executable's capacity, and ``n`` how many of those iterations to
        run.  Returns the final state buffers.

        The executable lives in the same cache as per-block functions under
        ``("loop", plan key, capacity, donate)`` — one compile serves every
        drain size up to ``capacity`` because ``n`` is a traced argument.
        The whole state pytree is donated when the platform supports
        donation and no state buffer is aliased by ``sync_store`` (a
        materialized snapshot must survive the dispatch); invariants are
        never donated."""
        use_compile_cache()
        ctx = self.lowering_context()
        donate = False
        if self.jit and self.donation_enabled():
            synced = {id(b) for b in self.sync_store.values()}
            donate = not any(id(b) in synced for b in state)
        key = ("loop", loop_plan.key, int(salts.shape[0]), donate)
        with trace.span("stage.execute", loop=True, n_iterations=int(n)):
            with self._lock:
                cached = self._cache.get(key)
            self._count_dispatch(cached is not None)
            if cached is not None:
                trace.instant("cache.exec", hit=True, loop=True)
                fn = cached[0]
            else:
                trace.instant("cache.exec", hit=False, loop=True)
                with trace.span("build", loop=True,
                                n_ops=len(loop_plan.tape)):
                    from .backends.loop_body import build_loop_fn
                    fn = build_loop_fn(loop_plan.tape, loop_plan.plans,
                                       loop_plan.input_sources,
                                       loop_plan.tape_inputs,
                                       loop_plan.tape_outputs, ctx)
                    if self.jit:
                        fn = jax.jit(fn,
                                     donate_argnums=(3,) if donate else ())
                with self._lock:
                    self._cache[key] = (fn,)
            self.stats.inc("loop_flushes")
            self.stats.inc("loop_iterations", int(n))
            if donate:
                self.stats.inc("donated_buffers", len(state))
            return tuple(fn(jnp.int32(n), salts, tuple(invariants),
                            tuple(state)))

    def run_batch(self, schedule, tape_inputs: Sequence[int],
                  tape_outputs: Sequence[int],
                  in_cols: Sequence[Sequence], salt_rows: Sequence[Sequence[int]]
                  ) -> List:
        """Dispatch B structurally-identical flushes as ONE vmapped
        executable (cross-request micro-batching, DESIGN.md §18).

        ``schedule`` is the lead request's planned flush (the structural
        template), ``tape_inputs``/``tape_outputs`` its tape-level io in
        canonical ``cache.tape_io`` order, ``in_cols`` one column per input
        position (each a length-B list of store buffers, request order) and
        ``salt_rows`` one row per request of that request's ``random``-op
        salts (schedule work-block order).  Returns one ``(B, *shape)``
        stacked buffer per output position; the caller scatters row ``r``
        back into request ``r``'s buffer store.

        The executable is cached under ``("serve_batch", plan key, B)`` —
        the batch width is a static shape, so each width compiles once and
        every later window of that width re-dispatches it."""
        use_compile_cache()
        B = len(salt_rows)
        plan_key = (schedule.key if schedule.key is not None
                    else tuple(p.signature for p in schedule.blocks))
        key = ("serve_batch", plan_key, B)
        with trace.span("serve.batch", n_requests=B):
            with self._lock:
                cached = self._cache.get(key)
            self._count_dispatch(cached is not None)
            if cached is not None:
                trace.instant("cache.exec", hit=True, batch=True)
                fn, n_rand = cached
            else:
                trace.instant("cache.exec", hit=False, batch=True)
                with trace.span("build", batch=True,
                                n_ops=len(schedule.tape)):
                    from .backends.batch_body import build_batch_fn
                    fn, n_rand = build_batch_fn(
                        schedule.tape, schedule.blocks,
                        tuple(tape_inputs), tuple(tape_outputs),
                        self.lowering_context())
                    if self.jit:
                        fn = jax.jit(fn)
                with self._lock:
                    self._cache[key] = (fn, n_rand)
            self.metrics.counter("serve.batch.dispatches").inc()
            self.metrics.counter("serve.batch.requests").inc(B)
            stacked = tuple(jnp.stack(list(col)) for col in in_cols)
            salts = jnp.asarray(
                np.asarray(salt_rows, dtype=np.int32).reshape(B, n_rand))
            return list(fn(stacked, salts))
