"""Seeded random lazy-program generator + differential fuzzer (DESIGN.md §15).

A :class:`TapeProgram` is a deterministic function of its seed: the same
seed always performs the same sequence of lazy-array actions — elementwise
chains, axis/full reductions, strided and partial views, RMW partial
writes, scalar/row/column broadcasts, transposes, opaque matmuls, explicit
DELs, quantized ``random`` draws, indexed ``gather``/``take`` reads (the
index array is itself a computed integer-valued program array) and
(``sharded=True``) placement annotations that make the flush insert COMM
collectives.  Replaying one
program under different runtime configurations is therefore a *differential
test*: every configuration must produce bitwise-identical results.

**Why bitwise equality is achievable.**  In ``exact=True`` mode (the fuzz
default) programs stay closed over *low-granularity dyadic* float64 data:
leaves are integer-valued, scalar factors are dyadic (0.5/0.25/2/3/-1.5),
array-array products are clamped back to whole integers
(``floor(x % 1021)``), and magnitude-growing scalar chains are re-bounded
by ``% 1021``.  Elementwise ops are computed per element in program order
under every partition (only identical rounding can occur), and every
value that reaches a *reduction* is a bounded-magnitude dyadic whose sums
are exactly representable — so reductions are exactly associative and the
answer is independent of partition shape, tiling, accumulation order or
collective schedule.  Any mismatch is a real bug, never round-off.
``exact=False`` widens the opcode pool with transcendentals
(sin/exp/sqrt/div/…) for calibration workloads, where values need to look
like real numerics and nobody compares them.

**Shrinking by seed**: there is no structural shrinker — the generator is
seeded and sized, so a failure reproduces from two integers.  The sweep
prints the failing seed and the exact one-command repro; shrink by
rerunning ``--only SEED`` with smaller ``--actions``/``--size`` until the
tape is small enough to read.

Checks (each returns normally or raises ``AssertionError``):

* ``check_graph`` — staged base-indexed ``build_graph`` produces identical
  E_d/E_f to the O(V²) ``build_graph_reference`` oracle (sharded tapes are
  run through ``insert_resharding`` first, exactly like a real flush);
* ``check_exec``  — fused greedy/XLA and greedy/Pallas runs are bitwise
  identical to the unfused singleton/XLA reference;
* ``check_dist``  — a COMM-inserting sharded program on a real device mesh
  (shard_map collectives) is bitwise identical to the same program on a
  single device (COMM as identity copies);
* ``check_lm``    — an LM-shaped program (:class:`LMProgram` grammars:
  rmsnorm / masked-softmax attention / MoE top-k routing / selective
  scan) run on the ``backend="lm"`` stack agrees with the plain XLA stack
  under the SAME greedy partition to ``LM_RTOL``, and the ``rowblock``
  backend claimed a block of every grammar but moe.

CLI sweep (the CI fuzz job)::

    PYTHONPATH=src python -m repro.testing.tapegen --n 200 [--dist]
    PYTHONPATH=src python -m repro.testing.tapegen --n 40 --checks lm
    PYTHONPATH=src python -m repro.testing.tapegen --only 1337   # repro
"""

from __future__ import annotations

import random
from typing import List, Optional, Sequence, Tuple

import numpy as np

# value bound for products: keeps every intermediate integer exactly
# representable in float64 (see module docstring)
_MOD = 1021.0


class TapeProgram:
    """One seeded random lazy program.

    Parameters
    ----------
    seed      : the program identity; everything derives from it.
    n_actions : number of generator actions (tape length scales with it).
    size      : elements in the 1-D working shape (2-D uses ``(8, size//8)``;
                sizes below 64 are rounded up so both shapes exist).
    exact     : restrict to the dyadic/integer-valued opcode pool whose
                results are bitwise partition-invariant (see module doc).
    sharded   : annotate some whole-base arrays as block-sharded over
                ``n_shards`` logical shards and insert explicit placement
                casts — the flush's resharding pass then injects COMM ops.
    n_shards  : logical shard count (match the mesh size when executing on
                a real mesh so the shard_map backend claims the blocks).
    """

    def __init__(self, seed: int, *, n_actions: int = 20, size: int = 64,
                 exact: bool = True, sharded: bool = False,
                 n_shards: int = 4):
        self.seed = int(seed)
        self.n_actions = int(n_actions)
        self.size = max(64, int(size) - int(size) % 8)
        self.exact = bool(exact)
        self.sharded = bool(sharded)
        self.n_shards = int(n_shards)

    # -- the generator --------------------------------------------------
    def _build(self, rt, materialize: bool) -> List[np.ndarray]:
        """Run the action sequence against runtime ``rt`` (already the
        active runtime).  With ``materialize`` the live arrays are read
        back (flushing the tape); without, the recorded tape is left in
        place for graph-level checks."""
        from repro.core import lazy as bh
        rnd = random.Random(self.seed)
        n = self.size
        shapes = {"1d": (n,), "2d": (8, n // 8)}
        pool: List[Tuple[object, str, bool]] = []   # (arr, kind, whole_base)

        def quantize(a):
            # integer-valued in [0, 16): exact under float64 arithmetic
            return bh.floor(a * 16.0)

        def fresh(kind: str):
            shape = shapes[kind]
            w = rnd.randrange(3)
            if w == 0:
                a = bh.full(shape, float(rnd.randrange(-8, 9)))
            elif w == 1 and kind == "1d":
                a = bh.arange(n) * (0.5 if rnd.random() < 0.3 else 1.0)
            else:
                a = quantize(bh.random(shape))
            pool.append((a, kind, True))
            return a

        for kind in ("1d", "2d"):
            fresh(kind)
        if self.sharded:
            from repro.core.dist import shard
            for i, (a, kind, whole) in enumerate(pool):
                if whole and rnd.random() < 0.8:
                    shard(a, dim=0, n=self.n_shards)

        def pick(kind: Optional[str] = None):
            cands = [e for e in pool if kind is None or e[1] == kind]
            return cands[rnd.randrange(len(cands))] if cands else None

        def clamp(a):
            # After an array-array product, both bound the magnitude AND
            # reset the dyadic granularity to whole integers: reductions
            # over the result are then exactly associative no matter how
            # deep the producing chains were (see module docstring).
            return bh.floor(a % _MOD) if self.exact \
                else bh.tanh(a * 0.125) * 8.0

        for _ in range(self.n_actions):
            act = rnd.randrange(15)
            ent = pick()
            if ent is None:
                fresh("1d")
                continue
            a, kind, _whole = ent
            if kind not in shapes and act not in (0, 2, 3, 11):
                continue    # odd-shaped leftovers only do shape-free actions
            shape = shapes.get(kind)
            if act == 0:                       # new leaf
                fresh(rnd.choice(("1d", "2d")))
            elif act == 1:                     # elementwise binop, same shape
                other = pick(kind)
                oc = rnd.choice(("add", "sub", "mul", "maximum", "minimum"))
                b = other[0]
                r = {"add": lambda: a + b, "sub": lambda: a - b,
                     "mul": lambda: clamp(a * b),
                     "maximum": lambda: bh.maximum(a, b),
                     "minimum": lambda: bh.minimum(a, b)}[oc]()
                pool.append((r, kind, True))
            elif act == 2:                     # scalar chain (dyadic consts)
                c = rnd.choice((0.5, 0.25, 2.0, 3.0, -1.5))
                r = a * c
                if self.exact and abs(c) >= 1.5:
                    r = r % _MOD               # upscaling: re-bound magnitude
                r = r + float(rnd.randrange(-4, 5))
                pool.append((r, kind, True))
            elif act == 3:                     # unary
                fns = [bh.absolute, bh.floor, bh.sign,
                       lambda x: -x, lambda x: x.copy()]
                if not self.exact:
                    fns += [lambda x: bh.sqrt(bh.absolute(x)), bh.sin,
                            bh.cos, bh.tanh,
                            lambda x: bh.log(bh.absolute(x) + 1.0),
                            lambda x: 1.0 / (bh.absolute(x) + 1.0)]
                pool.append((fns[rnd.randrange(len(fns))](a), kind, True))
            elif act == 4:                     # in-place update (same base)
                other = pick(kind)
                a += other[0] * rnd.choice((0.5, 1.0, 2.0))
            elif act == 5:                     # where on a comparison
                other = pick(kind)
                pool.append((bh.where(a > other[0], a, other[0]), kind, True))
            elif act == 6:                     # reduction
                oc = rnd.choice(("sum", "max", "min"))
                axis = rnd.choice((None, 0, 1)) if kind == "2d" \
                    else rnd.choice((None, 0))
                r = getattr(a, oc)(axis)
                if axis is None:               # scalar: broadcast back in
                    r = bh.zeros(shapes["1d"]) + r.broadcast_to(shapes["1d"])
                    pool.append((r, "1d", True))
                elif kind == "2d":
                    # feed the genuine row/col vector forward as a stride-0
                    # broadcast operand — vector-shaped reduction outputs
                    # are exactly where tiling bugs would hide
                    if axis == 0:              # row vector (n//8,)
                        r2 = r.broadcast_to(shapes["2d"])
                    else:                      # col vector (8,) -> column
                        r2 = r.broadcast_to((shapes["2d"][1], 8)).T
                    two = pick("2d")
                    if two is not None:
                        pool.append((two[0] + r2, "2d", True))
                else:
                    r = bh.zeros(shapes["1d"]) + r.broadcast_to(shapes["1d"])
                    pool.append((r, "1d", True))
            elif act == 7:                     # strided/partial view read
                if kind == "1d":
                    sl = rnd.choice((slice(0, None, 2), slice(1, None, 2),
                                     slice(1, -1), slice(None, n // 2)))
                    v = a[sl]
                    c = bh.zeros(shape)
                    c[0:v.shape[0]] = v        # partial write of the window
                else:
                    v = a[1:-1, :]
                    c = bh.zeros(shape)
                    c[1:-1, :] = v
                pool.append((c, kind, True))
            elif act == 8:                     # RMW partial write
                other = pick(kind)
                if kind == "1d":
                    a[n // 4: 3 * n // 4] = other[0][n // 4: 3 * n // 4] + 1.0
                else:
                    a[2:6, :] = other[0][2:6, :] * 0.5
            elif act == 9:                     # broadcast 1d row into 2d
                row = pick("1d")
                if row is not None:
                    r2 = row[0][0: n // 8].broadcast_to(shapes["2d"])
                    two = pick("2d")
                    if two is not None:
                        pool.append((two[0] + r2, "2d", True))
            elif act == 10 and kind == "2d":   # transpose read (gather path)
                sq = a[:, 0:8]
                pool.append((sq.T.copy().reshape(64), "none", True))
            elif act == 11:                    # explicit DEL
                if len(pool) > 2:
                    i = pool.index(ent)
                    pool.pop(i)
                    a.delete()
            elif act == 12 and kind == "2d" and rnd.random() < 0.5:
                m = a[:, 0:8]                  # opaque op: small matmul
                r = bh.matmul(m.T.copy(), m.copy())
                pool.append((r.reshape(64) % _MOD, "none", True))
            elif act == 13 and self.sharded:
                from repro.core.dist import ShardSpec, reshard, spec_of
                src = ent
                if src[2]:
                    s = spec_of(src[0].view.base)
                    if s is None:
                        spec = ShardSpec.for_dim(src[0].shape, 0, "dev",
                                                 self.n_shards)
                        pool.append((reshard(src[0], spec), src[1], True))
                    else:
                        pool.append((reshard(src[0], None), src[1], True))
            elif act == 14:                    # gather / take (indexed read)
                # table = a 1-D program array; indices = another program
                # array floored into [0, n) — selecting integer-valued
                # dyadics is exact, so gathers stay bitwise
                # partition-invariant like every other action
                tbl = pick("1d")
                if tbl is not None:
                    idx = bh.floor(bh.absolute(a) % float(n))
                    pool.append((bh.take(tbl[0], idx), kind, True))
            # other act values on mismatched kinds: no-op (keeps the action
            # stream aligned across replays regardless of branch outcomes)

        outs: List[np.ndarray] = []
        if materialize:
            for a, _, _ in pool:
                outs.append(a.numpy())
        for a, _, _ in pool:
            a._alive = False                   # no DELs after harvest
        return outs

    # -- public entry points --------------------------------------------
    def run(self, **runtime_kw) -> List[np.ndarray]:
        """Execute under a fresh runtime built from ``runtime_kw`` and
        return every live array materialized, in creation order."""
        from repro.core.lazy import fresh_runtime
        with fresh_runtime(**runtime_kw) as rt:
            return self._build(rt, materialize=True)

    def run_current(self) -> List[np.ndarray]:
        """Execute against the *currently active* runtime (callers own the
        ``fresh_runtime`` context).  Repeated calls in one runtime replay a
        structurally-identical tape — merge-cache and executable-cache hits
        — which is how the calibration loop gets warm, timeable dispatches."""
        from repro.core.lazy import get_runtime
        return self._build(get_runtime(), materialize=True)

    def record(self) -> List:
        """Record the program without executing; returns the tape."""
        from repro.core.lazy import fresh_runtime
        with fresh_runtime() as rt:
            self._build(rt, materialize=False)
            tape = list(rt.tape)
            rt.tape.clear()
        return tape


class IterativeProgram:
    """A seeded *iterative* lazy program: one randomly-drawn step body
    replayed ``steps`` times with carried state and a flush per step — the
    workload shape cross-flush loop fusion (DESIGN.md §16) detects and
    defers.

    The step recipe is drawn ONCE from the seed and replayed verbatim, so
    every step traces a structurally identical tape.  The recipe mixes the
    carry shapes the recurrence detector must prove safe: in-place partial
    writes (same base every step), fresh-chain carries (new base each step,
    old base deleted), loop-invariant reads, contracted temporaries,
    reductions fed back through RMW partial writes, and per-step quantized
    ``random`` draws (fresh trace-time salts each step — the loop path must
    reproduce them bit for bit from its stacked salt matrix).  Only the
    final state materializes; intermediate steps must never be observable.
    """

    def __init__(self, seed: int, *, steps: int = 9, n_ops: int = 6,
                 size: int = 64):
        self.seed = int(seed)
        self.steps = int(steps)
        self.n_ops = int(n_ops)
        self.size = max(64, int(size) - int(size) % 8)

    def run(self, **runtime_kw) -> List[np.ndarray]:
        from repro.core import lazy as bh
        from repro.core.lazy import fresh_runtime
        rnd = random.Random(self.seed ^ 0x17E5A71)
        n = self.size
        shapes = {"1d": (n,), "2d": (8, n // 8)}
        # the step recipe: drawn once, replayed identically every step
        recipe = [(rnd.randrange(6), rnd.choice((0.5, 0.25, 2.0, 3.0, -1.5)))
                  for _ in range(self.n_ops)]
        with fresh_runtime(**runtime_kw):
            g = bh.floor(bh.random(shapes["2d"]) * 16.0)
            a = bh.floor(bh.random(shapes["1d"]) * 16.0)
            k = bh.full(shapes["1d"], float(rnd.randrange(1, 7)))  # invariant
            bh.flush()
            for _step in range(self.steps):
                for act, c in recipe:
                    if act == 0:           # in-place stencil update (RMW)
                        inner = (g[1:-1, :] + g[:-2, :] + g[2:, :]) * 0.25
                        g[1:-1, :] = bh.floor(inner)
                        inner.delete()
                    elif act == 1:         # fresh-chain carry on `a`
                        b = bh.floor((a * c) % _MOD) + k
                        a.delete()
                        a = b
                    elif act == 2:         # per-step RNG draw
                        r = bh.floor(bh.random(shapes["1d"]) * 16.0)
                        b = a + r
                        a.delete()
                        r.delete()
                        a = b
                    elif act == 3:         # reduction fed back through RMW
                        s = g.sum(0)
                        a[0: n // 8] = bh.floor((s + a[0: n // 8]) % _MOD)
                        s.delete()
                    elif act == 4:         # in-place whole-array update
                        a += k * c
                    elif act == 5:         # where-mix into `g`, full write
                        m = a[0: n // 8].broadcast_to(shapes["2d"])
                        t = bh.where(g > m, g, m)
                        g[:, :] = t
                        t.delete()
                bh.flush()
            outs = [g.numpy(), a.numpy(), k.numpy()]
            for arr in (g, a, k):
                arr._alive = False         # no DELs after harvest
        return outs


class LMProgram:
    """A seeded LM-shaped lazy program (DESIGN.md §20).

    Four grammars, chosen by ``seed % 4``, each tracing the op shapes the
    ``rowblock`` backend's row-pattern screen admits — sized by the seed
    so the sweep covers many domains:

    * ``rmsnorm``    — residual add, sum-of-squares variance, the
      ``div→add(eps)→rsqrt→mul→mul`` scale chain;
    * ``attention``  — scaled masked scores, ``where(-inf)``, the
      max / shifted-exp / sum / normalize softmax chain (two claimed
      reduction blocks);
    * ``moe``        — top-k expert routing: host-computed argsort
      indices, ``take`` gathers out of an expert table, gate-weighted
      combine (gathers stay on the XLA floor — nothing claims them);
    * ``scan``       — a selective-scan step ``exp(dtA)*h + gate*u``
      with a trailing contraction.

    Leaves are integer-valued float32 (``floor(u * 16) - 8``), so sums of
    squares and masked maxima are exact; the softmax and scan reductions
    are row-local in both the row-replay kernels and the XLA block
    fallback.
    """

    GRAMMARS = ("rmsnorm", "attention", "moe", "scan")

    def __init__(self, seed: int, *, size: int = 64):
        self.seed = int(seed)
        self.grammar = self.GRAMMARS[self.seed % 4]
        rnd = random.Random(self.seed ^ 0x1A57F00D)
        self.b = rnd.choice((1, 2))                   # batch
        self.s = rnd.choice((4, 8, 16))               # sequence
        self.d = max(8, min(128, int(size)))          # feature
        self.h = rnd.choice((1, 2, 4))                # heads
        self.n_exp = rnd.choice((4, 8))               # experts

    def _q16(self, rng, shape) -> np.ndarray:
        return (np.floor(rng.random(shape, dtype=np.float32) * 16.0)
                - 8.0).astype(np.float32)

    def _trace(self, rt) -> List[np.ndarray]:
        from repro.core import lazy as bh
        rng = np.random.default_rng(self.seed)
        b, s, d, h = self.b, self.s, self.d, self.h
        if self.grammar == "rmsnorm":
            x = rt.adopt(self._q16(rng, (b, s, d)))
            r = rt.adopt(self._q16(rng, (b, s, d)))
            g1 = rt.adopt(self._q16(rng, (1, 1, d)) / 16.0 + 1.0)
            y = x + r
            var = (y * y).sum(axis=-1)
            var_b = var.reshape(b, s, 1).broadcast_to((b, s, d))
            inv = bh.rsqrt(var_b / float(d) + 1e-6)
            out = y * inv * g1.broadcast_to((b, s, d))
            return [out.numpy()]
        if self.grammar == "attention":
            sc = rt.adopt(self._q16(rng, (b, h, s, s)))
            mask = rt.adopt(
                np.tril(np.ones((s, s), bool)).reshape(1, 1, s, s))
            neg = rt.adopt(np.full((1, 1, 1, 1), -1e30, np.float32))
            scm = bh.where(mask.broadcast_to(sc.shape), sc * 0.125, neg)
            m = scm.max(axis=-1)
            e = bh.exp(scm - m.reshape(b, h, s, 1).broadcast_to(scm.shape))
            z = e.sum(axis=-1)
            p = e / z.reshape(b, h, s, 1).broadcast_to(e.shape)
            return [p.numpy()]
        if self.grammar == "moe":
            t, k = b * s, 2
            logits = self._q16(rng, (t, self.n_exp)) \
                + rng.random((t, self.n_exp), dtype=np.float32) * 0.5
            topk = np.argsort(-logits, axis=1)[:, :k]     # host-side top-k
            picked = np.take_along_axis(logits, topk, axis=1)
            ex = np.exp(picked - picked.max(1, keepdims=True))
            gates = (ex / ex.sum(1, keepdims=True)).astype(np.float32)
            table = rt.adopt(self._q16(rng, (self.n_exp, d)))
            x = rt.adopt(self._q16(rng, (t, d)))
            out = None
            for j in range(k):
                idx = rt.adopt(topk[:, j].astype(np.int32))
                gate = rt.adopt(np.ascontiguousarray(gates[:, j:j + 1]))
                expert = bh.take(table, idx, axis=0)      # (t, d) gather
                term = x * expert * gate.broadcast_to((t, d))
                out = term if out is None else out + term
            return [out.numpy()]
        # scan: one selective-scan step + contraction
        dt_a = rt.adopt(-(self._q16(rng, (b, s, d)) / 16.0 + 0.5))
        hid = rt.adopt(self._q16(rng, (b, s, d)))
        upd = rt.adopt(self._q16(rng, (b, s, d)))
        gate = rt.adopt(self._q16(rng, (b, s, d)) / 16.0)
        h_new = bh.exp(dt_a) * hid + gate * upd
        y = (h_new * gate).sum(axis=-1)
        out = h_new + y.reshape(b, s, 1).broadcast_to((b, s, d))
        return [h_new.numpy(), out.numpy()]

    def run(self, **runtime_kw) -> List[np.ndarray]:
        from repro.core.lazy import fresh_runtime
        with fresh_runtime(**runtime_kw) as rt:
            return self._trace(rt)


# ---------------------------------------------------------------------------
# Differential checks
# ---------------------------------------------------------------------------

def _assert_bitwise(ref: Sequence[np.ndarray], got: Sequence[np.ndarray],
                    label: str) -> None:
    assert len(ref) == len(got), f"{label}: {len(ref)} vs {len(got)} outputs"
    for i, (r, g) in enumerate(zip(ref, got)):
        assert r.dtype == g.dtype and r.shape == g.shape, \
            f"{label}: output {i} meta {r.dtype}{r.shape} vs {g.dtype}{g.shape}"
        if r.tobytes() != g.tobytes():
            bad = int(np.flatnonzero(r.reshape(-1) != g.reshape(-1))[0])
            raise AssertionError(
                f"{label}: output {i} differs at flat index {bad}: "
                f"{r.reshape(-1)[bad]!r} vs {g.reshape(-1)[bad]!r}")


def check_graph(seed: int, *, n_actions: int = 20, size: int = 64,
                sharded: bool = False) -> None:
    """Staged graph builder == O(V²) reference oracle, edge for edge."""
    from repro.core import build_graph, build_graph_reference
    from repro.core.dist import insert_resharding, tape_has_sharding
    tape = TapeProgram(seed, n_actions=n_actions, size=size,
                       sharded=sharded).record()
    if tape_has_sharding(tape):
        tape = insert_resharding(tape)
    a = build_graph(list(tape))
    b = build_graph_reference(list(tape))
    assert a.dep_out == b.dep_out, f"seed {seed}: E_d (out) differs"
    assert a.dep_in == b.dep_in, f"seed {seed}: E_d (in) differs"
    assert a.fuse_forbidden == b.fuse_forbidden, f"seed {seed}: E_f differs"


def check_exec(seed: int, *, n_actions: int = 20, size: int = 64) -> None:
    """Fused (greedy; XLA and Pallas backend stacks) == unfused singleton
    XLA reference, bitwise."""
    prog = TapeProgram(seed, n_actions=n_actions, size=size, exact=True)
    ref = prog.run(algorithm="singleton", backend="xla")
    for algorithm, backend in (("greedy", "xla"), ("greedy", "pallas")):
        got = prog.run(algorithm=algorithm, backend=backend)
        _assert_bitwise(ref, got,
                        f"seed {seed} [{algorithm}/{backend} vs singleton]")


def check_dist(seed: int, *, n_actions: int = 20, size: int = 64,
               n_dev: int = 0) -> None:
    """Sharded COMM-inserting program: shard_map collectives on a device
    mesh == identity-copy COMM on a single device, bitwise."""
    import jax
    from repro.core.dist import host_mesh
    if n_dev <= 0:
        n_dev = len(jax.devices())
    if n_dev < 2:
        return                                 # nothing to compare against
    prog = TapeProgram(seed, n_actions=n_actions, size=size, exact=True,
                       sharded=True, n_shards=n_dev)
    ref = prog.run(algorithm="greedy", cost_model="comm", backend="xla")
    got = prog.run(algorithm="greedy", cost_model="comm", backend="xla",
                   mesh=host_mesh(n_dev))
    _assert_bitwise(ref, got, f"seed {seed} [mesh({n_dev}) vs single-device]")


def check_loop(seed: int, *, n_actions: int = 6, size: int = 64,
               steps: int = 9) -> None:
    """Loop-fused steady-state execution == per-flush execution, bitwise.

    A small threshold/unroll (2/4) forces the interesting transitions in
    one program: per-flush warmup, deferral, a capacity drain mid-run AND a
    tail drain at the final materialization.  Checked on both the XLA and
    the Pallas backend stacks (the loop body composes whatever per-block
    backends the lower stage picked)."""
    prog = IterativeProgram(seed, steps=steps, n_ops=n_actions, size=size)
    for backend in ("xla", "pallas"):
        ref = prog.run(loop_fusion=False, backend=backend)
        got = prog.run(loop_fusion=True, loop_threshold=2, loop_unroll=4,
                       backend=backend)
        _assert_bitwise(ref, got,
                        f"seed {seed} [{backend} loop-fused vs per-flush]")


def check_serve(seed: int, *, tenants: int = 4, requests: int = 2,
                n_actions: int = 8, size: int = 64) -> None:
    """Concurrent serving == serial execution, bitwise (DESIGN.md §18).

    Phase 1 — **concurrent sessions**: a seeded shuffle assigns ``tenants``
    distinct :class:`TapeProgram`\\ s to per-tenant sessions of ONE shared
    runtime; all tenants run simultaneously from their own threads (barrier
    start, many interleaved flushes against the shared merge/executable
    caches) and every tenant's outputs must match its own serial
    fresh-runtime run bit for bit.

    Phase 2 — **micro-batching**: every tenant submits the same seeded
    request recipe (same structure, private data, per-session RNG salts)
    through a batching :class:`~repro.core.serve.Server` concurrently; the
    reference is a batching-OFF server driven serially.  The vmapped
    batched dispatch must be bitwise identical to the per-session flush
    path — including ``random`` draws, which ride the salt matrix."""
    import threading

    from repro.core import lazy as bh
    from repro.core.lazy import Runtime
    from repro.core.serve import Server

    rnd = random.Random(seed ^ 0x5EABE17)

    # -- phase 1: N threads x N structurally-distinct programs ----------
    progs = [TapeProgram(rnd.randrange(1_000_000), n_actions=n_actions,
                         size=size, exact=True) for _ in range(tenants)]
    rnd.shuffle(progs)
    refs = [p.run() for p in progs]
    rt = Runtime(loop_fusion=False)
    sessions = [rt.session() for _ in range(tenants)]
    results: List = [None] * tenants
    errors: List = []
    barrier = threading.Barrier(tenants)

    def worker(i: int) -> None:
        try:
            barrier.wait()
            with sessions[i].activate():
                results[i] = progs[i].run_current()
        except BaseException as e:      # noqa: BLE001 — re-raised below
            errors.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(tenants)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise AssertionError(
            f"seed {seed}: concurrent session failed: {errors[0]!r}")
    for i in range(tenants):
        _assert_bitwise(refs[i], results[i],
                        f"seed {seed} [tenant {i} concurrent vs serial]")

    # -- phase 2: batched server vs serial batching-off server ----------
    def request_fn(rseed: int, data: np.ndarray):
        def fn():
            r = random.Random(rseed)
            a = bh.asarray(data)
            x = a
            for _ in range(n_actions):
                act = r.randrange(5)
                if act == 0:
                    x = bh.floor((x * r.choice((0.5, 2.0, 3.0))) % _MOD)
                elif act == 1:
                    x = x + float(r.randrange(-4, 5))
                elif act == 2:
                    x = bh.maximum(x, a)
                elif act == 3:
                    x = x + bh.floor(bh.random(x.shape) * 8.0)
                else:
                    x = bh.where(x > a, x, a)
            return x
        return fn

    npr = np.random.default_rng(seed)
    datas = [np.floor(npr.random(size) * 16.0) for _ in range(tenants)]
    rseeds = [rnd.randrange(1_000_000) for _ in range(requests)]

    ref_srv = Server(batching=False)
    refs2 = {(i, r): ref_srv.submit(i, request_fn(rs, datas[i]))
             for r, rs in enumerate(rseeds) for i in range(tenants)}

    srv = Server(window_s=0.25, max_batch=tenants)
    out2: dict = {}
    errors2: List = []
    barrier2 = threading.Barrier(tenants)

    def serve_worker(i: int) -> None:
        try:
            for r, rs in enumerate(rseeds):
                barrier2.wait()
                out2[(i, r)] = srv.submit(i, request_fn(rs, datas[i]))
        except BaseException as e:      # noqa: BLE001 — re-raised below
            errors2.append((i, e))

    threads = [threading.Thread(target=serve_worker, args=(i,))
               for i in range(tenants)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors2:
        raise AssertionError(
            f"seed {seed}: batched serve failed: {errors2[0]!r}")
    for k in refs2:
        _assert_bitwise([refs2[k]], [out2[k]],
                        f"seed {seed} [tenant/request {k} batched vs serial]")
    batched = srv.metrics.counter("serve.batched_requests").get()
    assert batched > 0, \
        f"seed {seed}: no request ever coalesced (window too small?)"


#: the grammars whose blocks the ``rowblock`` backend must claim (moe is
#: gather-dominated and stays on the XLA floor)
_ROW_GRAMMARS = ("rmsnorm", "attention", "scan")
#: largest ``max|lm - xla| / max|xla|`` of an output: both stacks run the
#: same jnp ops on the same rows, so they differ only where one contracts
#: a multiply-add into one rounding or sums a row in another order — a
#: few float32 ulps (~1e-7) — while a wrong op, row or mask moves the
#: result by far more than 1e-6
LM_RTOL = 1e-6


def check_lm(seed: int, *, size: int = 64) -> None:
    """LM stack == XLA stack to ``LM_RTOL``, under the SAME partition.

    Both runs use greedy/bohrium — partitioning is backend-independent, so
    the two stacks lower the *identical* block sequence and the comparison
    isolates the ``rowblock`` lowering from the XLA fallback (DESIGN.md
    §20).  For the grammars it should claim, the check also asserts the
    claim happened — a silently-declining screen would otherwise turn this
    into XLA vs XLA."""
    from repro.core.lazy import fresh_runtime
    prog = LMProgram(seed, size=size)
    kw = dict(algorithm="greedy", cost_model="bohrium", loop_fusion=False)
    ref = prog.run(backend="xla", **kw)
    with fresh_runtime(backend="lm", **kw) as rt:
        got = prog._trace(rt)
        blocks = dict(rt.executor.stats.get("backend_blocks", {}))
    label = f"seed {seed} [lm/{prog.grammar} vs xla]"
    assert len(ref) == len(got), f"{label}: {len(ref)} vs {len(got)} outputs"
    for i, (r, g) in enumerate(zip(ref, got)):
        assert r.dtype == g.dtype and r.shape == g.shape, f"{label}: {i}"
        err = float(np.max(np.abs(g.astype(np.float64) - r))
                    / np.max(np.abs(r)))
        assert err <= LM_RTOL, f"{label}: output {i} rel. error {err!r}"
    if prog.grammar in _ROW_GRAMMARS:
        assert blocks.get("rowblock", 0) >= 1, (
            f"seed {seed}: grammar {prog.grammar!r} never exercised the "
            f"'rowblock' backend (backend_blocks={blocks})")


CHECKS = {"graph": check_graph, "exec": check_exec, "dist": check_dist,
          "loop": check_loop, "serve": check_serve, "lm": check_lm}


def check_seed(seed: int, checks: Sequence[str] = ("graph", "exec"),
               **kw) -> None:
    """Run the named differential checks for one seed (raises on failure)."""
    for name in checks:
        if name == "graph":
            check_graph(seed, n_actions=kw.get("n_actions", 20),
                        size=kw.get("size", 64), sharded=bool(seed % 2))
        elif name == "exec":
            check_exec(seed, n_actions=kw.get("n_actions", 20),
                       size=kw.get("size", 64))
        elif name == "dist":
            check_dist(seed, n_actions=kw.get("n_actions", 20),
                       size=kw.get("size", 64), n_dev=kw.get("n_dev", 0))
        elif name == "loop":
            check_loop(seed, n_actions=max(3, kw.get("n_actions", 20) // 3),
                       size=kw.get("size", 64))
        elif name == "serve":
            check_serve(seed, n_actions=max(4, kw.get("n_actions", 20) // 3),
                        size=kw.get("size", 64))
        elif name == "lm":
            check_lm(seed, size=kw.get("size", 64))
        else:
            raise ValueError(f"unknown check {name!r}; have {sorted(CHECKS)}")


def main(argv: Optional[List[str]] = None) -> None:
    import argparse
    import sys
    import time
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=200,
                    help="number of consecutive seeds to sweep")
    ap.add_argument("--start", type=int, default=0, help="first seed")
    ap.add_argument("--only", type=int, default=None,
                    help="run a single seed (failure repro)")
    ap.add_argument("--actions", type=int, default=20,
                    help="generator actions per program")
    ap.add_argument("--size", type=int, default=64,
                    help="1-D working-shape elements")
    ap.add_argument("--checks", default="graph,exec,loop",
                    help=f"comma list from {sorted(CHECKS)}")
    ap.add_argument("--dist", action="store_true",
                    help="append the dist check (needs >= 2 devices, e.g. "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    args = ap.parse_args(argv)
    checks = [c for c in args.checks.split(",") if c]
    if args.dist and "dist" not in checks:
        checks.append("dist")
    seeds = ([args.only] if args.only is not None
             else list(range(args.start, args.start + args.n)))
    t0 = time.time()
    for i, seed in enumerate(seeds):
        try:
            check_seed(seed, checks, n_actions=args.actions, size=args.size)
        except Exception:
            print(f"\nFAIL seed={seed}  (checks: {','.join(checks)})",
                  file=sys.stderr)
            print("repro: PYTHONPATH=src python -m repro.testing.tapegen "
                  f"--only {seed} --actions {args.actions} "
                  f"--size {args.size} --checks {','.join(checks)}",
                  file=sys.stderr, flush=True)
            raise
        if (i + 1) % 25 == 0:
            print(f"  …{i + 1}/{len(seeds)} seeds ok "
                  f"({time.time() - t0:.0f}s)", flush=True)
    print(f"tapegen: {len(seeds)} seeds x [{','.join(checks)}] "
          f"differential-identical ({time.time() - t0:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
