"""Batched flush compilation for cross-request micro-batching
(DESIGN.md §18).

When N concurrent serving requests trace structurally-identical tapes
inside one coalescing window, the server executes them as ONE dispatch:
the planned flush body — every fused block, composed exactly as the
per-flush dispatch engine would run it — is wrapped in ``jax.vmap`` over a
batched leading axis, so N requests cost one executable-cache probe and
one device program instead of N.

The composition mirrors ``loop_body.build_loop_fn``: per-block backend
builders are reused verbatim and chained through an env of tape-local
buffers, so the batched run performs the same primitive operations as N
per-flush runs — in the runtime's exact (dyadic) value domain the results
are bitwise identical, which the serve fuzzer (``tapegen check_serve``)
asserts.  RNG salts are per-request data: each request contributes one row
of the ``(B, R)`` salt matrix, so batched ``random`` ops draw exactly what
each request's solo flush would have drawn.
"""

from __future__ import annotations

from typing import Sequence, Tuple


def build_batch_fn(tape: Sequence, plans: Sequence,
                   tape_inputs: Tuple[int, ...],
                   tape_outputs: Tuple[int, ...], ctx):
    """Compose a planned flush into a vmapped multi-request executable.

    Returns ``(fn, n_rand)`` where ``fn(inputs, salts) -> outputs`` maps a
    tuple of ``(B, *base shape)`` stacked tape-input buffers and a ``(B,
    n_rand)`` int32 salt matrix to a tuple of ``(B, *base shape)`` stacked
    tape-output buffers (canonical ``tape_io`` order on all three).
    ``salts`` always carries the batch axis — even with ``n_rand == 0`` —
    so ``vmap`` has a mapped operand on tapes with no inputs.

    Blocks build on the backend their ``BlockPlan.lowering`` decision
    names; a builder failure raises ``BackendBuildError``, as in the
    dispatch engine (the server only batches schedules whose decisions are
    vmap-safe in the first place)."""
    import jax
    import jax.numpy as jnp

    from . import build_block

    work = []
    salt_off = 0
    for p in plans:
        if not p.has_work:
            continue
        ops = [tape[i] for i in p.op_indices]
        name = p.lowering.backend if p.lowering is not None else "xla"
        fn = build_block(name, ops, p, ctx)
        n_rand = sum(1 for op in ops if op.opcode == "random")
        work.append((fn, p.inputs, p.outputs, salt_off, n_rand))
        salt_off += n_rand
    total_rand = salt_off
    empty_salts = jnp.zeros((0,), dtype=jnp.int32)

    def flush_fn(inputs, salts_row):
        env = {u: b for u, b in zip(tape_inputs, inputs)}
        for fn, ins, outs, off, n_rand in work:
            s = salts_row[off:off + n_rand] if n_rand else empty_salts
            vals = fn(*[env[u] for u in ins], s)
            for u, b in zip(outs, vals):
                env[u] = b
        return tuple(env[u] for u in tape_outputs)

    return jax.vmap(flush_fn, in_axes=(0, 0)), total_rand
