"""Loop-body compilation for cross-flush loop fusion (DESIGN.md §16).

A steady-state iterative workload re-flushes a structurally-identical tape
every timestep.  Once the recurrence detector proves the structure repeats
with a consistent carried-state mapping, the whole flush body — every fused
block, lowered on whatever backend the lower stage picked for it — is
composed into ONE function and iterated with ``jax.lax.fori_loop``: carried
bases become loop state, per-iteration executable dispatch and host
round-trips disappear, and XLA sees the time loop as a single program.

The composition reuses the per-block backend builders verbatim (``xla``
block fns, tiled Pallas kernels, …), so a loop-lowered run performs exactly
the same primitive operations in the same order as the per-flush run — the
bitwise-equivalence story of the backend layer extends across the iteration
boundary (differentially tested, and fuzzed by tapegen's iterative mode).

RNG salts are the one per-iteration datum: each flush's ``random`` ops carry
fresh trace-time salts, so the loop executable takes a ``(capacity, R)``
salt matrix and each iteration indexes its own row — drawn values match the
per-flush path bit for bit.
"""

from __future__ import annotations

from typing import Sequence, Tuple


def build_loop_fn(tape: Sequence, plans: Sequence,
                  input_sources: Tuple,
                  tape_inputs: Tuple[int, ...],
                  tape_outputs: Tuple[int, ...], ctx):
    """Compose a planned flush into a steady-state loop executable.

    Returns ``fn(n, salts, invariants, state) -> state`` where ``state`` is
    one buffer per tape-level output (canonical order), ``invariants`` one
    buffer per loop-invariant input, ``salts`` the stacked per-iteration RNG
    salt rows, and ``n`` the (traced) iteration count — one compiled
    executable serves every drain size up to the salt matrix's capacity.

    ``input_sources[j]`` says where input position ``j`` of each iteration
    comes from: ``("carry", q)`` reads loop state slot ``q`` (the previous
    iteration's output ``q``), ``("inv", k)`` reads invariant ``k``.  Blocks
    build on the backend their ``BlockPlan.lowering`` decision names, with
    the same rule as the per-flush dispatch engine: a builder failure raises
    ``BackendBuildError``."""
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

    # invariant buffers index by their *input position* (the mapping's
    # ("inv", j) carries j), so hand each block its buffer via a dense map
    inv_positions = tuple(j for j, s in enumerate(input_sources)
                          if s[0] == "inv")
    inv_index = {j: k for k, j in enumerate(inv_positions)}

    def loop_fn(n, salts, invariants, state):
        def body(i, state):
            env = {}
            for j, u in enumerate(tape_inputs):
                kind, idx = input_sources[j]
                env[u] = (state[idx] if kind == "carry"
                          else invariants[inv_index[idx]])
            row = (jax.lax.dynamic_index_in_dim(salts, i, 0, keepdims=False)
                   if total_rand else None)
            for fn, ins, outs, off, n_rand in work:
                s = row[off:off + n_rand] if n_rand else empty_salts
                vals = fn(*[env[u] for u in ins], s)
                for u, b in zip(outs, vals):
                    env[u] = b
            return tuple(env[u] for u in tape_outputs)
        return jax.lax.fori_loop(0, n, body, tuple(state))

    return loop_fn
