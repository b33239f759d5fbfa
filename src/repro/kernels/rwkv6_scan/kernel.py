"""RWKV6 (Finch) recurrence as a Pallas TPU kernel.

Per head, the state is an (N_k × N_v) matrix updated with a data-dependent
per-channel decay (the RWKV6 novelty vs RWKV5's static decay):

    wkv_t = S + diag(u) · k_tᵀ v_t
    o_t   = r_t · wkv_t
    S     = diag(w_t) · S + k_tᵀ v_t

The kernel walks the sequence in chunks (grid dim 1, sequential on TPU) with
the state held in VMEM scratch — HBM traffic is exactly r,k,v,w,o (the WSP
``ext`` set of the fused scan; the state is contracted).  The token loop
inside a chunk is a ``fori_loop`` of rank-1 updates on the VMEM-resident
state.  A chunked matmul (intra-chunk parallel) formulation is the §Perf
hillclimb variant — see EXPERIMENTS.md.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *,
                  chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[0].astype(jnp.float32)            # (N,)

    def body(t, state):
        r = r_ref[0, t].astype(jnp.float32)     # (N,)
        k = k_ref[0, t].astype(jnp.float32)
        v = v_ref[0, t].astype(jnp.float32)
        w = w_ref[0, t].astype(jnp.float32)     # decay in (0,1)
        kv = k[:, None] * v[None, :]            # (N, N) rank-1
        wkv = state + u[:, None] * kv
        o = jnp.einsum("i,ij->j", r, wkv,
                       preferred_element_type=jnp.float32)
        o_ref[0, t] = o.astype(o_ref.dtype)
        return w[:, None] * state + kv

    s_scr[...] = jax.lax.fori_loop(0, chunk, body, s_scr[...])


def rwkv6_scan(r, k, v, w, u, *, chunk: int = 64,
               interpret: Optional[bool] = None):
    """r,k,v,w: (BH, T, N); u: (N,).  Returns o: (BH, T, N).

    ``w`` is the per-token per-channel decay (already exp(-exp(...))'d).
    """
    bh, t, n = r.shape
    assert t % chunk == 0 or t < chunk, (t, chunk)
    c = min(chunk, t)
    n_chunks = (t + c - 1) // c
    pad = n_chunks * c - t
    if pad:
        z = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
        r, k, v = z(r), z(k), z(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)

    kernel = functools.partial(_rwkv6_kernel, chunk=c)
    spec = pl.BlockSpec((1, c, n), lambda b, i: (b, i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, n), lambda b, i: (0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bh, n_chunks * c, n), r.dtype),
        scratch_shapes=[_vmem((n, n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(r, k, v, w, u[None])
    return out[:, :t]


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
