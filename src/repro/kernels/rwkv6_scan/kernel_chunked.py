"""RWKV6 CHUNKED-PARALLEL Pallas kernel — the MXU formulation.

The token-recurrent kernel (kernel.py) does T rank-1 VPU updates; the MXU
sits idle.  This variant processes chunks of C tokens with three matmuls
(the GLA/flash-linear-attention factorization, adapted to RWKV6's
per-channel data-dependent decay):

With inclusive per-channel decay products  Cum_t = ∏_{τ≤t} w_τ  (Cum_0=1):

    r̃_t = r_t ⊙ Cum_{t-1}          k̃_τ = k_τ / Cum_τ
    o_t  = r̃_t · S_0                               (inter-chunk, matmul)
         + Σ_{τ<t} (r̃_t · k̃_τ) v_τ                (intra, masked matmul)
         + ((r_t ⊙ u) · k_t) v_t                   (bonus diagonal)
    S_C  = diag(Cum_C) (S_0 + k̃ᵀ V)               (state update, matmul)

Numerics: 1/Cum explodes for long chunks (w^C underflows), so C=32 keeps
the dynamic range inside f32 for decays ≥ ~0.4 — the trade documented in
EXPERIMENTS.md §Perf(3).  All three inner products are 128-aligned matmuls
when N=64 is padded/blocked — MXU work instead of VPU rank-1 updates.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret


def _rwkv6_chunk_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *,
                        chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)           # (C, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)           # (N,)
    s0 = s_scr[...]                            # (N, N)

    cum = jnp.cumprod(w, axis=0)               # (C, N) inclusive
    cum_prev = jnp.concatenate([jnp.ones((1, w.shape[1]), jnp.float32),
                                cum[:-1]], axis=0)
    r_t = r * cum_prev
    k_t = k / cum

    inter = jax.lax.dot_general(r_t, s0, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    scores = jax.lax.dot_general(r_t, k_t, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    ti = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(tj < ti, scores, 0.0)   # strictly causal
    bonus = jnp.sum((r * u[None]) * k, axis=1)  # (C,) diagonal term
    intra = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o = inter + intra + bonus[:, None] * v
    o_ref[0] = o.astype(o_ref.dtype)

    ktv = jax.lax.dot_general(k_t, v, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    s_scr[...] = cum[-1][:, None] * (s0 + ktv)


def rwkv6_chunked(r, k, v, w, u, *, chunk: int = 32,
                  interpret: Optional[bool] = None):
    """Same contract as ``rwkv6_scan`` (r,k,v,w: (BH,T,N); u: (N,))."""
    bh, t, n = r.shape
    c = min(chunk, t)
    n_chunks = (t + c - 1) // c
    pad = n_chunks * c - t
    if pad:
        z = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
        r, k, v = z(r), z(k), z(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)

    kernel = functools.partial(_rwkv6_chunk_kernel, chunk=c)
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
