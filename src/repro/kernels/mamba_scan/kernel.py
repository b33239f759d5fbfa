"""Selective state-space (Mamba) scan as a Pallas TPU kernel (Jamba's SSM
layers).

    h_t = exp(Δ_t ⊙ A) ⊙ h_{t-1} + (Δ_t ⊙ x_t) ⊗ B_t
    y_t = h_t · C_t + D ⊙ x_t

State h is (d_inner × d_state), held in VMEM scratch across sequence chunks
(grid dim 1 is sequential on TPU).  HBM traffic = x, Δ, B, C, y only; the
O(T · d_inner · d_state) state history is contracted — never materialized —
which is exactly the paper's array contraction applied to a scan.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret


def _mamba_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_scr, *,
                  chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)            # (d_inner, d_state)
    d = d_ref[...].astype(jnp.float32)            # (1, d_inner)

    def body(t, h):
        x = x_ref[0, t].astype(jnp.float32)       # (d_inner,)
        dt = dt_ref[0, t].astype(jnp.float32)     # (d_inner,)
        bb = b_ref[0, t].astype(jnp.float32)      # (d_state,)
        cc = c_ref[0, t].astype(jnp.float32)      # (d_state,)
        da = jnp.exp(dt[:, None] * a)             # (d_inner, d_state)
        h = da * h + (dt * x)[:, None] * bb[None, :]
        y = jnp.einsum("is,s->i", h, cc,
                       preferred_element_type=jnp.float32) + d[0] * x
        y_ref[0, t] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, body, h_scr[...])


def mamba_scan(x, dt, b, c, a, d, *, chunk: int = 64,
               interpret: Optional[bool] = None):
    """x, dt: (B, T, d_inner); b, c: (B, T, d_state); a: (d_inner, d_state);
    d: (d_inner,).  Returns y: (B, T, d_inner)."""
    bsz, t, d_inner = x.shape
    d_state = b.shape[-1]
    ch = min(chunk, t)
    n_chunks = (t + ch - 1) // ch
    pad = n_chunks * ch - t
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))

    kernel = functools.partial(_mamba_kernel, chunk=ch)
    xspec = pl.BlockSpec((1, ch, d_inner), lambda i, j: (i, j, 0))
    sspec = pl.BlockSpec((1, ch, d_state), lambda i, j: (i, j, 0))
    y = pl.pallas_call(
        kernel,
        grid=(bsz, n_chunks),
        in_specs=[xspec, xspec, sspec, sspec,
                  pl.BlockSpec((d_inner, d_state), lambda i, j: (0, 0)),
                  pl.BlockSpec((1, d_inner), lambda i, j: (0, 0))],
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct((bsz, n_chunks * ch, d_inner), x.dtype),
        scratch_shapes=[_vmem((d_inner, d_state), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, dt, b, c, a, d[None])
    return y[:, :t]


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
