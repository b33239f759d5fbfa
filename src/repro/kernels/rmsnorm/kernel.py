"""Fused residual-add + RMSNorm + scale Pallas kernel.

One HBM round-trip for the (x, residual) pair instead of three (add, norm,
scale) — the transformer-layer analogue of the paper's loop fusion + array
contraction: the sum and the reciprocal-rms live only in VMEM.

Grid tiles rows (tokens); the model dimension stays whole per tile (norm is
a row reduction).  Supports the two scale conventions used by the assigned
archs: ``(1+g)`` (gemma2) and ``g`` (llama/qwen/starcoder).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret


def _rmsnorm_kernel(x_ref, res_ref, g_ref, y_ref, resid_ref, *,
                    eps: float, plus_one: bool):
    x = x_ref[...].astype(jnp.float32)
    r = res_ref[...].astype(jnp.float32)
    h = x + r
    resid_ref[...] = h.astype(resid_ref.dtype)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    g = g_ref[...].astype(jnp.float32)
    if plus_one:
        g = g + 1.0
    y_ref[...] = (h * inv * g).astype(y_ref.dtype)


def fused_add_rmsnorm(x: jnp.ndarray, residual: jnp.ndarray,
                      gamma: jnp.ndarray, *, eps: float = 1e-6,
                      plus_one: bool = False, block_rows: int = 128,
                      interpret: Optional[bool] = None):
    """x, residual: (..., N, D); gamma: (D,).  Returns (normed, new_residual).

    ``new_residual = x + residual`` is emitted too (the standard pre-norm
    transformer needs both), still in one HBM pass.
    """
    orig_shape = x.shape
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    r2 = residual.reshape(-1, d)
    n = x2.shape[0]
    br = min(block_rows, _round_up(n, 8))
    n_pad = _round_up(n, br)
    if n_pad != n:
        x2 = jnp.pad(x2, ((0, n_pad - n), (0, 0)))
        r2 = jnp.pad(r2, ((0, n_pad - n), (0, 0)))

    kernel = functools.partial(_rmsnorm_kernel, eps=eps, plus_one=plus_one)
    y, resid = pl.pallas_call(
        kernel,
        grid=(n_pad // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, d), x.dtype),
            jax.ShapeDtypeStruct((n_pad, d), x.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(x2, r2, gamma)
    return (y[:n].reshape(orig_shape), resid[:n].reshape(orig_shape))


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
