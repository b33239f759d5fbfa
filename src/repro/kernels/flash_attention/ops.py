"""Public attention op: jit wrapper + custom VJP.

Forward = the Pallas flash kernel (interpret mode on CPU, compiled on TPU).
Backward = VJP of the jnp reference (XLA recompute — standard fallback while
a hand-written dq/dk/dv kernel is not required for the dry-run target).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import flash_attention
from .ref import reference_attention


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def attention(q, k, v, causal: bool = True, window: Optional[int] = None,
              softcap: Optional[float] = None, scale: Optional[float] = None,
              interpret: Optional[bool] = None):
    return flash_attention(q, k, v, causal=causal, window=window,
                           softcap=softcap, scale=scale, interpret=interpret)


def _fwd(q, k, v, causal, window, softcap, scale, interpret):
    out = attention(q, k, v, causal, window, softcap, scale, interpret)
    return out, (q, k, v)


def _bwd(causal, window, softcap, scale, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: reference_attention(
        q_, k_, v_, causal=causal, window=window, softcap=softcap,
        scale=scale), q, k, v)
    return vjp(g)


attention.defvjp(_fwd, _bwd)
