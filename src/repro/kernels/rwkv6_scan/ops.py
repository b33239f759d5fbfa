"""Public RWKV6 scan op with custom VJP (reference backward)."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .kernel import rwkv6_scan
from .ref import reference_rwkv6


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def rwkv6(r, k, v, w, u, chunk: int = 64,
          interpret: Optional[bool] = None):
    return rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=interpret)


def _fwd(r, k, v, w, u, chunk, interpret):
    return rwkv6(r, k, v, w, u, chunk, interpret), (r, k, v, w, u)


def _bwd(chunk, interpret, res, g):
    r, k, v, w, u = res
    _, vjp = jax.vjp(reference_rwkv6, r, k, v, w, u)
    return vjp(g)


rwkv6.defvjp(_fwd, _bwd)
