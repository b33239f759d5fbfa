"""Public Mamba scan op with custom VJP (reference backward)."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .kernel import mamba_scan
from .ref import reference_mamba


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def mamba(x, dt, b, c, a, d, chunk: int = 64,
          interpret: Optional[bool] = None):
    return mamba_scan(x, dt, b, c, a, d, chunk=chunk, interpret=interpret)


def _fwd(x, dt, b, c, a, d, chunk, interpret):
    return mamba(x, dt, b, c, a, d, chunk, interpret), (x, dt, b, c, a, d)


def _bwd(chunk, interpret, res, g):
    x, dt, b, c, a, d = res
    _, vjp = jax.vjp(reference_mamba, x, dt, b, c, a, d)
    return vjp(g)


mamba.defvjp(_fwd, _bwd)
