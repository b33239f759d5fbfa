"""Public fused add+RMSNorm op with custom VJP (reference backward)."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .kernel import fused_add_rmsnorm
from .ref import reference_add_rmsnorm


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def add_rmsnorm(x, residual, gamma, eps: float = 1e-6,
                plus_one: bool = False, interpret: Optional[bool] = None):
    return fused_add_rmsnorm(x, residual, gamma, eps=eps, plus_one=plus_one,
                             interpret=interpret)


def _fwd(x, residual, gamma, eps, plus_one, interpret):
    out = add_rmsnorm(x, residual, gamma, eps, plus_one, interpret)
    return out, (x, residual, gamma)


def _bwd(eps, plus_one, interpret, res, g):
    x, residual, gamma = res
    _, vjp = jax.vjp(lambda a, b, c: reference_add_rmsnorm(
        a, b, c, eps=eps, plus_one=plus_one), x, residual, gamma)
    return vjp(g)


add_rmsnorm.defvjp(_fwd, _bwd)
