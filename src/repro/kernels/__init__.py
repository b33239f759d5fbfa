"""Pallas kernels: the fused-block codegen behind the runtime's ``pallas``
and LM-claimant backends, and the hand-written LM kernels."""

from __future__ import annotations

from typing import Optional


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode for one kernel build: what the caller asked
    for, else True exactly when JAX's default backend is not a TPU.  Every
    kernel's ``interpret=None`` default resolves here, so nothing on the
    chip runs interpreted unless a caller asks for it."""
    if interpret is None:
        import jax
        return jax.default_backend() != "tpu"
    return bool(interpret)
