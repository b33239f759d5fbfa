"""Pallas kernels: the fused-block codegen behind the runtime's ``pallas``
backend and its row-replay variant behind ``rowblock``, plus plain jnp
references (attention, add+rmsnorm, the mamba and rwkv6 scans) that tests
and the direct model compare against."""

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
