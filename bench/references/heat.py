"""Plain reference of the heat-equation stencil: the same Jacobi update in
``jax.numpy``, iterated by ``lax.fori_loop``, with nothing of the program.

:func:`compare` decides ``correct`` for a grid the program produced: the
largest difference from the reference, as a share of the reference's
largest magnitude.  Both compute each update in the same order in float32,
so a sound run reads 0 or a few rounding steps; a run computed in
bfloat16, one that skipped or repeated an iteration, or one that altered a
cell of the grid reads orders of magnitude above the limit.  The limit's
readings are in ``PERF.md`` (section 2).
"""

from __future__ import annotations

from typing import List

import numpy as np

#: max |got - ref| / max |ref| after the window's iterations
GRID_ERR_LIMIT = 1e-5


def _jacobi(g):
    inner = (g[1:-1, :-2] + g[1:-1, 2:] + g[:-2, 1:-1] + g[2:, 1:-1]) * 0.25
    return g.at[1:-1, 1:-1].set(inner)


def reference(initial: np.ndarray, iterations: int,
              dtype: str = "float32") -> np.ndarray:
    """The grid after ``iterations`` updates of ``initial``, computed in
    ``dtype`` (the control computes it in bfloat16) and returned as
    float32."""
    import jax
    import jax.numpy as jnp

    run = jax.jit(lambda g, n: jax.lax.fori_loop(
        0, n, lambda _, x: _jacobi(x), g))
    g = jnp.asarray(initial, dtype=dtype)
    out = run(g, jnp.int32(iterations))
    return np.asarray(out.astype(jnp.float32))


def compare(got: np.ndarray, ref: np.ndarray) -> List:
    from bench.harness import Check

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return [Check("grid_shape", float("inf"), 0.0)]
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    return [Check("grid_err", err if np.isfinite(err) else float("inf"),
                  GRID_ERR_LIMIT)]
