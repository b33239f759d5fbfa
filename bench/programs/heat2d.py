"""The heat-equation stencil of the paper's Benchpress suite (Table I), on
the lazy array API: a Jacobi update of a 2-D grid, its interior set to the
mean of its four neighbours, one tape per iteration.

Copied from ``benchmarks/programs.py:heat_equation`` so that a change to
the repository's programs cannot change the benchmark's work.  The grid's
interior starts random from the seed, with a hot top row, so that every
seed gives the same work on other data.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from bench import counts

HOT = 100.0


def initial(cfg: Dict[str, Any], seed: int) -> np.ndarray:
    n = int(cfg["n"])
    g = np.zeros((n, n), cfg["dtype"])
    g[1:-1, 1:-1] = np.random.default_rng(seed).random(
        (n - 2, n - 2), dtype=np.float32)
    g[0, :] = HOT
    return g


def adopt(bh, grid: np.ndarray):
    return bh.asarray(grid)


def step(bh, g) -> None:
    n = g.shape[0]
    inner = (g[1:-1, :-2] + g[1:-1, 2:] + g[:-2, 1:-1]
             + g[2:, 1:-1]) * 0.25
    g[1:n - 1, 1:n - 1] = inner
    inner.delete()


def read(g) -> np.ndarray:
    return g.numpy()


def work(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Required bytes and FLOPs of one iteration."""
    n = int(cfg["n"])
    return {"bytes": counts.jacobi_bytes(n, np.dtype(cfg["dtype"]).itemsize),
            "flops": counts.jacobi_flops(n)}
