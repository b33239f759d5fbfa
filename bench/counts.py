"""The operations and bytes each cell's work requires, from its shapes.

These are the least the algorithm needs, not what an implementation
happens to move or compute: a roofline or utilization share divides them
by the time measured, so extra copies or recomputation count against it.
"""

from __future__ import annotations

from typing import Any, Dict


def jacobi_bytes(n: int, itemsize: int) -> int:
    """HBM bytes of one Jacobi update of an ``n x n`` grid: read the grid
    once and write its ``(n-2) x (n-2)`` interior once."""
    return itemsize * (n * n + (n - 2) * (n - 2))


def jacobi_flops(n: int) -> int:
    """Three additions and one multiplication per interior point."""
    return 4 * (n - 2) * (n - 2)


def layer_params(cfg: Dict[str, Any]) -> int:
    """Weights of one dense decoder layer: q, k, v, o projections and the
    gated MLP's three matrices (norm gains are negligible and left out)."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return d * (2 * q + 2 * kv) + 3 * d * cfg["intermediate_size"]


def prefill_flops(cfg: Dict[str, Any], s: int) -> int:
    """Model FLOPs of one batch-1 prefill of ``s`` tokens that returns the
    last position's logits: 2 x weights x tokens in every layer, causal
    attention (the lower triangle of Q K^T and of P V, 2 s^2 d together)
    in every layer, and the head at the last position only."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    per_layer = 2 * layer_params(cfg) * s + 2 * s * s * d
    return layers * per_layer + 2 * d * cfg["vocab_size"]
