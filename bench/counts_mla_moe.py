"""The operations and bytes a DeepSeek-V2 prefill requires, from its shapes
(``configs/dsv2lite.json``), as ``counts.py`` gives them for the dense
model: the least the algorithm needs, which a utilization or roofline share
divides by the time measured.

The routed experts count at their expected held share: each of a prompt's
``s * num_experts_per_tok`` assignments goes to a held expert with
probability ``held / n_experts`` under uniform routing, so the expected
routed rows of a layer are ``s * k * held / n_experts``.  A run's actual
rows are read from the program's ``moe.rows`` spans (``metrics/
moe.gmm_roofline.py``).
"""

from __future__ import annotations

from typing import Any, Dict


def held(cfg: Dict[str, Any]) -> int:
    start, stop = cfg["held_experts"]
    return stop - start


def router_width(cfg: Dict[str, Any]) -> int:
    """The router's outputs: the published expert count."""
    return cfg["published"]["n_routed_experts"]


def attention_params(cfg: Dict[str, Any]) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    return (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v)
            + h * v * d)


def expert_row_flops(cfg: Dict[str, Any]) -> int:
    """FLOPs of one routed row through one expert: gate, up, down."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def prefill_flops(cfg: Dict[str, Any], s: int) -> float:
    """Model FLOPs of one batch-1 prefill of ``s`` tokens that returns the
    last position's logits: 2 x weights x tokens for every projection,
    causal attention (the lower triangle of ``Q K^T`` over
    ``qk_head_dim`` and of ``P V`` over ``v_head_dim``) in every layer, the
    dense SwiGLU in the leading layers, and in the others the router over
    every expert, the shared experts and the held routed experts' expected
    rows; the head at the last position only."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = 2 * attention_params(cfg) * s + s * s * h * (qk
                                                       + cfg["v_head_dim"])
    mlp = 2 * 3 * d * cfg["intermediate_size"] * s
    e, k = router_width(cfg), cfg["num_experts_per_tok"]
    shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    moe = (2 * d * e * s + 2 * 3 * d * shared * s
           + s * k * held(cfg) / e * expert_row_flops(cfg))
    return (layers * attn + dense * mlp + (layers - dense) * moe
            + 2 * d * cfg["vocab_size"])


def expert_weight_bytes(cfg: Dict[str, Any], itemsize: int = 4) -> int:
    """Bytes of the held experts' weights that one ``ragged_matmul`` pass
    (gate, up or down of one layer) reads."""
    return held(cfg) * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * itemsize


def expert_row_bytes(cfg: Dict[str, Any], itemsize: int = 4) -> int:
    """Bytes one routed row moves through a layer's three passes: gate and
    up read ``d`` and write ``f`` each, down reads ``f`` and writes ``d``."""
    return 3 * (cfg["hidden_size"] + cfg["moe_intermediate_size"]) \
        * itemsize
