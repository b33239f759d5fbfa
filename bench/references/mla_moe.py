"""Plain reference of DeepSeek-V2's decoder (arXiv:2405.04434 §2):
``jax.numpy`` in float32, one layer at a time, with nothing of the program.

Per layer: RMSNorm; multi-head latent attention without a query low-rank
(``q = h W_q`` split into ``q_nope`` and ``q_pe``; ``[c_kv | k_pe] = h
W_kv_a``; ``c_kv`` RMSNormed and up-projected by ``W_kv_b`` to per-head
``k_nope`` and ``v``; rotary embedding of ``q_pe`` and of ``k_pe``, which
every head shares, with YaRN's frequencies; causal softmax of ``[q_nope |
q_pe] . [k_nope | k_pe]`` scaled by ``qk_head_dim**-0.5 * m**2``, ``m =
0.1 * mscale_all_dim * ln(factor) + 1``; ``W_o``) and the residual;
RMSNorm, then a SwiGLU MLP in the leading dense layers or, after them, the
routed experts plus the shared experts, and the residual.  A final RMSNorm
and the untied head give the logits.

The routed experts: softmax over all ``n_routed_experts`` router logits,
the top ``num_experts_per_tok`` by ``jax.lax.top_k`` (lower index first on
ties), gates not renormalised, times ``routed_scaling_factor``.  Each held
expert's SwiGLU runs on every token, weighted by the token's gate for it
(zero where it is not among the token's top-k): dense, no sort, computed
one held expert at a time.  Experts outside the held range are the other
chips' share of the deployment (the configuration's ``reduced`` entry).

It reads the weights in the layout the benchmark makes them
(``systems/lazy_mla_moe.make_weights``).  Matrix products take a ``matmul``
of one of two kinds, as in ``dense_mha``: ``highest`` (float32, as the
configuration states) and the control's ``bf16x3``.

**What decides ``correct`` (:func:`check`, :func:`compare`).**  Program
and reference can route a token differently where its k-th and (k+1)-th
router logits lie within rounding of each other: the two sum the router's
input in different orders, so their router logits differ by up to
:data:`ROUTER_LOGIT_DIFF` (measured on the chip; ``PERF.md``, section 2),
and a different k-th expert moves the logits far past rounding.  Either
expert is then a right answer.  So a request whose logits miss
:data:`LOGIT_ERR_LIMIT` under the reference's own routing is checked again
under the routings rounding allows: the k-th and (k+1)-th experts of one or
two of its :data:`NEAR_TIE_TRIES` nearest ties swapped, where a tie is a
token of an expert layer whose gap between the two logits is under
:data:`NEAR_TIE_DELTA`, a stated multiple of the measured difference, and
one of the two experts is held (the held experts' output depends on no
other boundary).  The request's ``logit_err`` is the least over those
routings, and it counts in ``near_tie_requests`` when a swap gave it.  The
run fails if ``logit_err`` misses its limit for any request, or if more
than a quarter of the checked requests needed a swap.  No request is left
out.
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

#: max over the checked requests of max |got - ref| / max |ref| of the
#: last-position logits, between the sound runs' highest reading on the
#: chip (8.8e-7) and the ``bf16x3`` control's lowest (1.2e-5; ``PERF.md``,
#: section 2)
LOGIT_ERR_LIMIT = 3e-6
#: the largest |router logit| difference between program and reference
#: measured on the chip, over every expert layer and token of 8 prompts
#: (``PERF.md``, section 2)
ROUTER_LOGIT_DIFF = 2.74e-6
#: a tie's largest gap: 10 x the measured router-logit difference, so that
#: every boundary rounding can move lies inside it
NEAR_TIE_DELTA = 10 * ROUTER_LOGIT_DIFF
#: the nearest ties of a request whose swaps are tried, one at a time and
#: then in pairs
NEAR_TIE_TRIES = 4
#: the most of the checked requests that may need a swap
NEAR_TIE_SHARE = 0.25


def mm_highest(a, b):
    import jax
    import jax.numpy as jnp
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def mm_bf16x3(a, b):
    """``a @ b`` from bfloat16 parts, as ``high`` precision computes it on
    a TPU: hi*hi + hi*lo + lo*hi (``references/dense_mha.py``)."""
    import jax

    def part(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    a_hi, b_hi = part(a), part(b)
    a_lo, b_lo = part(a - a_hi), part(b - b_hi)
    return (mm_highest(a_hi, b_hi) + mm_highest(a_hi, b_lo)
            + mm_highest(a_lo, b_hi))


MATMULS = {"highest": mm_highest, "bf16x3": mm_bf16x3}


def _rmsnorm(x, g, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def yarn(cfg: Dict[str, Any]):
    """YaRN's inverse frequencies (float32) of the rope dimensions, the
    factor on the cos/sin tables and the softmax scale, from the
    ``rope_scaling`` entry of ``config.json`` (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``), in float64 on the host."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    ys = cfg["rope_scaling"]
    f = float(ys["factor"])
    orig = ys["original_max_position_embeddings"]

    def mscale(m):
        return 1.0 if f <= 1 else 0.1 * m * math.log(f) + 1.0

    def dim_at(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_at(ys["beta_fast"])), 0)
    high = min(math.ceil(dim_at(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    j = np.arange(dim // 2, dtype=np.float64)
    mask = 1.0 - np.clip((j - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / f
    inv = inter * (1.0 - mask) + extra * mask
    table = mscale(ys["mscale"]) / mscale(ys["mscale_all_dim"])
    scale = cfg["qk_nope_head_dim"] + dim
    return (inv.astype(np.float32), table,
            scale ** -0.5 * mscale(ys["mscale_all_dim"]) ** 2)


def _rope(x, inv, table):
    """Rotary embedding of ``(s, heads, dim)`` at positions 0..s-1, the
    two halves of ``dim`` rotated together."""
    import jax.numpy as jnp
    s, _, dim = x.shape
    half = dim // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    cos = (jnp.cos(ang) * np.float32(table))[:, None, :]
    sin = (jnp.sin(ang) * np.float32(table))[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _w(tree, *path):
    for p in path:
        tree = tree[p]
    return tree[0]


def _attention(x, lw, *, cfg, inv, table, scale, mm):
    import jax
    import jax.numpy as jnp
    s, d = x.shape
    heads, eps = cfg["num_attention_heads"], float(cfg["rms_norm_eps"])
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    h = _rmsnorm(x, _w(lw, "norm1", "g"), eps)
    q = mm(h, _w(lw, "mixer", "wq")).reshape(s, heads, nope + rope)
    kv_a = mm(h, _w(lw, "mixer", "wkv_a"))
    c = _rmsnorm(kv_a[:, :r], _w(lw, "mixer", "kv_norm", "g"), eps)
    k_pe = _rope(kv_a[:, r:].reshape(s, 1, rope), inv, table)
    kv = mm(c, _w(lw, "mixer", "wkv_b")).reshape(s, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, table)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (s, heads, rope))], -1)
    v = kv[..., nope:]
    qh, kh, vh = (t.transpose(1, 0, 2) for t in (q, k, v))
    scores = mm(qh, kh.transpose(0, 2, 1)) * np.float32(scale)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = mm(p, vh).transpose(1, 0, 2).reshape(s, heads * vd)
    return x + mm(o, _w(lw, "mixer", "wo"))


def _swiglu(h, wg, wu, wd, mm):
    import jax
    return mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)


def _dense_ffn(x, lw, *, cfg, mm):
    h = _rmsnorm(x, _w(lw, "norm2", "g"), float(cfg["rms_norm_eps"]))
    f = lw["ffn"]
    return x + _swiglu(h, f["w_gate"][0], f["w_up"][0], f["w_down"][0], mm)


def _moe_ffn(x, lw, swap, *, cfg, mm):
    """The layer's output; each token's gap between its k-th and (k+1)-th
    router logit, infinite where neither expert is held (module doc); and
    the router logits.  A token where ``swap`` is set takes its (k+1)-th
    expert in place of its k-th."""
    import jax
    import jax.numpy as jnp
    h = _rmsnorm(x, _w(lw, "norm2", "g"), float(cfg["rms_norm_eps"]))
    f = lw["ffn"]
    k = cfg["num_experts_per_tok"]
    start, stop = cfg["held_experts"]
    logits = mm(h, f["router"][0])                          # (s, experts)
    probs = jax.nn.softmax(logits, axis=-1)
    _, ranked = jax.lax.top_k(probs, k + 1)
    top = ranked[:, :k].at[:, k - 1].set(
        jnp.where(swap, ranked[:, k], ranked[:, k - 1]))
    chosen = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top].set(1.0)
    gates = probs * chosen * np.float32(cfg["routed_scaling_factor"])
    edge = jnp.take_along_axis(logits, ranked[:, k - 1:], axis=1)
    held = (ranked[:, k - 1:] >= start) & (ranked[:, k - 1:] < stop)
    gap = jnp.where(held.any(axis=-1), edge[:, 0] - edge[:, 1], jnp.inf)

    def expert(e, acc):
        y = _swiglu(h, f["w_gate"][0, e], f["w_up"][0, e],
                    f["w_down"][0, e], mm)
        return acc + y * jax.lax.dynamic_index_in_dim(
            gates, start + e, axis=1, keepdims=True)
    routed = jax.lax.fori_loop(0, stop - start, expert, jnp.zeros_like(x))
    shared = f["shared"]
    y = routed + _swiglu(h, shared["w_gate"][0], shared["w_up"][0],
                         shared["w_down"][0], mm)
    return x + y, gap, logits


def _key(cfg: Dict[str, Any]):
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["kv_lora_rank"], cfg["v_head_dim"],
            float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
            tuple(sorted(cfg["rope_scaling"].items())),
            cfg["num_experts_per_tok"], tuple(cfg["held_experts"]),
            float(cfg["routed_scaling_factor"]))


@functools.lru_cache(maxsize=None)
def _programs(key, matmul: str, all_positions: bool):
    import jax
    import jax.numpy as jnp
    (heads, nope, rope, r, vd, eps, theta, scaling, k, held,
     routed_scale) = key
    cfg = {"num_attention_heads": heads, "qk_nope_head_dim": nope,
           "qk_rope_head_dim": rope, "kv_lora_rank": r, "v_head_dim": vd,
           "rms_norm_eps": eps, "rope_theta": theta,
           "rope_scaling": dict(scaling), "num_experts_per_tok": k,
           "held_experts": held, "routed_scaling_factor": routed_scale}
    mm = MATMULS[matmul]
    inv, table, scale = yarn(cfg)
    attend = jax.jit(functools.partial(_attention, cfg=cfg, inv=inv,
                                       table=table, scale=scale, mm=mm))
    dense = jax.jit(functools.partial(_dense_ffn, cfg=cfg, mm=mm))
    moe = jax.jit(functools.partial(_moe_ffn, cfg=cfg, mm=mm))

    def head(x, final_g, lm_head):
        x = x if all_positions else x[-1:]
        return mm(_rmsnorm(x, final_g, eps), lm_head)

    embed = jax.jit(lambda table_, ids: jnp.take(table_, ids, axis=0))
    return embed, attend, dense, moe, jax.jit(head)


def forward(weights: Dict[str, Any], tokens: np.ndarray, cfg: Dict[str, Any],
            matmul: str = "highest", all_positions: bool = False,
            swaps: Sequence[Tuple[int, int]] = ()):
    """Logits of one prompt (``tokens``: ``(length,)`` ids), the last
    position's ``(1, vocab)`` or every position's ``(length, vocab)``; each
    expert layer's gaps (``(length,)``, module doc); and each expert
    layer's router logits ``(length, experts)``.  ``swaps``: ``(expert
    layer, token)`` pairs that take their (k+1)-th expert in place of
    their k-th."""
    import jax.numpy as jnp
    embed, attend, dense, moe, head = _programs(_key(cfg), matmul,
                                                all_positions)
    x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    gaps, routers = [], []
    for i in range(cfg["num_hidden_layers"]):
        lw = weights["groups"][f"l{i}"]
        x = attend(x, lw)
        if i < cfg["first_k_dense_replace"]:
            x = dense(x, lw)
            continue
        swap = np.zeros(len(tokens), bool)
        swap[[t for layer, t in swaps if layer == len(gaps)]] = True
        x, gap, router = moe(x, lw, jnp.asarray(swap))
        gaps.append(np.asarray(gap))
        routers.append(router)
    return (head(x, weights["final_norm"]["g"], weights["lm_head"]), gaps,
            routers)


def rel_err(got, ref) -> float:
    """max |got - ref| / max |ref|; infinite for a wrong shape or a value
    that is not finite."""
    g = np.asarray(got, np.float64).reshape(-1)
    r = np.asarray(ref, np.float64).reshape(-1)
    if g.shape != r.shape or not np.all(np.isfinite(g)):
        return float("inf")
    return float(np.max(np.abs(g - r)) / np.max(np.abs(r)))


def nearest_ties(gaps: Sequence[np.ndarray], n: int) -> List[Tuple]:
    """The ``n`` smallest gaps under :data:`NEAR_TIE_DELTA` that can reach
    the last position's logits (in the last expert layer, only the last
    token's), as ``(gap, expert layer, token)``, smallest first."""
    last = len(gaps) - 1
    ties = [(float(g[t]), layer, int(t)) for layer, g in enumerate(gaps)
            for t in np.flatnonzero(g < NEAR_TIE_DELTA)
            if layer < last or t == len(g) - 1]
    return sorted(ties)[:n]


def check(weights: Dict[str, Any], tokens: np.ndarray, cfg: Dict[str, Any],
          got, matmul: str = "highest", search: bool = True) -> Tuple:
    """One request (module doc): ``got`` is the served last-position logits
    of ``tokens``.  Returns its ``logit_err``, the number of swaps that gave
    it (0: the reference's own routing), the largest gap those swaps
    crossed (0.0 without one) and the request's smallest gap.  With
    ``search`` false no swap is tried."""
    ref, gaps, _ = forward(weights, tokens, cfg, matmul)
    err, used, crossed = rel_err(got, ref), 0, 0.0
    ties = nearest_ties(gaps, NEAR_TIE_TRIES)
    nearest = min((float(g.min()) for g in gaps), default=float("inf"))
    if err <= LOGIT_ERR_LIMIT or not search:
        return err, used, crossed, nearest
    combos = [(t,) for t in ties] + list(itertools.combinations(ties, 2))
    for combo in combos:
        r, _, _ = forward(weights, tokens, cfg, matmul,
                          swaps=[(layer, t) for _, layer, t in combo])
        e = rel_err(got, r)
        if e < err:
            err, used, crossed = e, len(combo), max(g for g, _, _ in combo)
        if err <= LOGIT_ERR_LIMIT:
            break
    return err, used, crossed, nearest


def compare(errs: Sequence[float], swaps: Sequence[int],
            crossed: Sequence[float]) -> List:
    """The run's checks from each checked request's :func:`check`, each
    passing at or under its limit:

    * ``logit_err``: the largest (:data:`LOGIT_ERR_LIMIT`);
    * ``near_tie_requests``: the requests that needed a swap, at most a
      quarter of those checked;
    * ``near_tie_gap``: the largest gap a swap crossed, beside
      :data:`NEAR_TIE_DELTA` (under it by construction: it shows how near
      the rule's edge the run's ties came)."""
    from bench.harness import Check

    return [Check("logit_err", max(errs, default=float("inf")),
                  LOGIT_ERR_LIMIT),
            Check("near_tie_requests", float(sum(u > 0 for u in swaps)),
                  NEAR_TIE_SHARE * len(errs)),
            Check("near_tie_gap", max(crossed, default=0.0),
                  NEAR_TIE_DELTA)]
