"""Plain reference of a dense decoder with multi-head attention (the
Llama layout of DeepSeek-LLM-7B): ``jax.numpy`` in float32, one layer at a
time, with nothing of the program.

Per layer: RMSNorm, q/k/v projections, rotary embedding on the two halves
of each head (``rope_theta``), causal softmax attention scaled by
``1/sqrt(head_dim)``, the output projection and the residual; RMSNorm, a
SwiGLU MLP (``silu(x W_gate) * (x W_up)`` then ``W_down``) and the
residual.  A final RMSNorm and the untied head give the logits.

It reads the weights in the layout the benchmark makes them
(``systems/lazy_transformer.make_weights``): layer ``i`` is index ``i`` of
each stacked array, so no layer is copied out.  Matrix products take a
``matmul`` of one of two kinds: :func:`mm_highest` (float32 at
``highest`` precision, as the configuration states) and the control's
:func:`mm_bf16x3` (three bfloat16 products, what ``high`` precision does on
a TPU), written out so that it is the same on every backend.

:func:`compare` decides ``correct`` for the logits the program served.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence

import numpy as np

#: max over checked requests of max |got - ref| / max |ref| of the
#: last-position logits
LOGIT_ERR_LIMIT = 3e-6


def mm_highest(a, b):
    import jax
    import jax.numpy as jnp
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def mm_bf16x3(a, b):
    """``a @ b`` from bfloat16 parts, as ``high`` precision computes it on
    a TPU: hi*hi + hi*lo + lo*hi, the lo*lo term dropped.  The parts are cut
    with ``reduce_precision``, which the compiler keeps (a round trip
    through a bfloat16 array it may drop as excess precision), and each
    product of two bfloat16 values is exact in a float32 product at
    ``highest``."""
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


def _rope(x, theta):
    """Rotary embedding of ``(s, heads, hd)`` at positions 0..s-1."""
    import jax.numpy as jnp
    s, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, groups, i, *, heads, eps, theta, mm):
    import jax
    import jax.numpy as jnp

    def w(*path):
        a = groups["l0"]
        for p in path:
            a = a[p]
        return jax.lax.dynamic_index_in_dim(a, i, keepdims=False)

    s, d = x.shape
    hd = d // heads
    h = _rmsnorm(x, w("norm1", "g"), eps)
    q = _rope(mm(h, w("mixer", "wq")).reshape(s, heads, hd), theta)
    k = _rope(mm(h, w("mixer", "wk")).reshape(s, heads, hd), theta)
    v = mm(h, w("mixer", "wv")).reshape(s, heads, hd)
    qh, kh, vh = (t.transpose(1, 0, 2) for t in (q, k, v))
    scores = mm(qh, kh.transpose(0, 2, 1)) * (1.0 / math.sqrt(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = mm(p, vh).transpose(1, 0, 2).reshape(s, d)
    x = x + mm(o, w("mixer", "wo"))
    h = _rmsnorm(x, w("norm2", "g"), eps)
    f = jax.nn.silu(mm(h, w("ffn", "w_gate"))) * mm(h, w("ffn", "w_up"))
    return x + mm(f, w("ffn", "w_down"))


@functools.lru_cache(maxsize=None)
def _programs(heads: int, eps: float, theta: float, matmul: str,
              all_positions: bool):
    import jax
    import jax.numpy as jnp
    mm = MATMULS[matmul]
    layer = jax.jit(functools.partial(_layer, heads=heads, eps=eps,
                                      theta=theta, mm=mm))

    def head(x, final_g, lm_head):
        x = x if all_positions else x[-1:]
        return mm(_rmsnorm(x, final_g, eps), lm_head)

    embed = jax.jit(lambda table, ids: jnp.take(table, ids, axis=0))
    return embed, layer, jax.jit(head)


def logits(weights: Dict[str, Any], tokens: np.ndarray, cfg: Dict[str, Any],
           matmul: str = "highest", all_positions: bool = False):
    """Logits of one prompt (``tokens``: ``(length,)`` ids): the last
    position's ``(1, vocab)``, or every position's ``(length, vocab)``."""
    import jax.numpy as jnp
    embed, layer, head = _programs(
        cfg["num_attention_heads"], float(cfg["rms_norm_eps"]),
        float(cfg["rope_theta"]), matmul, all_positions)
    x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, weights["groups"], jnp.int32(i))
    return head(x, weights["final_norm"]["g"], weights["lm_head"])


def compare(got: Sequence[np.ndarray], ref: Sequence[np.ndarray]) -> List:
    """``got``/``ref``: each checked request's last-position logits.

    The gap by which a served greedy token's reference logit lies below the
    reference's best is not compared: the control (``high`` products) moves
    the logits by about 1e-5 of their scale and puts the same token first
    at every position it was read at, so that gap gives no reading above
    a sound run's, and the logits' own error is the number that separates
    the two (``PERF.md``, section 2)."""
    from bench.harness import Check

    err = 0.0
    for g, r in zip(got, ref):
        g = np.asarray(g, np.float64).reshape(-1)
        r = np.asarray(r, np.float64).reshape(-1)
        if g.shape != r.shape or not np.all(np.isfinite(g)):
            err = float("inf")
            break
        err = max(err, float(np.max(np.abs(g - r)) / np.max(np.abs(r))))
    if not got:
        err = float("inf")
    return [Check("logit_err", err, LOGIT_ERR_LIMIT)]
