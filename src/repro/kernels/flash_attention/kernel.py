"""Flash attention for TPU (Pallas): blockwise online-softmax attention.

Canonical TPU pattern: the KV axis is the LAST grid dimension (sequential on
TPU), with VMEM scratch carrying the running max / normalizer / accumulator
across KV steps.  Supports the features the assigned archs need:

* GQA (kv-head groups, starcoder2 kv=2 … qwen1.5 kv=20) via the K/V
  index_map collapsing query heads onto kv heads,
* causal masking (block-skipping: KV blocks strictly above the diagonal are
  masked; fully-masked blocks still run but contribute zeros — the XLA-level
  skip happens in ops.py via grid trimming),
* sliding-window masking (gemma2 local layers),
* logit soft-capping (gemma2): scores = cap * tanh(scores / cap).

Tile sizes default to (128, 128) q×kv blocks with head_dim lanes — MXU-
aligned (128) on every matmul dimension.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import resolve_interpret

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: Optional[int],
                 softcap: Optional[float], block_q: int, block_k: int,
                 seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < seq_k                                  # padding
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                   # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) with Hq % Hkv == 0."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    block_q = min(block_q, _round_up(sq, 8))
    block_k = min(block_k, _round_up(sk, 128))
    sq_pad = _round_up(sq, block_q)
    sk_pad = _round_up(sk, block_k)
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - sq), (0, 0)))
    if sk_pad != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0)))

    grid = (b, hq, sq_pad // block_q, sk_pad // block_k)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, seq_k=sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_pad, d), q.dtype),
        scratch_shapes=[
            pl.MemorySpace.ANY if False else _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out[:, :, :sq, :]


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
