"""Model building blocks: attention (GQA/RoPE/qk-norm/bias/softcap/sliding),
chunked flash-style attention in pure XLA, GShard-style MoE, Mamba and RWKV6
mixers, RMSNorm.  Pure functions over param pytrees; every init_* returns
``(params, logical_axes)`` with matching tree structure for the sharding
rules in ``repro.distributed.sharding``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

Params = Dict[str, Any]
NEG_INF = -1e30


def _init(key, shape, dtype, scale=None):
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> Tuple[Params, Params]:
    return {"g": jnp.zeros((d,), dtype)}, {"g": ("embed",)}


def rmsnorm(p: Params, x: jnp.ndarray, *, eps=1e-6, plus_one=True) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    g = p["g"].astype(jnp.float32) + (1.0 if plus_one else 0.0)
    return (xf * inv * g).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq     # (B,S,half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> Tuple[Params, Params]:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init(ks[0], (d, h * hd), pd),
        "wk": _init(ks[1], (d, kv * hd), pd),
        "wv": _init(ks[2], (d, kv * hd), pd),
        "wo": _init(ks[3], (h * hd, d), pd),
    }
    ax = {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
          "wv": ("embed", "kv_heads"), "wo": ("heads", "embed")}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), pd)
        p["bk"] = jnp.zeros((kv * hd,), pd)
        p["bv"] = jnp.zeros((kv * hd,), pd)
        ax.update({"bq": ("heads",), "bk": ("kv_heads",), "bv": ("kv_heads",)})
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), pd)
        p["k_norm"] = jnp.zeros((hd,), pd)
        ax.update({"q_norm": (None,), "k_norm": (None,)})
    return p, ax


DENSE_ATTN_MAX_SEQ = 8192    # above this, chunk the query axis


def _dense_attn(q, k, v, *, causal, window, softcap, scale) -> jnp.ndarray:
    """Plain masked attention.  With heads TP-sharded the per-device score
    tensor is (B, H/tp, S, T) — at 4k train that is ~134 MB, and avoiding
    the query-chunk scan removes per-chunk all-reduces that SPMD pins
    inside the loop (measured: 618 GB/step of loop collectives on the
    qwen3-4b train cell — EXPERIMENTS.md §Perf(2b))."""
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    # FLAT heads + repeated K/V: reshaping h -> (kvh, group) breaks GSPMD
    # when kvh doesn't divide the model axis (the 235B's kv=4 on 16 TP ways
    # left the score tensor 12/16 replicated — 3.2 GB buffers, measured);
    # with flat h the scores shard cleanly h/16.
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    sc = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale
    if softcap is not None:
        sc = softcap * jnp.tanh(sc / softcap)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    sc = jnp.where(mask[None, None], sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", pr, v.astype(jnp.float32))
    return o.astype(q.dtype)


def _chunked_attn(q, k, v, *, causal: bool, window: Optional[int],
                  softcap: Optional[float], scale: float,
                  chunk: int = 2048) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure XLA: lax.scan over query
    chunks so no (S, S) score matrix is ever live (memory-roofline measure).

    q: (B, S, H, D) grouped-query; k, v: (B, T, Hkv, D).
    """
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    nq = -(-s // chunk)
    pad = nq * chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qc = q.reshape(b, nq, chunk, h, d).transpose(1, 0, 2, 3, 4)
    kg = k.transpose(0, 2, 1, 3)          # (B, Hkv, T, D)
    vg = v.transpose(0, 2, 1, 3)
    kpos = jnp.arange(t)

    def body(_, qi_i):
        qi, i = qi_i
        qg = qi.transpose(0, 2, 1, 3).reshape(b, kvh, group, chunk, d)
        sc = jnp.einsum("bkgqd,bktd->bkgqt", qg.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
        if softcap is not None:
            sc = softcap * jnp.tanh(sc / softcap)
        qpos = i * chunk + jnp.arange(chunk)
        mask = jnp.ones((chunk, t), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        sc = jnp.where(mask[None, None, None], sc, NEG_INF)
        m = jnp.max(sc, axis=-1, keepdims=True)
        p = jnp.exp(sc - m)
        o = jnp.einsum("bkgqt,bktd->bkgqd", p, vg.astype(jnp.float32))
        o = o / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        return None, o.reshape(b, h, chunk, d).transpose(0, 2, 1, 3)

    _, oc = jax.lax.scan(body, None, (qc, jnp.arange(nq)))
    out = oc.transpose(1, 0, 2, 3, 4).reshape(b, nq * chunk, h, d)
    return out[:, :s].astype(q.dtype)


def attention(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
              local: bool = False, positions: Optional[jnp.ndarray] = None,
              cache: Optional[Dict] = None, kv_src: Optional[jnp.ndarray] = None,
              causal: bool = True, attn_chunk: int = 512):
    """Returns (out, new_cache).  ``cache`` = {"k","v","idx"} for decode;
    ``kv_src`` = encoder output for cross-attention (k/v from it, no RoPE)."""
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(b, s, h, hd)
    src = x if kv_src is None else kv_src.astype(x.dtype)
    k = jnp.einsum("bsd,dh->bsh", src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", src, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    k = k.reshape(b, src.shape[1], kvh, hd)
    v = v.reshape(b, src.shape[1], kvh, hd)
    if cfg.qk_norm:
        q = rmsnorm({"g": p["q_norm"]}, q, plus_one=True)
        k = rmsnorm({"g": p["k_norm"]}, k, plus_one=True)
    if kv_src is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and kv_src is None:
        # decode/prefill-into-cache: write k/v at idx, attend over the cache
        idx = cache["idx"]
        t_cache = cache["k"].shape[1]
        ring = (local and cfg.sliding_window is not None
                and t_cache == cfg.sliding_window)
        if ring and s >= t_cache:
            # prefill into a ring buffer: keep the last `window` tokens at
            # slot = position % window (a roll of the tail slice)
            w = t_cache
            ck = jnp.roll(k[:, s - w:].astype(cache["k"].dtype), s % w,
                          axis=1)
            cv = jnp.roll(v[:, s - w:].astype(cache["v"].dtype), s % w,
                          axis=1)
        else:
            slot = idx % t_cache if ring else idx
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        new_cache = {"k": ck, "v": cv, "idx": idx + s}
        if s > 1:
            # multi-token prefill (idx==0): self-attention over the fresh
            # k/v; chunked online-softmax above DENSE_ATTN_MAX_SEQ (the
            # dense (s, t) score matrix was 8.6 GB/dev at 32k prefill)
            fn = _dense_attn if s <= DENSE_ATTN_MAX_SEQ else _chunked_attn
            o = fn(q, k, v, causal=causal,
                   window=cfg.sliding_window if local else None,
                   softcap=cfg.attn_softcap, scale=1.0 / math.sqrt(hd))
        else:
            k, v = ck, cv
            t = k.shape[1]
            kpos = jnp.arange(t)[None, :]                # (1, t)
            qpos = idx + jnp.arange(s)[:, None]          # (s, 1)
            if ring:
                # ring slots hold exactly the last `window` positions; all
                # filled slots are attendable (the newest overwrote the
                # oldest), so only emptiness masks
                valid = kpos < jnp.minimum(idx + s, t)
            else:
                valid = kpos <= qpos                     # causal incl. past
                if cfg.sliding_window is not None and local:
                    valid &= kpos > qpos - cfg.sliding_window
            qg = q.transpose(0, 2, 1, 3).reshape(b, kvh, h // kvh, s, hd)
            sc = jnp.einsum("bkgqd,btkd->bkgqt", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) / math.sqrt(hd)
            if cfg.attn_softcap:
                sc = cfg.attn_softcap * jnp.tanh(sc / cfg.attn_softcap)
            sc = jnp.where(valid[None, None, None], sc, NEG_INF)
            pr = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("bkgqt,btkd->bkgqd", pr, v.astype(jnp.float32))
            o = o.reshape(b, h, s, hd).transpose(0, 2, 1, 3).astype(x.dtype)
    else:
        fn = _dense_attn if x.shape[1] <= DENSE_ATTN_MAX_SEQ else _chunked_attn
        o = fn(q, k, v, causal=causal and kv_src is None,
               window=cfg.sliding_window if local else None,
               softcap=cfg.attn_softcap, scale=1.0 / math.sqrt(hd))
    out = jnp.einsum("bsh,hd->bsd", o.reshape(b, s, h * hd),
                     p["wo"].astype(x.dtype))
    return out, new_cache


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    p = {"w_gate": _init(ks[0], (d, f), pd),
         "w_up": _init(ks[1], (d, f), pd),
         "w_down": _init(ks[2], (f, d), pd)}
    ax = {"w_gate": ("embed", "ffn"), "w_up": ("embed", "ffn"),
          "w_down": ("ffn", "embed")}
    return p, ax


def mlp(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    g = act(jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype)))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", g * u, p["w_down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard-style capacity dispatch, EP-shardable)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d, e, f = cfg.d_model, m.n_experts, m.d_expert
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {"router": _init(ks[0], (d, e), pd, scale=0.02),
         "w_gate": _init(ks[1], (e, d, f), pd),
         "w_up": _init(ks[2], (e, d, f), pd),
         "w_down": _init(ks[3], (e, f, d), pd)}
    ax = {"router": ("embed", None),
          "w_gate": ("expert", "embed", "expert_ffn"),
          "w_up": ("expert", "embed", "expert_ffn"),
          "w_down": ("expert", "expert_ffn", "embed")}
    if m.n_shared_experts:
        sp, sax = init_mlp(jax.random.fold_in(key, 7), cfg,
                           d_ff=m.d_expert * m.n_shared_experts)
        p["shared"] = sp
        ax["shared"] = sax
    return p, ax


MOE_GROUP_TOKENS = 512     # GShard-style routing group (capacity per group)


def moe(p: Params, x: jnp.ndarray, cfg: ModelConfig, constrain=None):
    """Returns (y, aux_loss).  GShard-style grouped dispatch/combine.

    Tokens route within groups of <=512, so expert capacity — and therefore
    the (tokens, experts, capacity) dispatch tensor — stays LINEAR in
    sequence length (an ungrouped formulation is quadratic: at 32k prefill
    the slot one-hot alone was 43 GB/device).  The (s,k,e,cap) intermediate
    is collapsed to (s,e,cap) via the per-(token,expert) position (a token
    sends at most one slot to a given expert).  Everything stays einsum, so
    the dispatch tensors shard over (data, model) under GSPMD.
    """
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.n_experts, m.top_k
    s_g = min(s, MOE_GROUP_TOKENS)
    ng = s // s_g
    assert s % s_g == 0, (s, s_g)
    g = b * ng
    xg = x.reshape(g, s_g, d)
    cap = int(m.capacity_factor * s_g * k / e) + 1
    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                 # (g,s,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)       # (g,s,k,e)
    se_onehot = onehot.sum(2)                                # (g,s,e) 0/1
    gate_se = jnp.einsum("gsk,gske->gse", gate_vals, onehot)
    # position of each token within its expert's capacity buffer
    pos_se = jnp.cumsum(se_onehot, axis=1) - se_onehot       # exclusive
    keep = se_onehot * (pos_se < cap)
    slot = jax.lax.broadcasted_iota(jnp.int32, (g, s_g, e, cap), 3)
    dispatch = (keep[..., None]
                * (pos_se[..., None] == slot)).astype(x.dtype)
    combine = dispatch * gate_se[..., None].astype(x.dtype)
    if constrain is not None:
        dispatch = constrain("moe_dispatch", dispatch)
        combine = constrain("moe_dispatch", combine)
    xin = jnp.einsum("gsec,gsd->egcd", dispatch, xg)
    if constrain is not None:
        # expert-shard the dispatched tokens: without this the e dim of xin
        # is unsharded and SPMD ALL-GATHERS the expert weights to match —
        # 3.2 GB replicated expert stacks on the 235B cell (measured)
        xin = constrain("moe_expert", xin)
    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xin,
                               p["w_gate"].astype(x.dtype)))
    h = h * jnp.einsum("egcd,edf->egcf", xin, p["w_up"].astype(x.dtype))
    out = jnp.einsum("egcf,efd->egcd", h, p["w_down"].astype(x.dtype))
    if constrain is not None:
        out = constrain("moe_expert", out)
    y = jnp.einsum("gsec,egcd->gsd", combine, out).reshape(b, s, d)
    if "shared" in p:
        y = y + mlp(p["shared"], x, cfg)
    # aux losses: load-balance (Switch) + router z-loss
    me = probs.mean(axis=(0, 1))
    ce = se_onehot.mean(axis=(0, 1)) / k
    lb = e * jnp.sum(me * ce) * m.load_balance_coef
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_coef
    return y, lb + z


# ---------------------------------------------------------------------------
# Mamba mixer (Jamba's SSM layers)
# ---------------------------------------------------------------------------

def init_mamba(key, cfg: ModelConfig):
    m = cfg.mamba
    d = cfg.d_model
    d_in = m.expand * d
    dtr = m.dt_rank or -(-d // 16)
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 7)
    p = {
        "in_proj": _init(ks[0], (d, 2 * d_in), pd),
        "conv_w": _init(ks[1], (m.d_conv, d_in), pd, scale=0.5),
        "conv_b": jnp.zeros((d_in,), pd),
        "x_proj": _init(ks[2], (d_in, dtr + 2 * m.d_state), pd),
        "dt_proj": _init(ks[3], (dtr, d_in), pd),
        "dt_bias": jnp.zeros((d_in,), pd) + 0.1,
        "a_log": jnp.log(jnp.tile(jnp.arange(1, m.d_state + 1,
                                             dtype=jnp.float32), (d_in, 1))),
        "d": jnp.ones((d_in,), jnp.float32),
        "out_proj": _init(ks[4], (d_in, d), pd),
    }
    ax = {"in_proj": ("embed", "mamba_inner"), "conv_w": (None, "mamba_inner"),
          "conv_b": ("mamba_inner",), "x_proj": ("mamba_inner", None),
          "dt_proj": (None, "mamba_inner"), "dt_bias": ("mamba_inner",),
          "a_log": ("mamba_inner", None), "d": ("mamba_inner",),
          "out_proj": ("mamba_inner", "embed")}
    return p, ax


def mamba_mixer(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                state: Optional[Dict] = None):
    """state (decode): {"conv": (B, d_conv-1, d_in), "ssm": (B, d_in, N)}."""
    m = cfg.mamba
    b, s, d = x.shape
    d_in = m.expand * d
    dtr = m.dt_rank or -(-d // 16)
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    xi, z = xz[..., :d_in], xz[..., d_in:]
    # causal depthwise conv
    if state is None:
        pad = jnp.zeros((b, m.d_conv - 1, d_in), xi.dtype)
        xpad = jnp.concatenate([pad, xi], axis=1)
        new_conv = None
    else:
        xpad = jnp.concatenate([state["conv"].astype(xi.dtype), xi], axis=1)
        new_conv = xpad[:, -(m.d_conv - 1):]
    conv = sum(xpad[:, i:i + s] * p["conv_w"][i].astype(xi.dtype)
               for i in range(m.d_conv)) + p["conv_b"].astype(xi.dtype)
    xc = jax.nn.silu(conv)
    proj = jnp.einsum("bsi,ie->bse", xc, p["x_proj"].astype(xc.dtype))
    dt = jax.nn.softplus(
        jnp.einsum("bsr,ri->bsi", proj[..., :dtr],
                   p["dt_proj"].astype(xc.dtype)).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))
    bb = proj[..., dtr:dtr + m.d_state].astype(jnp.float32)
    cc = proj[..., dtr + m.d_state:].astype(jnp.float32)
    a = -jnp.exp(p["a_log"])
    from ..kernels.mamba_scan.ref import reference_mamba
    if state is None:
        y = reference_mamba(xc, dt, bb, cc, a, p["d"])
        new_state = None
    else:
        y, new_ssm = reference_mamba(xc, dt, bb, cc, a, p["d"],
                                     state=state["ssm"], return_state=True)
        new_state = {"conv": new_conv.astype(state["conv"].dtype),
                     "ssm": new_ssm}
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"].astype(x.dtype))
    return out, new_state


# ---------------------------------------------------------------------------
# RWKV6 mixer (Finch: data-dependent per-channel decay)
# ---------------------------------------------------------------------------

def init_rwkv(key, cfg: ModelConfig):
    d = cfg.d_model
    n = cfg.rwkv.head_dim
    heads = d // n
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 9)
    lora = max(32, d // 32)
    p = {
        "mix": _init(ks[0], (5, d), pd, scale=0.02),     # r,k,v,w,g lerp
        "wr": _init(ks[1], (d, d), pd),
        "wk": _init(ks[2], (d, d), pd),
        "wv": _init(ks[3], (d, d), pd),
        "wg": _init(ks[4], (d, d), pd),
        "wo": _init(ks[5], (d, d), pd),
        "w0": jnp.zeros((d,), jnp.float32) - 6.0,        # base decay logits
        "w_a": _init(ks[6], (d, lora), pd, scale=0.02),  # decay LoRA (the
        "w_b": _init(ks[7], (lora, d), pd, scale=0.02),  # RWKV6 novelty)
        "u": _init(ks[8], (heads, n), pd, scale=0.1),    # bonus
        "ln_g": jnp.ones((d,), pd),
    }
    ax = {"mix": (None, "embed"), "wr": ("embed", "heads"),
          "wk": ("embed", "heads"), "wv": ("embed", "heads"),
          "wg": ("embed", "heads"), "wo": ("heads", "embed"),
          "w0": ("embed",), "w_a": ("embed", None), "w_b": (None, "embed"),
          "u": ("heads", None), "ln_g": ("embed",)}
    return p, ax


def rwkv_mixer(p: Params, x: jnp.ndarray, cfg: ModelConfig,
               state: Optional[Dict] = None):
    """state (decode): {"last": (B, d), "wkv": (B, H, N, N)}."""
    b, s, d = x.shape
    n = cfg.rwkv.head_dim
    heads = d // n
    if state is None:
        prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    else:
        prev = jnp.concatenate([state["last"][:, None].astype(x.dtype),
                                x[:, :-1]], axis=1)
    mix = jax.nn.sigmoid(p["mix"].astype(jnp.float32))
    xm = [x * m + prev * (1 - m) for m in
          (mix[0].astype(x.dtype), mix[1].astype(x.dtype),
           mix[2].astype(x.dtype), mix[3].astype(x.dtype),
           mix[4].astype(x.dtype))]
    r = jnp.einsum("bsd,de->bse", xm[0], p["wr"].astype(x.dtype))
    k = jnp.einsum("bsd,de->bse", xm[1], p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,de->bse", xm[2], p["wv"].astype(x.dtype))
    # data-dependent decay (low-rank) — the Finch contribution
    wlog = p["w0"].astype(jnp.float32) + jnp.einsum(
        "bsd,dl,le->bse", xm[3].astype(jnp.float32),
        p["w_a"].astype(jnp.float32), p["w_b"].astype(jnp.float32))
    w = jnp.exp(-jnp.exp(wlog))                          # (B,S,d) in (0,1)
    g = jax.nn.silu(jnp.einsum("bsd,de->bse", xm[4], p["wg"].astype(x.dtype)))

    rh = r.reshape(b, s, heads, n).transpose(0, 2, 1, 3).reshape(b * heads, s, n)
    kh = k.reshape(b, s, heads, n).transpose(0, 2, 1, 3).reshape(b * heads, s, n)
    vh = v.reshape(b, s, heads, n).transpose(0, 2, 1, 3).reshape(b * heads, s, n)
    wh = w.reshape(b, s, heads, n).transpose(0, 2, 1, 3).reshape(b * heads, s, n)
    u = p["u"].astype(jnp.float32)

    if state is None:
        o = _rwkv_heads(rh, kh, vh, wh, u, b, heads)
        new_state = None
    else:
        o, stT = _rwkv_heads(rh, kh, vh, wh, u, b, heads,
                             state=state["wkv"], return_state=True)
        new_state = {"last": x[:, -1].astype(state["last"].dtype),
                     "wkv": stT}
    o = o.reshape(b, heads, s, n).transpose(0, 2, 1, 3).reshape(b, s, d)
    # per-head group norm
    oh = o.reshape(b, s, heads, n).astype(jnp.float32)
    oh = oh * jax.lax.rsqrt(jnp.mean(oh * oh, axis=-1, keepdims=True) + 1e-6)
    o = (oh.reshape(b, s, d) * p["ln_g"].astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bsd,de->bse", o * g, p["wo"].astype(x.dtype))
    return out, new_state


def _rwkv_heads(rh, kh, vh, wh, u, b, heads, state=None, return_state=False):
    """Run the RWKV6 reference per head (the bonus u differs per head).
    state: (B, H, N, N) initial wkv or None."""
    from ..kernels.rwkv6_scan.ref import reference_rwkv6
    s, n = rh.shape[1], rh.shape[2]
    r4 = rh.reshape(b, heads, s, n)
    k4 = kh.reshape(b, heads, s, n)
    v4 = vh.reshape(b, heads, s, n)
    w4 = wh.reshape(b, heads, s, n)
    if not return_state:
        o = jax.vmap(lambda r, k, v, w, uh: reference_rwkv6(r, k, v, w, uh),
                     in_axes=(1, 1, 1, 1, 0), out_axes=1)(r4, k4, v4, w4, u)
        return o.reshape(b * heads, s, n)
    o, stT = jax.vmap(
        lambda r, k, v, w, uh, s0: reference_rwkv6(
            r, k, v, w, uh, state=s0, return_state=True),
        in_axes=(1, 1, 1, 1, 0, 1), out_axes=(1, 1))(r4, k4, v4, w4, u, state)
    return o.reshape(b * heads, s, n), stT
