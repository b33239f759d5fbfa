"""The transformer forward/prefill/decode paths traced through the lazy
runtime (ISSUE 10 tentpole).

``LazyTransformer`` wraps a ``repro.models.transformer`` parameter tree and
re-expresses each entry point as ONE lazy tape: every call records the full
step — embedding gather, per-layer rmsnorm / attention / SwiGLU chains,
final norm, unembed — and the terminal ``materialize`` flushes it through
the whole pipeline (trace → graph → partition → schedule → lower →
execute).  Under the ``backend="lm"`` stack the masked-softmax blocks lower
through the ``flash_attention`` claimant and the residual+rmsnorm blocks
through the ``rmsnorm`` claimant (DESIGN.md §20).

**Bit-identity contract**: every method returns bitwise the same logits
(and KV caches) as the JITTED direct calls — ``jax.jit(forward)``,
``jax.jit(serve_prefill)``, ``jax.jit(serve_decode)`` — which is what
``tests/test_lm.py`` asserts.  The jitted paths are the reference because
XLA contracts ``mul``+``add`` into FMA under jit but not in op-by-op eager
mode; block-granularity execution reproduces the jitted bits exactly
because the transformer decomposition has no multiply whose consuming add
lands in a different fusion block.  The recipes below are each individually
load-bearing for that contract:

* RoPE cos/sin tables are computed with *eager jnp* on the host (module
  constants, adopted once per position set) — ``np.cos`` and XLA's cosine
  differ in the last ulp;
* the ``(1+g)`` norm scale is precomputed host-side in float32 (IEEE
  addition is deterministic, so host numpy == XLA);
* scalar scales enter as Python float literals — JAX weak typing rounds
  them to float32 before the multiply, exactly like the direct model's
  ``np.float32`` constants; prefill MULTIPLIES scores by ``1/sqrt(hd)``
  while decode DIVIDES by ``sqrt(hd)``, mirroring the two einsum paths in
  ``layers.attention``;
* the masked-softmax ``-inf`` fill is an adopted float32 array, never a
  Python scalar (``where`` would promote a scalar operand to float64);
* reduction results are consumed through
  ``r.reshape(..., 1).broadcast_to(domain)`` — the stride-0 form both the
  XLA fallback and the row-replay kernels reproduce bit-exactly.

Supported configs are the dense decoder-only subset (all-attention layer
pattern, MHA, SwiGLU, float32, no qk-norm/bias/softcap, untied lm_head),
and DeepSeek-V2's block (arXiv:2405.04434 §2): multi-head latent attention
without a query low-rank, YaRN rope, leading dense layers, then
routed-plus-shared expert layers with a softmax router and greedy top-k
gates left unnormalised.  :func:`validate_config` raises for anything else
rather than silently diverging from the direct model.

**Latent attention** keeps one cache per layer of ``kv_lora_rank +
qk_rope_head_dim`` floats a token: the normed latent ``c_kv`` and the
roped key ``k_pe`` shared by every head.  Prefill and decode both
up-project the latent to per-head ``k_nope`` and ``v`` (the naive form);
scores are ``[q_nope | q_pe] . [k_nope | k_pe]`` scaled by
``qk_head_dim**-0.5 * m**2``, ``m`` YaRN's ``mscale_all_dim`` factor.

**The expert layer** is dropless with static shapes, so one plan and one
set of executables serve every routing and nothing is read to the host:
the router scores all ``n_experts``; ``argsort`` of the negated scores
gives each token's top-k (lower index first on ties) and a ``take`` of the
flat scores its gates; the ``T * top_k`` assignments are sorted by held
expert (absent experts last), their token rows taken in that order, run
through the held experts' gate, up and down matrices by ``ragged_matmul``
(rows past the held count come out zero), taken back through the inverse
permutation, weighted by the gates and summed over the k.  The shared
experts are one SwiGLU of ``n_shared_experts * d_expert``.  Experts outside
``MoEConfig.held_experts`` are this device's share of nothing: another
device of an expert-parallel deployment computes them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core import lazy as bh
from ..core.lazy import LazyArray, Runtime
from .config import ModelConfig, yarn_mscale

Params = Dict[str, Any]


def validate_config(cfg: ModelConfig) -> None:
    """Raise ``ValueError`` unless ``cfg`` is in the supported subset."""
    unit, _ = cfg.scan_groups()
    ffns = ("mlp", "moe") if cfg.moe is not None else ("mlp",)
    bad = [m for m, f in unit if m != "attn" or f not in ffns]
    if bad:
        raise ValueError(f"lazy transformer supports attn+mlp layers only, "
                         f"pattern unit has {unit}")
    if cfg.moe is not None or cfg.mla is not None \
            or cfg.rope_scaling is not None:
        _validate_deepseek_v2(cfg)
    checks = [
        (cfg.n_kv_heads == cfg.n_heads, "GQA (n_kv_heads < n_heads)"),
        (cfg.act == "silu", f"act={cfg.act!r}"),
        (str(cfg.dtype) == "float32", f"dtype={cfg.dtype!r}"),
        (str(cfg.param_dtype) == "float32",
         f"param_dtype={cfg.param_dtype!r}"),
        (not cfg.qkv_bias, "qkv_bias"),
        (not cfg.qk_norm, "qk_norm"),
        (not cfg.attn_softcap, "attn_softcap"),
        (not cfg.final_softcap, "final_softcap"),
        (not cfg.tie_embeddings, "tie_embeddings"),
        (cfg.n_encoder_layers == 0, "encoder layers"),
    ]
    for ok, what in checks:
        if not ok:
            raise ValueError(f"lazy transformer does not support {what}")


def _validate_deepseek_v2(cfg: ModelConfig) -> None:
    """The DeepSeek-V2 block: latent attention without a query low-rank,
    leading dense layers then a dropless softmax/greedy expert layer with
    unnormalised gates in every layer after them."""
    m, a = cfg.moe, cfg.mla
    checks = [
        (a is not None and m is not None,
         "moe or yarn rope without latent attention and experts"),
        (a is None or a.q_lora_rank is None, "a query low-rank"),
        (m is None or (m.scoring, m.topk_method) == ("softmax", "greedy"),
         f"routing {m and (m.scoring, m.topk_method)}"),
        (m is None or not m.norm_topk_prob, "renormalised top-k gates"),
        (m is None or cfg.moe_period == 1, "moe_period != 1"),
        (m is None or 0 <= m.held[0] < m.held[1] <= m.n_experts,
         f"held experts {m and m.held}"),
        (m is None or m.top_k <= m.n_experts, "top_k > n_experts"),
    ]
    for ok, what in checks:
        if not ok:
            raise ValueError(f"lazy transformer does not support {what}")


def _np(a) -> np.ndarray:
    return np.asarray(a)


class LazyTransformer:
    """One model instance bound to one lazy :class:`Runtime`.

    Parameters are converted to host numpy, group-sliced out of the stacked
    ``params["groups"]`` tree and adopted into the runtime ONCE at
    construction (adoption records no bytecode); every later ``forward`` /
    ``prefill`` / ``decode`` call traces pure compute.  KV caches live as
    runtime buffers across flushes — decode steps update them in place with
    window copies, the host tracks only the integer write index.
    """

    def __init__(self, params: Params, cfg: ModelConfig, *,
                 runtime: Optional[Runtime] = None, **runtime_kw):
        validate_config(cfg)
        self.cfg = cfg
        if runtime is None:
            kw = dict(algorithm="greedy", cost_model="bohrium",
                      backend="lm", loop_fusion=False)
            kw.update(runtime_kw)
            runtime = Runtime(**kw)
        self.rt = runtime
        adopt = self.rt.adopt
        plus = np.float32(1.0 if cfg.norm_plus_one else 0.0)

        def norm_g1(p) -> LazyArray:
            # host-side (1+g): IEEE f32 addition, identical bits to XLA's
            return adopt(_np(p["g"]).astype(np.float32) + plus)

        self.embed = adopt(_np(params["embed"]))
        self.lm_head = adopt(_np(params["lm_head"]))
        self.final_g1 = norm_g1(params["final_norm"])
        unit, n_groups = cfg.scan_groups()
        self.layers: List[Dict[str, LazyArray]] = []
        for g in range(n_groups):
            for i in range(len(unit)):
                lp = params["groups"][f"l{i}"]
                mx, ffn = lp["mixer"], lp["ffn"]
                if cfg.mla is not None:
                    self.layers.append(self._adopt_deepseek_v2(
                        lp, g, unit[i][1], norm_g1))
                    continue
                self.layers.append({
                    "norm1_g1": norm_g1({"g": _np(lp["norm1"]["g"])[g]}),
                    "norm2_g1": norm_g1({"g": _np(lp["norm2"]["g"])[g]}),
                    "wq": adopt(_np(mx["wq"])[g]),
                    "wk": adopt(_np(mx["wk"])[g]),
                    "wv": adopt(_np(mx["wv"])[g]),
                    "wo": adopt(_np(mx["wo"])[g]),
                    "w_gate": adopt(_np(ffn["w_gate"])[g]),
                    "w_up": adopt(_np(ffn["w_up"])[g]),
                    "w_down": adopt(_np(ffn["w_down"])[g]),
                })
        # masked-softmax -inf fill: an adopted f32 ARRAY — `where` with a
        # Python scalar operand would compute the result in float64
        self._neg = adopt(np.full((1, 1, 1, 1), -1e30, np.float32))
        self._rope_cache: Dict[Tuple, Tuple[LazyArray, LazyArray]] = {}
        self._mask_cache: Dict[Tuple, LazyArray] = {}
        #: per-layer (k, v) cache arrays after prefill, layer order
        self.caches: List[Tuple[LazyArray, LazyArray]] = []
        self._idx = 0                     # host-tracked decode position

    def _adopt_deepseek_v2(self, lp, g: int, ffn_kind: str,
                           norm_g1) -> Dict[str, LazyArray]:
        """Layer ``g`` of one DeepSeek-V2 layer's stacked weights: mixer
        ``wq`` ``(d, heads * qk_head_dim)``, ``wkv_a`` ``(d, kv_lora_rank +
        qk_rope_head_dim)``, ``kv_norm.g``, ``wkv_b`` ``(kv_lora_rank, heads
        * (qk_nope_head_dim + v_head_dim))``, ``wo``; a dense ffn as for
        the dense model, or an expert ffn: ``router`` ``(d, n_experts)``,
        ``w_gate``/``w_up``/``w_down`` stacked over all ``n_experts`` or
        over the held ones only, and ``shared`` (a SwiGLU)."""
        adopt = self.rt.adopt
        mx, ffn = lp["mixer"], lp["ffn"]
        out = {"norm1_g1": norm_g1({"g": _np(lp["norm1"]["g"])[g]}),
               "norm2_g1": norm_g1({"g": _np(lp["norm2"]["g"])[g]}),
               "kv_norm_g1": norm_g1({"g": _np(mx["kv_norm"]["g"])[g]})}
        for k in ("wq", "wkv_a", "wkv_b", "wo"):
            out[k] = adopt(_np(mx[k])[g])
        if ffn_kind == "mlp":
            for k in ("w_gate", "w_up", "w_down"):
                out[k] = adopt(_np(ffn[k])[g])
            return out
        m = self.cfg.moe
        start, stop = m.held
        out["router"] = adopt(_np(ffn["router"])[g])
        for k in ("w_gate", "w_up", "w_down"):
            w = _np(ffn[k])[g]
            if w.shape[0] == m.n_experts:
                w = w[start:stop]
            assert w.shape[0] == stop - start, (k, w.shape, m.held)
            out["e_" + k[2:]] = adopt(w)
        for k in ("w_gate", "w_up", "w_down"):
            out["s_" + k[2:]] = adopt(_np(ffn["shared"][k])[g])
        return out

    # -- adopted constants ------------------------------------------------

    def _rope_consts(self, positions: np.ndarray) -> Tuple[LazyArray, LazyArray]:
        """cos/sin tables shaped (1, s, 1, half) for (1, s) positions.

        Computed with EAGER jnp and adopted: the direct model evaluates
        ``jnp.cos`` under jit, and host ``np.cos`` is not bit-identical to
        XLA's — eager jnp is."""
        key = ("rope",) + tuple(int(p) for p in positions.ravel())
        hit = self._rope_cache.get(key)
        if hit is not None:
            return hit
        cfg = self.cfg
        if cfg.rope_scaling is None:
            half = cfg.hd // 2
            freq = cfg.rope_theta ** (
                -jnp.arange(0, half, dtype=jnp.float32) / half)
            scale = 1.0
        else:
            freq, scale = yarn_inv_freq(cfg.mla.qk_rope_head_dim,
                                        cfg.rope_theta, cfg.rope_scaling)
        ang = jnp.asarray(positions)[..., None].astype(jnp.float32) * freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        if scale != 1.0:
            cos, sin = cos * np.float32(scale), sin * np.float32(scale)
        cos = self.rt.adopt(_np(cos[..., None, :]))
        sin = self.rt.adopt(_np(sin[..., None, :]))
        self._rope_cache[key] = (cos, sin)
        return cos, sin

    def _zero_cache(self, shape: Tuple[int, ...]) -> LazyArray:
        """A zeroed KV cache of ``shape``, handed to the runtime flat and
        viewed in ``shape``.  Prefill writes it through views and nothing
        reads it whole, so it gains nothing from a buffer of its own shape,
        while copying an N-D host array to a TPU holds the host longer: on
        a v5e, dsllm7b's 8 caches of a 2048-token prefill held it 31.5 ms
        in ``adopt`` in their own shape, against 3.6 ms flat."""
        return self.rt.adopt(
            np.zeros(math.prod(shape), np.float32)).reshape(shape)

    def _causal_mask(self, s: int) -> LazyArray:
        key = ("causal", s)
        if key not in self._mask_cache:
            m = np.arange(s)[None, :] <= np.arange(s)[:, None]
            self._mask_cache[key] = self.rt.adopt(m.reshape(1, 1, s, s))
        return self._mask_cache[key]

    def _decode_mask(self, idx: int, tt: int) -> LazyArray:
        key = ("decode", idx, tt)
        if key not in self._mask_cache:
            m = np.arange(tt)[None, :] <= np.asarray([[idx]])
            self._mask_cache[key] = self.rt.adopt(m.reshape(1, 1, 1, tt))
        return self._mask_cache[key]

    # -- building blocks --------------------------------------------------

    def _proj(self, x: LazyArray, w: LazyArray) -> LazyArray:
        b, s, d = x.shape
        return bh.matmul(x.reshape(b * s, d), w).reshape(b, s, w.shape[1])

    def _rmsnorm(self, x: LazyArray, g1: LazyArray) -> LazyArray:
        b, s, d = x.shape
        var = (x * x).sum(axis=-1)                       # (b, s)
        var_b = var.reshape(b, s, 1).broadcast_to((b, s, d))
        inv = bh.rsqrt(var_b / float(d) + 1e-6)
        return x * inv * g1.reshape(1, 1, d).broadcast_to((b, s, d))

    def _rope(self, x: LazyArray, cos: LazyArray, sin: LazyArray) -> LazyArray:
        half = x.shape[-1] // 2
        tgt = x.shape[:-1] + (half,)
        x1, x2 = x[:, :, :, :half], x[:, :, :, half:]
        c, s_ = cos.broadcast_to(tgt), sin.broadcast_to(tgt)
        return bh.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], axis=-1)

    def _softmax_rows(self, sc: LazyArray, mask: LazyArray) -> LazyArray:
        """where(mask, sc, -inf) -> max -> exp -> sum -> div over the last
        axis — the flash_attention claimant's block (with the scale op that
        fused in front of it)."""
        b, h, s, t = sc.shape
        scm = bh.where(mask.broadcast_to(sc.shape), sc, self._neg)
        m = scm.max(axis=-1)
        e = bh.exp(scm - m.reshape(b, h, s, 1).broadcast_to(scm.shape))
        z = e.sum(axis=-1)
        return e / z.reshape(b, h, s, 1).broadcast_to(e.shape)

    def _qkv(self, lp, h: LazyArray, positions: np.ndarray):
        b, s, _ = h.shape
        nh, hd = self.cfg.n_heads, self.cfg.hd
        cos, sin = self._rope_consts(positions)
        q = self._proj(h, lp["wq"]).reshape(b, s, nh, hd)
        k = self._proj(h, lp["wk"]).reshape(b, s, nh, hd)
        v = self._proj(h, lp["wv"]).reshape(b, s, nh, hd)
        return self._rope(q, cos, sin), self._rope(k, cos, sin), v

    def _attn_out(self, lp, pr: LazyArray, v_t: LazyArray) -> LazyArray:
        b, nh = pr.shape[0], pr.shape[1]
        s, hd = pr.shape[2], v_t.shape[-1]
        o = bh.matmul(pr, v_t)                           # (b, nh, s, hd)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
        return self._proj(o, lp["wo"])

    def _attention_prefill(self, lp, h: LazyArray, ck, cv):
        """Dense causal attention over the FRESH k/v (the cache write is
        pure data movement, exactly like ``layers.attention`` prefill)."""
        b, s, _ = h.shape
        hd = self.cfg.hd
        q, k, v = self._qkv(lp, h, np.arange(s)[None])
        ck[:, 0:s] = k
        cv[:, 0:s] = v
        sc = bh.matmul(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1))
        pr = self._softmax_rows(sc * float(1.0 / math.sqrt(hd)),
                                self._causal_mask(s))
        return self._attn_out(lp, pr, v.transpose(0, 2, 1, 3))

    def _attention_decode(self, lp, h: LazyArray, ck, cv, idx: int):
        """One-token attention over the whole cache, emptiness-masked by
        position (``layers.attention`` decode divides by sqrt(hd))."""
        hd = self.cfg.hd
        q, k, v = self._qkv(lp, h, np.asarray([[idx]]))
        ck[:, idx:idx + 1] = k
        cv[:, idx:idx + 1] = v
        tt = ck.shape[1]
        sc = bh.matmul(q.transpose(0, 2, 1, 3), ck.transpose(0, 2, 3, 1))
        pr = self._softmax_rows(sc / float(math.sqrt(hd)),
                                self._decode_mask(idx, tt))
        return self._attn_out(lp, pr, cv.transpose(0, 2, 1, 3))

    # -- latent attention and the expert layer (DeepSeek-V2) -------------

    def _mla_scale(self) -> float:
        a, y = self.cfg.mla, self.cfg.rope_scaling
        m = yarn_mscale(y.factor, y.mscale_all_dim) if y else 1.0
        return float(a.qk_head_dim ** -0.5 * m * m)

    def _mla_attend(self, lp, q: LazyArray, c: LazyArray, k_pe: LazyArray,
                    cos, sin, mask: LazyArray) -> LazyArray:
        """Attention of ``q`` ``(b, s, heads, qk_head_dim)``, rope not yet
        applied, over the latents ``c`` ``(b, t, kv_lora_rank)`` and the
        roped keys ``k_pe`` ``(b, t, 1, qk_rope_head_dim)``."""
        a, nh = self.cfg.mla, self.cfg.n_heads
        nope = a.qk_nope_head_dim
        b, t = c.shape[0], c.shape[1]
        kv = self._proj(c, lp["wkv_b"]).reshape(b, t, nh, nope + a.v_head_dim)
        q = bh.concatenate([q[:, :, :, :nope],
                            self._rope(q[:, :, :, nope:], cos, sin)], axis=-1)
        k = bh.concatenate([kv[:, :, :, :nope], k_pe.broadcast_to(
            (b, t, nh, a.qk_rope_head_dim))], axis=-1)
        sc = bh.matmul(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1))
        pr = self._softmax_rows(sc * self._mla_scale(), mask)
        return self._attn_out(lp, pr, kv[:, :, :, nope:].transpose(0, 2, 1, 3))

    def _mla_latent(self, lp, h: LazyArray, cos, sin):
        """This step's normed latent ``(b, s, r)`` and roped key ``(b, s,
        1, rope)``."""
        a = self.cfg.mla
        b, s, _ = h.shape
        r = a.kv_lora_rank
        kv_a = self._proj(h, lp["wkv_a"])
        c = self._rmsnorm(kv_a[:, :, :r], lp["kv_norm_g1"])
        k_pe = self._rope(kv_a[:, :, r:].reshape(b, s, 1, a.qk_rope_head_dim),
                          cos, sin)
        return c, k_pe

    def _mla_query(self, lp, h: LazyArray) -> LazyArray:
        b, s, _ = h.shape
        return self._proj(h, lp["wq"]).reshape(
            b, s, self.cfg.n_heads, self.cfg.mla.qk_head_dim)

    def _mla_prefill(self, lp, h: LazyArray, cache: LazyArray) -> LazyArray:
        """Causal latent attention over the fresh latents; writes them to
        the layer's latent cache ``(b, max_seq, r + rope)``."""
        b, s, _ = h.shape
        r = self.cfg.mla.kv_lora_rank
        cos, sin = self._rope_consts(np.arange(s)[None])
        c, k_pe = self._mla_latent(lp, h, cos, sin)
        cache[:, 0:s, 0:r] = c
        cache[:, 0:s, r:] = k_pe.reshape(b, s, self.cfg.mla.qk_rope_head_dim)
        return self._mla_attend(lp, self._mla_query(lp, h), c, k_pe,
                                cos, sin, self._causal_mask(s))

    def _mla_decode(self, lp, h: LazyArray, cache: LazyArray,
                    idx: int) -> LazyArray:
        """One token's latent attention: its latent is written at ``idx``,
        then the whole latent cache is read (emptiness-masked)."""
        a = self.cfg.mla
        b, r, tt = h.shape[0], a.kv_lora_rank, cache.shape[1]
        cos, sin = self._rope_consts(np.asarray([[idx]]))
        c, k_pe = self._mla_latent(lp, h, cos, sin)
        cache[:, idx:idx + 1, 0:r] = c
        cache[:, idx:idx + 1, r:] = k_pe.reshape(b, 1, a.qk_rope_head_dim)
        return self._mla_attend(
            lp, self._mla_query(lp, h), cache[:, :, 0:r],
            cache[:, :, r:].reshape(b, tt, 1, a.qk_rope_head_dim),
            cos, sin, self._decode_mask(idx, tt))

    def _swiglu(self, h: LazyArray, wg, wu, wd) -> LazyArray:
        t = self._proj(h, wg)
        return self._proj((t * bh.sigmoid(t)) * self._proj(h, wu), wd)

    def _routed(self, lp, h: LazyArray) -> LazyArray:
        """The held experts' share of the routed experts' output for ``h``
        ``(b, s, d)``: dropless, one flush, no host read (module doc)."""
        m = self.cfg.moe
        b, s, d = h.shape
        n, e, k = b * s, m.n_experts, m.top_k
        start, stop = m.held
        held, na = stop - start, b * s * m.top_k
        x = h.reshape(n, d)
        logits = bh.matmul(x, lp["router"])                   # (n, e)
        z = bh.exp(logits - logits.max(axis=-1).reshape(n, 1)
                   .broadcast_to((n, e)))
        p = z / z.sum(axis=-1).reshape(n, 1).broadcast_to((n, e))
        top = bh.argsort(-p, axis=-1)[:, :k]                  # (n, k)
        row0 = (bh.arange(n, np.float32) * float(e)).reshape(n, 1)
        gates = bh.take(p.reshape(n * e), top + row0.broadcast_to((n, k)))
        # sort the assignments by held expert, the absent ones last
        local = top.reshape(na) - float(start)
        inside = (local > -0.5) * (local < held - 0.5)
        key = inside * local + (1.0 - inside) * float(held)
        perm = bh.argsort(key, axis=0)
        xs = bh.take(x, bh.floor(perm / float(k)), axis=0)   # (na, d)
        gid = bh.arange(held, np.float32).reshape(1, held).broadcast_to(
            (na, held))
        kb = key.reshape(na, 1).broadcast_to((na, held))
        sizes = ((kb > gid - 0.5) * (kb < gid + 0.5)).sum(axis=0)
        g = bh.ragged_matmul(xs, lp["e_gate"], sizes)
        u = bh.ragged_matmul(xs, lp["e_up"], sizes)
        ys = bh.ragged_matmul((g * bh.sigmoid(g)) * u, lp["e_down"], sizes)
        y = bh.take(ys, bh.argsort(perm, axis=0), axis=0).reshape(n, k, d)
        y = (y * gates.reshape(n, k, 1).broadcast_to((n, k, d))).sum(axis=1)
        if m.routed_scaling_factor != 1.0:
            y = y * float(m.routed_scaling_factor)
        return y.reshape(b, s, d)

    def _layer(self, lp, x: LazyArray, attend) -> LazyArray:
        h = self._rmsnorm(x, lp["norm1_g1"])
        x = x + attend(lp, h)
        h = self._rmsnorm(x, lp["norm2_g1"])
        if "router" in lp:
            return x + (self._routed(lp, h) + self._swiglu(
                h, lp["s_gate"], lp["s_up"], lp["s_down"]))
        t = self._proj(h, lp["w_gate"])
        u = self._proj(h, lp["w_up"])
        f = self._proj((t * bh.sigmoid(t)) * u, lp["w_down"])
        return x + f

    def _embed_tokens(self, tokens: np.ndarray) -> LazyArray:
        b, s = tokens.shape
        d = self.cfg.d_model
        idx = self.rt.adopt(np.asarray(tokens, np.int32).reshape(-1))
        x = bh.take(self.embed, idx, axis=0).reshape(b, s, d)
        if self.cfg.norm_plus_one:          # gemma convention
            x = x * float(math.sqrt(d))
        return x

    def _unembed(self, x: LazyArray) -> LazyArray:
        b, s, d = x.shape
        return bh.matmul(x.reshape(b * s, d), self.lm_head).reshape(b, s, -1)

    # -- entry points (one flush each) ------------------------------------

    def forward(self, tokens: np.ndarray) -> np.ndarray:
        """Training/eval logits (b, s, vocab) — bitwise ``jit(forward)``."""
        tokens = np.asarray(tokens)
        with self.rt.activate():
            x = self._embed_tokens(tokens)
            s = tokens.shape[1]
            for lp in self.layers:
                x = self._layer(lp, x, lambda lp_, h: self._attention_dense(
                    lp_, h, s))
            x = self._rmsnorm(x, self.final_g1)
            return self._unembed(x).numpy()

    def _attention_dense(self, lp, h: LazyArray, s: int) -> LazyArray:
        hd = self.cfg.hd
        q, k, v = self._qkv(lp, h, np.arange(s)[None])
        sc = bh.matmul(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1))
        pr = self._softmax_rows(sc * float(1.0 / math.sqrt(hd)),
                                self._causal_mask(s))
        return self._attn_out(lp, pr, v.transpose(0, 2, 1, 3))

    def prefill(self, tokens: np.ndarray, max_seq: int) -> np.ndarray:
        """Run the prompt; returns last-position logits (b, 1, vocab) and
        leaves per-layer KV caches live in the runtime (``self.caches``):
        ``(k, v)`` pairs, or one latent cache ``(b, max_seq, kv_lora_rank
        + qk_rope_head_dim)`` a layer under latent attention."""
        tokens = np.asarray(tokens)
        b, s = tokens.shape
        kvh, hd = self.cfg.n_kv_heads, self.cfg.hd
        with self.rt.activate():
            x = self._embed_tokens(tokens)
            self.caches = []
            for lp in self.layers:
                if self.cfg.mla is not None:
                    a = self.cfg.mla
                    c = self._zero_cache(
                        (b, max_seq, a.kv_lora_rank + a.qk_rope_head_dim))
                    x = self._layer(lp, x, lambda lp_, h, c=c:
                                    self._mla_prefill(lp_, h, c))
                    self.caches.append(c)
                    continue
                ck = self._zero_cache((b, max_seq, kvh, hd))
                cv = self._zero_cache((b, max_seq, kvh, hd))
                x = self._layer(
                    lp, x, lambda lp_, h, ck=ck, cv=cv:
                    self._attention_prefill(lp_, h, ck, cv))
                self.caches.append((ck, cv))
            x = self._rmsnorm(x, self.final_g1)
            last = x[:, s - 1:s]
            logits = self._unembed(last).numpy()
        self._idx = s
        return logits

    def decode(self, tokens: np.ndarray) -> np.ndarray:
        """One decode step for (b, 1) tokens after :meth:`prefill`; updates
        the caches in place, returns (b, 1, vocab) logits."""
        tokens = np.asarray(tokens)
        assert self.caches, "call prefill() before decode()"
        assert tokens.shape[1] == 1, tokens.shape
        idx = self._idx
        with self.rt.activate():
            x = self._embed_tokens(tokens)
            if self.cfg.mla is not None:
                for lp, c in zip(self.layers, self.caches):
                    x = self._layer(lp, x, lambda lp_, h, c=c:
                                    self._mla_decode(lp_, h, c, idx))
            else:
                for lp, (ck, cv) in zip(self.layers, self.caches):
                    x = self._layer(
                        lp, x, lambda lp_, h, ck=ck, cv=cv:
                        self._attention_decode(lp_, h, ck, cv, idx))
            x = self._rmsnorm(x, self.final_g1)
            logits = self._unembed(x).numpy()
        self._idx = idx + 1
        return logits

    def cache_numpy(self) -> List:
        """Materialize the per-layer (k, v) caches, or latent caches
        (test/debug helper)."""
        with self.rt.activate():
            if self.cfg.mla is not None:
                return [c.numpy() for c in self.caches]
            return [(k.numpy(), v.numpy()) for k, v in self.caches]


def yarn_inv_freq(dim: int, theta: float, y) -> Tuple[np.ndarray, float]:
    """YaRN's inverse frequencies of a ``dim``-wide rotary embedding, in
    float32, and the factor on its cos/sin tables (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``): dimensions that turn fewer than
    ``beta_slow`` times over ``original_max_position_embeddings`` are
    interpolated by ``factor``, those that turn more than ``beta_fast``
    times are kept, with a linear ramp between.  Computed in float64."""
    def corr(rot):
        return (dim * math.log(y.original_max_position_embeddings
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(corr(y.beta_fast)), 0)
    hi = min(math.ceil(corr(y.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    keep = 1.0 - ramp
    inv = extra / y.factor * (1.0 - keep) + extra * keep
    scale = (yarn_mscale(y.factor, y.mscale)
             / yarn_mscale(y.factor, y.mscale_all_dim))
    return inv.astype(np.float32), scale
