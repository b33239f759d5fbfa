"""Prefill requests served by ``LazyTransformer`` at DeepSeek-V2's block:
latent attention with a latent cache, a leading dense layer, then routed
and shared experts (``configs/dsv2lite.json``).

What it shares with ``systems/lazy_transformer.py``, it takes from there:
the state, the warm-up order, the window's log and the sample the
reference checks.  What differs is the model: the configuration's
published ``config.json`` keys (cut as its ``reduced`` entry says) become
the program's ``ModelConfig`` with its latent attention, YaRN rope and the
held experts of an expert-parallel deployment; the weights are drawn from
the seed on the device in one jitted call, the held experts' only; the
model FLOPs are ``counts_mla_moe.prefill_flops``; and the plain reference
is ``references/mla_moe.py``, whose ``check`` lets a request take either
expert at a tie that lies within rounding (its module doc).  The window
runs with Python's cyclic collector off (``window``).

* ``ttft_p95_ms``: 95th percentile over every request of the window of the
  time from the call to ``prefill`` until its logits are on the host;
* ``prefill_tok_s``: prompt tokens of all those requests over the window.
"""

from __future__ import annotations

import gc
import time
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

from bench import counts_mla_moe as counts
from bench import generator
from bench.harness import Check, Measured, Recorder, log
from bench.spec import load_module

_lm = load_module("systems", "lazy_transformer",
                  Path(__file__).resolve().parent.parent)
State = _lm.State


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for DeepSeek-V2's ``config.json``."""
    from repro.models.config import (MLAConfig, ModelConfig, MoEConfig,
                                     YarnConfig)

    if cfg.get("hidden_act") != "silu" or cfg.get("tie_word_embeddings") \
            or cfg.get("attention_bias"):
        raise ValueError("a SwiGLU decoder with an untied head and no "
                         "attention bias is served")
    if float(cfg["rms_norm_eps"]) != 1e-6:
        raise ValueError("the lazy transformer's RMSNorm uses eps 1e-6")
    ys = cfg["rope_scaling"]
    if ys.get("type") != "yarn" or (cfg["n_group"], cfg["topk_group"]) \
            != (1, 1):
        raise ValueError("YaRN rope and ungrouped routing are served")
    start, stop = cfg["held_experts"]
    if stop - start != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the held experts")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=d, n_heads=heads, n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnConfig(
            factor=float(ys["factor"]),
            original_max_position_embeddings=int(
                ys["original_max_position_embeddings"]),
            beta_fast=float(ys["beta_fast"]), beta_slow=float(ys["beta_slow"]),
            mscale=float(ys["mscale"]),
            mscale_all_dim=float(ys["mscale_all_dim"])),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"],
                      q_lora_rank=cfg["q_lora_rank"]),
        moe=MoEConfig(n_experts=counts.router_width(cfg),
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["moe_intermediate_size"],
                      n_shared_experts=cfg["n_shared_experts"],
                      scoring=cfg["scoring_func"],
                      topk_method=cfg["topk_method"],
                      norm_topk_prob=cfg["norm_topk_prob"],
                      routed_scaling_factor=float(
                          cfg["routed_scaling_factor"]),
                      held_experts=(start, stop)),
        moe_period=cfg["moe_layer_freq"],
        first_k_dense=cfg["first_k_dense_replace"],
        tie_embeddings=False, act="silu", dtype=cfg["torch_dtype"],
        param_dtype=cfg["torch_dtype"], remat=False)


def make_weights(cfg: Dict[str, Any], seed: int):
    """The model's weights from ``seed``, on the device, in one jitted
    call, as the tree ``LazyTransformer`` takes (each layer its own group
    entry ``l<i>`` with a leading axis of 1): projections drawn with
    standard deviation ``1/sqrt(fan_in)``, the router too, the embedding
    with 1, and RMSNorm gains near 1 (``1 + 0.1 N(0, 1)``).  Only the held
    experts are drawn."""
    import jax
    import jax.numpy as jnp

    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    f, ff = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    shared = cfg["n_shared_experts"] * f
    held, experts = counts.held(cfg), counts.router_width(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])

    def init(key):
        ks = iter(jax.random.split(key, 32 * cfg["num_hidden_layers"] + 8))

        def normal(shape, fan_in=None):
            std = 1.0 if fan_in is None else fan_in ** -0.5
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * std).astype(dtype)

        def proj(*shape):
            return normal((1,) + shape, shape[-2])

        def gain(*shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                                  jnp.float32)).astype(dtype)

        def swiglu(width):
            return {"w_gate": proj(d, width), "w_up": proj(d, width),
                    "w_down": proj(width, d)}

        groups = {}
        for i in range(cfg["num_hidden_layers"]):
            mixer = {"wq": proj(d, heads * (nope + rope)),
                     "wkv_a": proj(d, r + rope),
                     "kv_norm": {"g": gain(1, r)},
                     "wkv_b": proj(r, heads * (nope + vd)),
                     "wo": proj(heads * vd, d)}
            if i < cfg["first_k_dense_replace"]:
                ffn = swiglu(ff)
            else:
                ffn = {"router": proj(d, experts),
                       "w_gate": proj(held, d, f), "w_up": proj(held, d, f),
                       "w_down": proj(held, f, d), "shared": swiglu(shared)}
            groups[f"l{i}"] = {"norm1": {"g": gain(1, d)},
                               "norm2": {"g": gain(1, d)},
                               "mixer": mixer, "ffn": ffn}
        return {"embed": normal((cfg["vocab_size"], d)),
                "lm_head": normal((d, cfg["vocab_size"]), d),
                "final_norm": {"g": gain(d)}, "groups": groups}

    key = jax.random.PRNGKey(
        np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(1)[0])
    return jax.jit(init)(key)


def _program(cfg: Dict[str, Any], seed: int):
    import jax
    from repro.models.lazy_transformer import LazyTransformer

    mc = model_config(cfg)            # a program that lacks it stops here
    host = jax.device_get(make_weights(cfg, seed))
    log("weights made on the device and copied to the host")
    lt = LazyTransformer(host, mc, **cfg.get("runtime", {}))
    del host
    gc.collect()
    log("weights adopted by the program")
    return lt


def setup(cell, seed: int) -> State:
    cfg = cell.config
    generator.check_requests(cell.traffic, cell.traffic_name)
    lt = _program(cfg, seed)
    # each prompt length WARM_REPEATS times, in the mix's order, so that
    # every seed's set-up does the same work in the same order
    warm = {int(s): [] for s in cell.traffic["prompt_lengths"]}
    for req in generator.requests(cell.traffic, seed, cfg["vocab_size"],
                                  stream=1):
        if len(warm[req.length]) < _lm.WARM_REPEATS:
            warm[req.length].append(req)
        if all(len(w) == _lm.WARM_REPEATS for w in warm.values()):
            break
    with _lm._precision(cfg):
        for length, reqs in warm.items():
            for req in reqs:
                lt.prefill(req.tokens, req.max_seq)
                log(f"warm-up prefill of {length} tokens")
    return State(cell=cell, seed=seed, lt=lt)


def window(st: State, seconds: float, rec: Recorder) -> Measured:
    cfg = st.cell.config
    lt = st.lt
    stream = generator.requests(st.cell.traffic, st.seed, cfg["vocab_size"])
    cycle = generator.cycle_length(st.cell.traffic)
    collected = [g["collections"] for g in gc.get_stats()]
    # Python's cyclic collector is off for the window, as a server turns it
    # off where it finds nothing: the program's garbage is freed by
    # reference counts (the collection after the window logs what it
    # found), while each full collection walks set-up's 2e5 objects for
    # tens of milliseconds, which this host-bound prefill would take in its
    # latency every eighth request or so, deciding the 95th percentile
    gc.disable()
    try:
        with _lm._precision(cfg), rec.window(lt.rt.executor):
            t0 = time.perf_counter()
            while True:
                req = next(stream)
                with rec.annotate("bench.prefill"):
                    a = time.perf_counter()
                    logits = lt.prefill(req.tokens, req.max_seq)
                    ttft = time.perf_counter() - a
                st.served.append((req, ttft, logits))
                if (len(st.served) % cycle == 0
                        and time.perf_counter() - t0 >= seconds):
                    break
    finally:
        gc.enable()
    tokens = sum(int(r.tokens.size) for r, _, _ in st.served)
    ttfts = np.array([t for _, t, _ in st.served])
    _lm._log_window(st.served, ttfts, collected)
    a = time.perf_counter()
    found = gc.collect()
    log(f"a collection after the window found {found} objects in "
        f"{(time.perf_counter() - a) * 1e3:.1f} ms")
    flops = sum(counts.prefill_flops(cfg, r.length) * r.tokens.shape[0]
                for r, _, _ in st.served)
    return Measured(
        units=len(st.served),
        end_to_end={"prefill_tok_s": tokens / rec.seconds,
                    "ttft_p95_ms": float(np.percentile(ttfts, 95)) * 1e3},
        work={"flops": flops, "tokens": tokens,
              "gmm_row_flops": counts.expert_row_flops(cfg),
              "gmm_row_bytes": counts.expert_row_bytes(cfg),
              "gmm_weight_bytes": counts.expert_weight_bytes(cfg)})


def verify(st: State) -> List[Check]:
    cfg = st.cell.config
    served = st.served
    picks = _lm.checked(len(served), [r.length for r, _, _ in served],
                        st.seed)
    st.lt = None
    st.served = []
    gc.collect()
    ref = st.cell.module("references", cfg["reference"])
    weights = make_weights(cfg, st.seed)
    log(f"program freed; reference checks {len(picks)} of "
        f"{len(served)} requests")
    results = []
    for i in picks:
        req, _, logits = served[i]
        for b in range(req.tokens.shape[0]):
            # past the share of swaps the run may need, it fails anyway:
            # the rest are checked without a search
            search = (sum(r[1] > 0 or r[0] > ref.LOGIT_ERR_LIMIT
                          for r in results)
                      <= ref.NEAR_TIE_SHARE * len(picks))
            results.append(ref.check(weights, req.tokens[b], cfg,
                                     logits[b, -1], search=search))
    _log_ties(results)
    errs, swaps, crossed, _ = zip(*results)
    return ref.compare(errs, swaps, crossed)


def _log_ties(results) -> None:
    """On stderr: the requests of the smallest gaps and of the largest
    logit errors, each as ``gap:error:swaps``, so that a run shows how
    near its ties came and what they cost."""
    def items(order):
        return ", ".join(f"{results[i][3]:.3g}:{results[i][0]:.3g}:"
                         f"{results[i][1]}" for i in order[:6])
    log("nearest tie:logit error:swaps, nearest ties "
        + items(np.argsort([r[3] for r in results]))
        + "; largest errors "
        + items(np.argsort([r[0] for r in results])[::-1]))


def _router_logits(lt, tokens: np.ndarray, max_seq: int) -> List:
    """The program's router logits of one prefill, one ``(tokens,
    experts)`` array per expert layer: the router's products are kept from
    being freed and read after the prefill."""
    from repro.core import lazy as bh
    routers = {id(lp["router"].view.base) for lp in lt.layers
               if "router" in lp}
    kept, real = [], bh.matmul

    def keep(a, b):
        out = real(a, b)
        if id(b.view.base) in routers:
            kept.append(out)
        return out
    bh.matmul = keep
    try:
        lt.prefill(tokens, max_seq)
    finally:
        bh.matmul = real
    with lt.rt.activate():
        return [k.numpy() for k in kept]


def control(cell, seed: int, units: int) -> dict:
    """The reference in the program's place with ``high`` products (three
    bfloat16 passes; the configuration states float32 at ``highest``),
    over the first ``units`` prompts of the seed's traffic, checked as a
    run checks its logits (``references/mla_moe.check``).  Beside it, what
    sets ``NEAR_TIE_DELTA``: the program's router logits against the
    reference's at ``highest`` over every expert layer and token of those
    prompts (``router_logit_diff``), each prompt's smallest gap, and how
    many of them lie under that difference and under ``NEAR_TIE_DELTA``."""
    cfg = cell.config
    ref = cell.module("references", cfg["reference"])
    prompts = []
    stream = generator.requests(cell.traffic, seed, cfg["vocab_size"])
    for _ in range(units):
        req = next(stream)
        prompts.append((req.tokens, req.max_seq))
    lt = _program(cfg, seed)
    with _lm._precision(cfg):
        served = [_router_logits(lt, t, m) for t, m in prompts]
    del lt
    gc.collect()
    weights = make_weights(cfg, seed)
    results, diff = [], 0.0
    for (tokens, _), routers in zip(prompts, served):
        _, _, r_routers = ref.forward(weights, tokens[0], cfg)
        for p, q in zip(routers, r_routers):
            diff = max(diff, float(np.max(np.abs(
                np.asarray(p, np.float64).reshape(q.shape) - np.asarray(q)))))
        ctl, _, _ = ref.forward(weights, tokens[0], cfg, matmul="bf16x3")
        results.append(ref.check(weights, tokens[0], cfg, ctl[0]))
    errs, swaps, crossed, nearest = zip(*results)
    out = {c.name: c.value for c in ref.compare(errs, swaps, crossed)}
    out["router_logit_diff"] = diff
    out["nearest_ties"] = [float(m) for m in nearest]
    out["nearest_under_diff"] = sum(m < diff for m in nearest)
    out["nearest_under_delta"] = sum(m < ref.NEAR_TIE_DELTA
                                     for m in nearest)
    return out
