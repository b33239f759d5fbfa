"""Prefill requests served by ``LazyTransformer`` on the lazy runtime.

The configuration is a model's published ``config.json`` keys (cut as its
``reduced`` entry in ``BENCHMARK.json`` says) plus the matmul precision it
is run at and the ``Runtime`` options.  Weights are drawn from the seed on
the device in one jitted call.  The traffic mix (``kind: "requests"``, read
by ``generator.py``) gives the prompts; one client sends them in a closed
loop, each ``prefill`` returning the last position's logits to the host.

* ``ttft_p95_ms``: 95th percentile over every request of the window of the
  time from the call to ``prefill`` until its logits are on the host;
* ``prefill_tok_s``: prompt tokens of all those requests over the window.

The window closes at the end of the first whole cycle of the mix (a cycle
holds each prompt length as often as the mix says) that ends after its
seconds, so every window, traced or not, serves the same mix of lengths.

After the window the program is freed and the plain reference
(``references/<reference>.py``) computes the logits of a sample of the
window's requests, drawn from the seed with the longest prompt in it,
from weights made again from the seed.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List

import numpy as np

from bench import counts, generator
from bench.harness import Check, Measured, Recorder, log

#: requests the reference checks after a window: all of them up to this
#: many, else a sample of this many with the longest prompt in it
CHECK_MAX = 256
#: times each prompt length is served in set-up before the window opens
WARM_REPEATS = 2


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a published ``config.json``."""
    from repro.models.config import ModelConfig

    if cfg.get("hidden_act") != "silu" or cfg.get("tie_word_embeddings"):
        raise ValueError("a SwiGLU decoder with an untied head is served")
    if float(cfg["rms_norm_eps"]) != 1e-6:
        raise ValueError("the lazy transformer's RMSNorm uses eps 1e-6")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=d, n_heads=heads, n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=d // heads, rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=False, act="silu", dtype=cfg["torch_dtype"],
        param_dtype=cfg["torch_dtype"], remat=False)


def make_weights(cfg: Dict[str, Any], seed: int):
    """The model's weights from ``seed``, on the device, in one jitted
    call, as the stacked tree ``LazyTransformer`` takes: projections drawn
    with standard deviation ``1/sqrt(fan_in)``, the embedding with 1, and
    RMSNorm gains near 1 (``1 + 0.1 N(0, 1)``)."""
    import jax
    import jax.numpy as jnp

    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    shapes = {"wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d),
              "wo": (L, d, d), "w_gate": (L, d, f), "w_up": (L, d, f),
              "w_down": (L, f, d)}

    def init(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, std):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * std).astype(dtype)

        def gain(shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                                  jnp.float32)).astype(dtype)
        w = {k: normal(s, s[1] ** -0.5) for k, s in shapes.items()}
        return {
            "embed": normal((V, d), 1.0),
            "lm_head": normal((d, V), d ** -0.5),
            "final_norm": {"g": gain((d,))},
            "groups": {"l0": {
                "norm1": {"g": gain((L, d))}, "norm2": {"g": gain((L, d))},
                "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}}}

    key = jax.random.PRNGKey(
        np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(1)[0])
    return jax.jit(init)(key)


@dataclass
class State:
    cell: Any
    seed: int
    lt: Any
    #: (request, seconds to first token, last-position logits)
    served: List[Any] = field(default_factory=list)


def _precision(cfg):
    import jax
    return jax.default_matmul_precision(cfg["matmul_precision"])


def setup(cell, seed: int) -> State:
    import jax
    from repro.models.lazy_transformer import LazyTransformer

    cfg = cell.config
    generator.check_requests(cell.traffic, cell.traffic_name)
    host = jax.device_get(make_weights(cfg, seed))
    log("weights made on the device and copied to the host")
    lt = LazyTransformer(host, model_config(cfg), **cfg.get("runtime", {}))
    del host
    gc.collect()
    log("weights adopted by the program")
    # each prompt length WARM_REPEATS times, in the mix's order, so that
    # every seed's set-up does the same work in the same order
    warm = {int(s): [] for s in cell.traffic["prompt_lengths"]}
    for req in generator.requests(cell.traffic, seed, cfg["vocab_size"],
                                  stream=1):
        if len(warm[req.length]) < WARM_REPEATS:
            warm[req.length].append(req)
        if all(len(w) == WARM_REPEATS for w in warm.values()):
            break
    with _precision(cfg):
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
    with _precision(cfg), rec.window(lt.rt.executor):
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
    tokens = sum(int(r.tokens.size) for r, _, _ in st.served)
    ttfts = np.array([t for _, t, _ in st.served])
    _log_window(st.served, ttfts, collected)
    flops = sum(counts.prefill_flops(cfg, r.length) * r.tokens.shape[0]
                for r, _, _ in st.served)
    return Measured(
        units=len(st.served),
        end_to_end={"prefill_tok_s": tokens / rec.seconds,
                    "ttft_p95_ms": float(np.percentile(ttfts, 95)) * 1e3},
        work={"flops": flops, "tokens": tokens})


def _log_window(served, ttfts, collected) -> None:
    """On stderr: the median time to first token of each prompt length,
    the slowest requests beside it, and the garbage collections of each
    generation the window ran, so that a slow window shows where it lost
    its time."""
    lengths = np.array([r.length for r, _, _ in served])
    medians = {int(n): float(np.median(ttfts[lengths == n]))
               for n in np.unique(lengths)}
    slow = sorted(range(len(served)), key=lambda i: -(
        ttfts[i] - medians[int(lengths[i])]))[:3]
    gcs = [g["collections"] - c for g, c in zip(gc.get_stats(), collected)]
    log("window: median ms by prompt length "
        + ", ".join(f"{n}: {m * 1e3:.1f}" for n, m in medians.items())
        + "; slowest " + ", ".join(
            f"#{i} ({int(lengths[i])}) {ttfts[i] * 1e3:.1f}" for i in slow)
        + f"; garbage collections by generation {gcs}")


def checked(n: int, lengths: List[int], seed: int) -> List[int]:
    """Indices of the requests the reference checks: all of them up to
    :data:`CHECK_MAX`, else a sample drawn from the seed that holds the
    first request of the longest prompt length."""
    if n <= CHECK_MAX:
        return list(range(n))
    longest = int(np.argmax(lengths))
    rng = np.random.default_rng([int(seed) % (1 << 64), 7])
    rest = [i for i in range(n) if i != longest]
    pick = rng.choice(len(rest), CHECK_MAX - 1, replace=False)
    return sorted([longest] + [rest[int(j)] for j in pick])


def verify(st: State) -> List[Check]:
    cfg = st.cell.config
    served = st.served
    picks = checked(len(served), [r.length for r, _, _ in served], st.seed)
    st.lt = None
    st.served = []
    gc.collect()
    ref = st.cell.module("references", cfg["reference"])
    weights = make_weights(cfg, st.seed)
    log(f"program freed; reference checks {len(picks)} of "
        f"{len(served)} requests")
    got, want = [], []
    for i in picks:
        req, _, logits = served[i]
        for b in range(req.tokens.shape[0]):
            got.append(logits[b, -1])
            want.append(np.asarray(ref.logits(weights, req.tokens[b], cfg)))
    return ref.compare(got, want)


def control(cell, seed: int, units: int) -> dict:
    """The dense reference in the program's place with ``high`` products
    (three bfloat16 passes; the configuration states float32 at
    ``highest``), over the first ``units`` prompts of the seed's traffic,
    compared as a run compares its logits.  Beside it, the gap by which
    the reference's logit of the token the control puts first lies below
    the reference's best, at every position of each prompt."""
    import jax.numpy as jnp

    cfg = cell.config
    ref = cell.module("references", cfg["reference"])
    weights = make_weights(cfg, seed)
    stream = generator.requests(cell.traffic, seed, cfg["vocab_size"])
    got, want, gap = [], [], 0.0
    for _ in range(units):
        tokens = next(stream).tokens[0]
        r = ref.logits(weights, tokens, cfg, all_positions=True)
        c = ref.logits(weights, tokens, cfg, matmul="bf16x3",
                       all_positions=True)
        first = jnp.argmax(c, axis=-1)
        g = jnp.max(r, axis=-1) - jnp.take_along_axis(
            r, first[:, None], axis=-1)[:, 0]
        gap = max(gap, float(jnp.max(g)))
        got.append(np.asarray(c[-1]))
        want.append(np.asarray(r[-1]))
    out = {c.name: c.value for c in ref.compare(got, want)}
    out["token_gap_every_position"] = gap
    return out
