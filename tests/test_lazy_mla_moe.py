"""DeepSeek-V2's block through ``LazyTransformer`` at a tiny size, against
the benchmark's plain reference (``bench/references/mla_moe.py``, which
imports nothing of the program) on seeded weights: latent attention with a
latent cache, YaRN rope, a leading dense layer and dropless routed plus
shared experts, routed on the device by ``argsort`` and ``ragged_matmul``.

Tolerance: the last-position logits agree to ``LOGIT_ERR`` (3e-6 of their
largest magnitude), the benchmark's own limit: program and reference sum
the same float32 products in different orders, which moves logits by a few
1e-7 here, while products in bfloat16 (the reference with
``high``-precision products in the program's place, three bfloat16 passes)
move them by 1e-5 or more, which the control test below shows.
"""

import collections
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.spec import load_module
from repro.core import lazy as bh
from repro.core.blocks import BlockInfo
from repro.core.cost import make_cost_model
from repro.core.lazy import fresh_runtime
from repro.core.obs import trace
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.lazy_transformer import LazyTransformer, validate_config

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
SYSTEM = load_module("systems", "lazy_mla_moe", BENCH)
REF = load_module("references", "mla_moe", BENCH)

#: relative error of the logits (module doc)
LOGIT_ERR = 3e-6

#: 8 experts of width 32 behind the router, 4 held, top-2, 1 shared, one
#: dense layer then 2 expert layers, vocab 256
CFG = dict(json.loads((BENCH / "configs" / "dsv2lite.json").read_text()),
           hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
           kv_lora_rank=16, qk_rope_head_dim=8, qk_nope_head_dim=16,
           v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
           n_routed_experts=4, held_experts=[0, 4], num_experts_per_tok=2,
           n_shared_experts=1, num_hidden_layers=3, vocab_size=256,
           published={"num_hidden_layers": 27, "n_routed_experts": 8,
                      "torch_dtype": "bfloat16"})
SEED = 7


def _held(lo, hi):
    return dict(CFG, n_routed_experts=hi - lo, held_experts=[lo, hi])


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (1, n), dtype=np.int32)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.fixture(scope="module")
def weights():
    return SYSTEM.make_weights(CFG, SEED)


@pytest.fixture(scope="module")
def lt():
    with jax.default_matmul_precision("highest"):
        return SYSTEM._program(CFG, SEED)


def test_prefill_logits_match_the_reference(lt, weights):
    tokens = _tokens(40)
    with jax.default_matmul_precision("highest"):
        got = lt.prefill(tokens, 48)
    want, gaps, _ = REF.forward(weights, tokens[0], CFG)
    assert min(g.min() for g in gaps) > 1e-3, "no near tie in the prompt"
    assert got.shape == (1, 1, CFG["vocab_size"])
    assert _rel(got[0, -1], want[0]) <= LOGIT_ERR


def test_bf16_product_control_fails_the_tolerance(weights):
    """The reference with three-bfloat16-pass products against itself at
    ``highest``: the tolerance tells the two precisions apart."""
    errs = []
    for seed in range(3):
        tokens = _tokens(40, seed)[0]
        want, _, _ = REF.forward(weights, tokens, CFG)
        ctl, _, _ = REF.forward(weights, tokens, CFG, matmul="bf16x3")
        errs.append(_rel(ctl, want))
    assert max(errs) > LOGIT_ERR, errs


def test_prefill_then_decode_through_the_latent_cache(weights):
    """Prefill, then 4 decode steps that read only the latent cache, each
    against the reference's full forward at that position."""
    with jax.default_matmul_precision("highest"):
        lt = SYSTEM._program(CFG, SEED)
        tokens = _tokens(12, 3)
        steps = [np.asarray([[t]], np.int32) for t in (5, 77, 200, 31)]
        got = [lt.prefill(tokens, 32)[0, -1]]
        caches = lt.cache_numpy()
        for t in steps:
            got.append(lt.decode(t)[0, -1])
    seq = np.concatenate([tokens[0]] + [t[0] for t in steps])
    want, gaps, _ = REF.forward(weights, seq, CFG, all_positions=True)
    assert min(g.min() for g in gaps) > 1e-3
    for i, g in enumerate(got):
        assert _rel(g, want[11 + i]) <= LOGIT_ERR, i
    # the cache holds a latent and a roped key per token, nothing per head
    latent = CFG["kv_lora_rank"] + CFG["qk_rope_head_dim"]
    assert [c.shape for c in caches] == [(1, 32, latent)] * 3
    assert not np.any(caches[0][:, 12:])


def test_expert_shares_add_up_to_the_uncut_layer():
    """The routed outputs of the two halves of the experts, with the shared
    expert counted once, add up to the reference's whole layer."""
    full = _held(0, 8)
    w = SYSTEM.make_weights(full, SEED)
    lw = w["groups"]["l1"]
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (24, 64)),
                   np.float32)
    want, _, _ = jax.jit(lambda x, lw: REF._moe_ffn(
        x, lw, np.zeros(24, bool), cfg=full, mm=REF.mm_highest))(x, lw)
    host = jax.device_get(w)
    parts = []
    with jax.default_matmul_precision("highest"):
        for lo, hi in ((0, 4), (4, 8)):
            cfg = SYSTEM.model_config(dict(_held(lo, hi), name="share"))
            lt = LazyTransformer(host, cfg)
            lp = lt.layers[1]
            with lt.rt.activate():
                xa = lt.rt.adopt(x.reshape(1, 24, 64))
                h = lt._rmsnorm(xa, lp["norm2_g1"])
                y = lt._routed(lp, h)
                if lo == 0:
                    y = xa + (y + lt._swiglu(h, lp["s_gate"], lp["s_up"],
                                             lp["s_down"]))
                parts.append(y.numpy())
    assert _rel(parts[0] + parts[1], np.asarray(want)[None]) <= LOGIT_ERR


def test_argsort_and_ragged_matmul_match_jnp():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((6, 9)).astype(np.float32)
    a[2, 1] = a[2, 7]                                # a tie: index order
    x = rng.standard_normal((12, 5)).astype(np.float32)
    w = rng.standard_normal((3, 5, 4)).astype(np.float32)
    sizes = np.asarray([3, 0, 4], np.float32)       # 7 of 12 rows grouped
    with fresh_runtime(loop_fusion=False) as rt:
        order = bh.argsort(rt.adopt(a), axis=-1).numpy()
        order0 = bh.argsort(rt.adopt(a), axis=0).numpy()
        y = bh.ragged_matmul(rt.adopt(x), rt.adopt(w),
                             rt.adopt(sizes)).numpy()
    assert order.dtype == np.float32
    assert np.array_equal(order, np.argsort(a, -1, kind="stable"))
    assert np.array_equal(order0, np.argsort(a, 0, kind="stable"))
    with jax.default_matmul_precision("highest"):
        want = jax.lax.ragged_dot(x, w, sizes.astype(np.int32))
    np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[:3], x[:3] @ w[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y[3:7], x[3:7] @ w[2], rtol=1e-5, atol=1e-5)
    assert not np.any(y[7:])                          # past sum: zero


def test_ragged_matmul_zeroes_rows_a_kernel_leaves_unwritten(monkeypatch):
    """The TPU's grouped-product kernel leaves the rows past the groups'
    sum as it found them (the CPU's zeroes them): the lowering zeroes
    them itself, whatever they hold."""
    real = jax.lax.ragged_dot

    def unwritten(x, w, sizes, **kw):
        y = real(x, w, sizes, **kw)
        rows = jnp.arange(y.shape[0])[:, None]
        return jnp.where(rows < sizes.sum(), y, jnp.nan)
    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((10, 5)).astype(np.float32)
    w = rng.standard_normal((2, 5, 3)).astype(np.float32)
    with fresh_runtime(loop_fusion=False) as rt:
        y = bh.ragged_matmul(rt.adopt(x), rt.adopt(w),
                             rt.adopt(np.asarray([2, 4], np.float32))).numpy()
    np.testing.assert_allclose(y[:2], x[:2] @ w[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y[2:6], x[2:6] @ w[1], rtol=1e-5, atol=1e-5)
    assert not np.any(y[6:])


def test_routing_ops_run_in_blocks_of_their_own(lt):
    with jax.default_matmul_precision("highest"):
        lt.rt.use_cache = False
        try:
            lt.prefill(_tokens(16, 4), 16)
        finally:
            lt.rt.use_cache = True
    tape = lt.rt.last_tape
    seen = collections.Counter()
    for block in lt.rt.last_partition.op_blocks():
        work = [tape[i].opcode for i in block if not tape[i].is_system()]
        for oc in ("argsort", "ragged_matmul"):
            if oc in work:
                assert work == [oc], work
                seen[oc] += 1
    # per expert layer: the top-k sort, the assignment sort and its
    # inverse; gate, up and down
    assert seen == {"argsort": 6, "ragged_matmul": 6}


def test_prompts_routed_differently_share_one_plan(lt, weights):
    """Routing is data, not structure: a third prompt of a length hits
    the plan the second made (the first's key lacks a previous prompt's
    frees), though their routings differ."""
    prompts = [_tokens(24, s) for s in (10, 11, 12)]
    with jax.default_matmul_precision("highest"):
        for p in prompts:
            lt.prefill(p, 32)
    assert lt.rt.history[-1]["cached"] is True
    tops = [np.argsort(-np.asarray(REF.forward(weights, p[0], CFG)[2][0]),
                       axis=-1, kind="stable")[:, :2] for p in prompts[1:]]
    assert not np.array_equal(tops[0], tops[1])


def test_one_prefill_is_one_flush_and_one_host_read(lt):
    with jax.default_matmul_precision("highest"):
        lt.prefill(_tokens(20, 5), 32)                # warm
        tr = trace.Tracer()
        trace.enable(tr)
        try:
            lt.prefill(_tokens(20, 6), 32)
        finally:
            trace.disable()
    counts = tr.span_counts()
    assert counts.get("flush") == 1 and counts.get("sync.read") == 1
    (rows,) = [e["args"] for e in tr.events if e["name"] == "moe.rows"]
    assert rows["arrays"] == 2 and rows["groups"] == 2 * 4
    assert 0 < rows["max_rows"] <= rows["rows"] <= 2 * 20 * 2
    blocks = [e["args"] for e in tr.events
              if e["name"] == "block" and "opcode" in e["args"]]
    assert sorted({(b["opcode"], b["rows"]) for b in blocks}) == [
        ("argsort", 1), ("argsort", 20), ("ragged_matmul", 40)]


def test_untraced_prefill_reads_no_group_sizes(lt, monkeypatch):
    from repro.core.executor import BlockExecutor
    monkeypatch.setattr(BlockExecutor, "_keep_group_sizes",
                        lambda *a: pytest.fail("read while untraced"))
    with jax.default_matmul_precision("highest"):
        lt.prefill(_tokens(20, 7), 32)
    stats = lt.rt.history[-1]["exec"]
    assert stats["argsort_blocks"] == 6 and stats["ragged_matmul_blocks"] == 6


def test_routing_ops_are_priced_and_keyed_by_structure():
    """Opaque blocks with a price under every cost model, and a signature
    that holds the group sizes' shape, never their values."""
    def tape(sizes):
        with fresh_runtime(loop_fusion=False) as rt:
            y = bh.ragged_matmul(rt.adopt(np.ones((6, 4), np.float32)),
                                 rt.adopt(np.ones((2, 4, 3), np.float32)),
                                 rt.adopt(np.asarray(sizes, np.float32)))
            ops = [op for op in rt.tape if not op.is_system()]
            y._alive = False
            rt.tape.clear()
            return ops
    a, b = tape([1, 2]), tape([4, 0])
    from repro.core.cache import block_signature
    assert block_signature(a) == block_signature(b)
    for name in ("bohrium", "tpu"):
        cost = make_cost_model(name).block_cost(BlockInfo.from_ops(a))
        assert 0 < cost < float("inf")


def test_validate_config_accepts_deepseek_v2_and_refuses_the_rest():
    cfg = SYSTEM.model_config(dict(CFG, name="tiny"))
    validate_config(cfg)
    moe = cfg.moe
    for kw in ({"n_kv_heads": 2}, {"qkv_bias": True},
               {"attn_softcap": 50.0}, {"final_softcap": 30.0},
               {"tie_embeddings": True}, {"dtype": "bfloat16"},
               {"mla": None}, {"moe_period": 2},
               {"mla": dataclasses.replace(cfg.mla, q_lora_rank=32)},
               {"moe": dataclasses.replace(moe, norm_topk_prob=True)},
               {"moe": dataclasses.replace(moe, scoring="sigmoid")},
               {"moe": dataclasses.replace(moe, topk_method="noaux_tc")},
               {"moe": dataclasses.replace(moe, held_experts=(4, 12))}):
        with pytest.raises(ValueError):
            validate_config(dataclasses.replace(cfg, **kw))


#: the dense tiny model's prefill and decode tapes before the expert layer
#: and latent attention were added: op counts by opcode and the digest of
#: the whole tape's structural signature (every op, view, literal and free
#: in order; since the signature keys each base's shape, with the adopted
#: weights' own shapes, op for op the tapes they were), for the gemma-style
#: and the plain norm scale
DENSE_TAPES = {
    True: {"prefill": ({"add": 13, "copy": 15, "del": 110, "div": 7,
                        "exp": 2, "gather": 1, "matmul": 19, "mul": 38,
                        "reduce_max": 2, "reduce_sum": 7, "rsqrt": 5,
                        "sigmoid": 2, "sub": 6, "sync": 1, "where": 2},
                       "9bc06e0fbf0cf81e"),
           "decode": ({"add": 13, "copy": 12, "del": 109, "div": 9,
                       "exp": 2, "gather": 1, "matmul": 19, "mul": 36,
                       "reduce_max": 2, "reduce_sum": 7, "rsqrt": 5,
                       "sigmoid": 2, "sub": 6, "sync": 1, "where": 2},
                      "5fe3296eb2f7136d")},
    False: {"prefill": ({"add": 13, "copy": 15, "del": 109, "div": 7,
                         "exp": 2, "gather": 1, "matmul": 19, "mul": 37,
                         "reduce_max": 2, "reduce_sum": 7, "rsqrt": 5,
                         "sigmoid": 2, "sub": 6, "sync": 1, "where": 2},
                        "491b707d2bd28639"),
            "decode": ({"add": 13, "copy": 12, "del": 108, "div": 9,
                        "exp": 2, "gather": 1, "matmul": 19, "mul": 35,
                        "reduce_max": 2, "reduce_sum": 7, "rsqrt": 5,
                        "sigmoid": 2, "sub": 6, "sync": 1, "where": 2},
                       "e274bab090a9a7dc")},
}


@pytest.mark.parametrize("plus_one", [True, False])
def test_dense_tapes_are_pinned(plus_one):
    """The dense model shares ``_layer``, ``_rope_consts`` and
    ``validate_config`` with DeepSeek-V2's: its tapes stay op for op what
    they were, and its rope tables bit for bit the formula they were
    computed by.  Equal tapes over equal constants run the same blocks on
    the same inputs, so the logits are the same bits on any one machine."""
    from repro.core.cache import block_signature
    from repro.core.tuning.profile import signature_digest
    cfg = ModelConfig(name="pin_tiny", family="dense", n_layers=2,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                      vocab_size=97, dtype="float32", param_dtype="float32",
                      tie_embeddings=False, remat=False,
                      norm_plus_one=plus_one)
    p, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    lt = LazyTransformer(p, cfg)
    tokens = np.asarray([[3, 14, 15, 92, 65, 35], [8, 9, 79, 3, 2, 38]],
                        np.int32)
    lt.prefill(tokens, 16)
    got = {"prefill": lt.rt.last_tape}
    lt.decode(np.asarray([[5], [11]], np.int32))
    got["decode"] = lt.rt.last_tape
    for entry, tape in got.items():
        ops = dict(sorted(collections.Counter(o.opcode for o in tape)
                          .items()))
        assert (ops, signature_digest(block_signature(tape))) \
            == DENSE_TAPES[plus_one][entry], entry
    positions = np.arange(6)[None]
    freq = cfg.rope_theta ** (-jnp.arange(0, 8, dtype=jnp.float32) / 8)
    ang = jnp.asarray(positions)[..., None].astype(jnp.float32) * freq
    cos, sin = lt._rope_consts(positions)
    with lt.rt.activate():
        assert np.asarray(jnp.cos(ang)[..., None, :]).tobytes() \
            == cos.numpy().tobytes()
        assert np.asarray(jnp.sin(ang)[..., None, :]).tobytes() \
            == sin.numpy().tobytes()
