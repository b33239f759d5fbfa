"""LM workloads through the lazy runtime (ISSUE 10, DESIGN.md §20).

A tiny-config transformer forward / prefill / decode step traced through
:class:`repro.models.lazy_transformer.LazyTransformer` flushes as one tape
per call, and its logits and KV caches agree with the jitted direct model
at float32 ``highest`` to ``LOGIT_ERR``, while the ``backend="lm"`` stack
lowers the rmsnorm and masked-softmax blocks through the ``rowblock``
backend (asserted via executor stats and the explain report).

Tolerance: ``LOGIT_ERR`` is the benchmark's own limit on max|got - ref| /
max|ref|.  The program and the direct model sum the same float32 products
in different blocks and orders, which moves logits by a few 1e-7; products
in bfloat16 move them by 1e-5 or more (``test_lazy_mla_moe.py``'s
control).  Bits are held only between two runs of the program.
"""

import jax
import numpy as np
import pytest

from repro.core.obs.explain import explain
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.lazy_transformer import LazyTransformer, validate_config

CFG = ModelConfig(name="lm_tiny", family="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=97,
                  dtype="float32", param_dtype="float32", norm_plus_one=True,
                  tie_embeddings=False)
TOKENS = np.asarray([[3, 14, 15, 92, 65, 35], [8, 9, 79, 3, 2, 38]], np.int32)
MAX_SEQ = 16
#: relative error of the logits (module doc)
LOGIT_ERR = 3e-6


@pytest.fixture(scope="module")
def params():
    p, _ = T.init_params(CFG, jax.random.PRNGKey(0))
    return p


@pytest.fixture(scope="module")
def lt(params):
    return LazyTransformer(params, CFG)


def _claims(rt) -> dict:
    return dict(rt.executor.stats.get("backend_blocks", {}))


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _jit_highest(fn):
    jitted = jax.jit(fn)

    def run(*args):
        with jax.default_matmul_precision("highest"):
            return jitted(*args)
    return run


def test_forward_bitwise_identical_to_jitted_direct(params, lt):
    """Within ``LOGIT_ERR`` of the jitted direct forward; bitwise equal
    across two runs of the program."""
    ref = _jit_highest(lambda p, t: T.forward(p, t, CFG)[0])(params, TOKENS)
    got = lt.forward(TOKENS)
    assert got.dtype == np.float32 and got.shape == ref.shape
    assert _rel(got, ref) <= LOGIT_ERR
    assert lt.forward(TOKENS).tobytes() == got.tobytes()


def test_forward_lowers_through_rowblock(lt):
    lt.forward(TOKENS)
    claims = _claims(lt.rt)
    # per forward: one scale block per rmsnorm (2 per layer + final), two
    # reduction blocks per attention softmax
    assert claims.get("rowblock", 0) >= 4 * CFG.n_layers + 1


def test_explain_report_shows_rowblock_decisions(lt):
    lt.forward(TOKENS)
    rep = explain(lt.rt)
    assert rep.backends == ("rowblock", "pallas", "xla")
    won = [blk for blk in rep.blocks if blk.backend == "rowblock"]
    norm = next(blk for blk in won if "rsqrt" in blk.opcodes)
    by_name = {v.backend: v for v in norm.verdicts}
    assert by_name["rowblock"].claimed and by_name["rowblock"].winner
    assert any("reduce_max" in blk.opcodes for blk in won)
    assert any("reduce_sum" in blk.opcodes and "exp" in blk.opcodes
               for blk in won)
    # blocks outside the row patterns decline with the screen's slug
    declined = [v for blk in rep.blocks for v in blk.verdicts
                if v.backend == "rowblock" and not v.claimed]
    assert declined and {v.reason for v in declined} >= {"no_row_pattern"}


def test_prefill_and_decode_bitwise_identical_to_jitted_serving(params, lt):
    """Prefill logits and KV caches, then three decode steps, each within
    ``LOGIT_ERR`` of the jitted direct serving path."""
    ref_logits, ref_caches = _jit_highest(
        lambda p, t: T.serve_prefill(p, t, CFG, MAX_SEQ))(params, TOKENS)
    lt.rt.executor.reset_stats()
    got = lt.prefill(TOKENS, MAX_SEQ)
    assert _rel(got, ref_logits) <= LOGIT_ERR
    # the tiny prefill's blocks by backend: rowblock takes its 4 softmax
    # and 5 rmsnorm blocks
    assert _claims(lt.rt) == {"rowblock": 9, "pallas": 20, "xla": 22}

    unit, n_groups = CFG.scan_groups()
    cache_np = lt.cache_numpy()
    li = 0
    for g in range(n_groups):
        for i in range(len(unit)):
            gk, gv = cache_np[li]
            assert _rel(gk, np.asarray(ref_caches[f"l{i}"]["k"])[g]) \
                <= LOGIT_ERR
            assert _rel(gv, np.asarray(ref_caches[f"l{i}"]["v"])[g]) \
                <= LOGIT_ERR
            li += 1

    dec = _jit_highest(lambda p, c, t: T.serve_decode(p, c, t, CFG))
    caches = ref_caches
    for step in range(3):
        tok = np.asarray([[5 + step], [11 + step]], np.int32)
        ref_l, caches = dec(params, caches, tok)
        got_l = lt.decode(tok)
        assert _rel(got_l, ref_l) <= LOGIT_ERR, f"decode step {step}"
    assert _claims(lt.rt).get("rowblock", 0) >= 1


def test_lm_fuzz_grammars_cover_all_claimants():
    """One seed per LMProgram grammar: the lm stack agrees with the XLA
    stack to ``tapegen.LM_RTOL``, and ``rowblock`` claims a block of every
    grammar but moe (gathers on the XLA floor)."""
    from repro.testing.tapegen import LMProgram, check_lm
    grammars = {LMProgram(seed).grammar for seed in range(4)}
    assert grammars == {"rmsnorm", "attention", "moe", "scan"}
    for seed in range(4):
        check_lm(seed)


def test_validate_config_rejects_unsupported():
    import dataclasses
    validate_config(CFG)                       # the supported shape passes
    for kw in ({"dtype": "bfloat16"}, {"n_kv_heads": 1}, {"act": "gelu"},
               {"tie_embeddings": True}, {"qkv_bias": True}):
        with pytest.raises(ValueError):
            validate_config(dataclasses.replace(CFG, **kw))
