"""The ``dsv2lite.prefill_mix`` cell at a tiny size on the CPU
(``tests/data/tiny_dsv2lite.json``, the harness's look for a chip skipped):
a sound run is correct; a run whose served answer is altered is not; the
control with three-bfloat16-pass products fails the limit; the near-tie
rule of ``references/mla_moe.compare``; and the arithmetic of the
``moe.gmm_roofline`` reader on a synthetic trace and spans."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import run as bench_run
from bench import spec, tracefile
from bench.harness import Measured, Recorder, Window

from .conftest import BENCH

CELL = "dsv2lite.prefill_mix"
TINY = json.loads((BENCH / "tests" / "data" / "tiny_dsv2lite.json")
                  .read_text())["config"]


def _run(root, trace=0, seed=3_000_000_019):
    args = bench_run.parse_args(["--workload", CELL, "--seed", str(seed),
                                 "--seconds", "0.5", "--trace", str(trace)])
    return bench_run.run(args, require_tpu=False, root=root)


def test_sound_run_is_correct_and_reports_its_metrics(tiny_root):
    res = _run(tiny_root)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"prefill_tok_s", "ttft_p95_ms",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert set(res["checks"]) == {"logit_err", "near_tie_requests",
                                  "near_tie_gap"}
    assert res["attempted"] % 4 == 0


def test_traced_run_reads_the_cells_program_spans(tiny_root):
    """Off the chip there is no TPU plane and no chip's peaks: the grouped
    product's roofline, the idle share and the step's utilization are left
    out, never read as 0."""
    res = _run(tiny_root, trace=1)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"plan.host_ms.v2lite",
                                   "execute.host_ms.v2lite",
                                   "adopt.mb.v2lite"}
    # 3 latent caches of (max_seq, kv_lora_rank + rope) float32 a prefill,
    # over the mix's 3 prompts of 16 tokens to 1 of 64, and the tokens
    latent = TINY["kv_lora_rank"] + TINY["qk_rope_head_dim"]
    caches = 3 * latent * 4 * (3 * 16 + 64) / 4 / 1e6
    tokens = 4 * (3 * 16 + 64) / 4 / 1e6
    assert res["metrics"]["adopt.mb.v2lite"]["value"] == pytest.approx(
        caches + tokens, rel=0.05)


def test_altered_answer_is_not_correct(tiny_root, monkeypatch):
    from repro.core.lazy import Runtime
    real = Runtime.materialize

    def altered(self, view):
        out = np.array(real(self, view))
        out.reshape(-1)[0] += 1.0
        return out
    monkeypatch.setattr(Runtime, "materialize", altered)
    res = _run(tiny_root)
    assert res["correct"] is False, res["checks"]


def test_control_in_three_bfloat16_passes_fails_the_limit(tiny_root):
    cell = spec.resolve(CELL, root=tiny_root)
    system = cell.module("systems", cell.config["system"])
    out = system.control(cell, 5, 4)
    assert out["logit_err"] > cell.module(
        "references", "mla_moe").LOGIT_ERR_LIMIT, out
    assert 0 < out["router_logit_diff"] < 1e-4
    assert len(out["nearest_ties"]) == 4


def test_near_tie_rule(tiny_root, monkeypatch):
    """A served answer that took the other expert at a tie is right when
    the tie lies within ``NEAR_TIE_DELTA``, and wrong past it."""
    cell = spec.resolve(CELL, root=tiny_root)
    cfg = cell.config
    ref = cell.module("references", "mla_moe")
    weights = cell.module("systems", cfg["system"]).make_weights(cfg, 5)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 16)
    own, gaps, _ = ref.forward(weights, tokens, cfg)
    monkeypatch.setattr(ref, "NEAR_TIE_DELTA", float("inf"))
    (gap, layer, t), = ref.nearest_ties(gaps, 1)
    other, _, _ = ref.forward(weights, tokens, cfg, swaps=[(layer, t)])
    assert ref.rel_err(other, own) > ref.LOGIT_ERR_LIMIT
    assert ref.check(weights, tokens, cfg, own)[:3] == (0.0, 0, 0.0)
    err, used, crossed, _ = ref.check(weights, tokens, cfg, other)
    assert (err, used, crossed) == (0.0, 1, gap)
    monkeypatch.setattr(ref, "NEAR_TIE_DELTA", gap / 2)
    assert ref.check(weights, tokens, cfg, other)[:2] == (
        ref.rel_err(other, own), 0)
    # the run's checks: a quarter of the requests may need a swap
    ok = {c.name: c.ok for c in ref.compare([0.0] * 4, [0, 1, 0, 0],
                                            [0.0, gap / 2, 0.0, 0.0])}
    assert all(ok.values())
    ok = {c.name: c.ok for c in ref.compare([0.0] * 4, [0, 1, 2, 0],
                                            [0.0, gap / 2, gap / 2, 0.0])}
    assert not ok["near_tie_requests"] and ok["logit_err"]
    assert not ref.compare([0.0, 1e-3], [0, 0], [0.0, 0.0])[0].ok


def _window(spans, counters, ops, work):
    rec = Recorder(traced=True)
    rec.spans, rec.counters, rec.seconds = spans, counters, 1.0
    host = [tracefile.Event(tracefile.WINDOW, 0.0, 1e9)]
    rec.trace = tracefile.Trace(devices={"/device:TPU:0": ops}, host=host)
    return Window(rec=rec, measured=Measured(units=2, end_to_end={},
                                             work=work),
                  peaks={"bf16_flop_s": 100e12, "hbm_byte_s": 1e12})


def test_gmm_roofline_reader_arithmetic():
    reader = spec.load_module("metrics", "moe.gmm_roofline", BENCH)
    spans = [{"ph": "X", "name": "moe.rows", "args": {"rows": 300}},
             {"ph": "X", "name": "moe.rows", "args": {"rows": 100}},
             {"ph": "X", "name": "block", "args": {}}]
    ops = [tracefile.Event("%ragged-dot-none = f32[8,4]{1,0} custom-call(%a)",
                           1e6, 2e6),
           tracefile.Event("ragged-dot-metadata.1", 4e6, 1e6),
           tracefile.Event("fusion.3 = f32[8]{0} fusion(%b)", 6e6, 5e6)]
    work = {"gmm_row_flops": 1e9, "gmm_row_bytes": 1e5,
            "gmm_weight_bytes": 1e8}
    w = _window(spans, {"ragged_matmul_blocks": 6}, ops, work)
    assert reader.gmm_seconds(w.rec.trace) == pytest.approx(3e-3)
    # 400 rows: 4e11 FLOPs take 4 ms at 100 TFLOP/s; 6 weight passes and
    # the rows, 6.4e8 bytes, take 0.64 ms at 1 TB/s: compute bounds it
    assert reader.read(w) == pytest.approx(100 * 4e-3 / 3e-3)
    work["gmm_row_flops"] = 1e7                       # now bytes bound it
    assert reader.read(w) == pytest.approx(100 * 6.4e-4 / 3e-3)
    for broken in (_window(spans[2:], {"ragged_matmul_blocks": 6}, ops,
                           work),
                   _window(spans, {}, ops, work),
                   _window(spans, {"ragged_matmul_blocks": 6}, ops[2:],
                           work)):
        assert reader.read(broken) is None
