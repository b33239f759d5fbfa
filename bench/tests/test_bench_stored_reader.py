"""The reader of the ``stored_bytes`` arg of the program's ``block``
spans, ``view.stored_mb``: on windows made by hand, where a window whose
blocks lack the arg (a program that lacks it) reads nothing, and on a
traced run of the tiny LM cell, where every weight is read as it is
stored once a prefill."""

from __future__ import annotations

import pytest

from bench import harness, spec
from bench import run as bench_run

from .conftest import TINY


def _blocks(*stored, args=True):
    """One ``block`` span per ``(backend, stored_bytes)``."""
    return [{"name": "block", "ph": "X", "dur": 50.0,
             "args": dict({"backend": backend, "n_ops": 3,
                           "name": "repro_block", "cold": False,
                           "permutes": 0, "gathers": 0},
                          **({"stored_bytes": b} if args else {}))}
            for backend, b in stored]


def _read(spans, units=4, metric="view.stored_mb.lm"):
    rec = harness.Recorder(traced=True)
    rec.spans = list(spans)
    w = harness.Window(rec=rec, measured=harness.Measured(units, {}),
                       peaks={})
    return spec.load_module("metrics", spec.reader_name(metric)).read(w)


@pytest.mark.parametrize("metric", ["view.stored_mb.lm",
                                    "view.stored_mb.v2lite"])
@pytest.mark.parametrize("stored, units, want", [
    ((("xla", 4_000_000), ("pallas", 0), ("xla", 2_000_000)), 4, 1.5),
    ((("xla", 0), ("rmsnorm", 0)), 2, 0.0),
    ((("xla", 123_456_789),), 1, 123.456789),
])
def test_stored_mb_by_hand(metric, stored, units, want):
    assert _read(_blocks(*stored), units, metric) == pytest.approx(want)


@pytest.mark.parametrize("spans, units", [
    ([], 4),
    (_blocks(("xla", 4_000_000), args=False), 4),        # the arg absent
    ([{"name": "stage.execute", "ph": "X", "dur": 7000.0, "args": {}}], 4),
    (_blocks(("xla", 4_000_000)), 0),                    # no prefill
], ids=["no spans", "no arg", "no blocks", "no units"])
def test_stored_mb_without_its_arg_reads_nothing(spans, units):
    assert _read(spans, units) is None


def test_tiny_lm_cell_reads_every_weight_as_stored(tiny_root):
    """Each prefill of the tiny LM reads its embedding, head and every
    layer's projections in their adopted shape: at least their bytes a
    prefill, and little more (token ids and other whole adopted inputs)."""
    args = bench_run.parse_args(["--workload", "dsllm7b.prefill_mix",
                                 "--seed", "3000000019", "--seconds", "0.5",
                                 "--trace", "1"])
    res = bench_run.run(args, require_tpu=False, root=tiny_root)
    assert res["correct"] is True, res["checks"]
    c = TINY["dsllm7b"]
    d, f = c["hidden_size"], c["intermediate_size"]
    weights = 4 * (2 * c["vocab_size"] * d
                   + c["num_hidden_layers"] * (4 * d * d + 3 * d * f))
    got = res["metrics"]["view.stored_mb.lm"]["value"]
    assert weights / 1e6 <= got <= 1.01 * weights / 1e6
