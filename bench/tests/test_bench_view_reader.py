"""The reader of the ``permutes`` and ``gathers`` args of the program's
``block`` spans, on windows made by hand and on a traced run of the tiny
LM cell: it reads what its docstring says, and a window whose blocks lack
the args (a program that lacks them) reads nothing."""

from __future__ import annotations

import pytest

from bench import harness, spec
from bench import run as bench_run


def _blocks(*counts, args=True):
    """One ``block`` span per ``(backend, permutes, gathers)``."""
    return [{"name": "block", "ph": "X", "dur": 50.0,
             "args": dict({"backend": backend, "n_ops": 3,
                           "name": "repro_block", "cold": False},
                          **({"permutes": p, "gathers": g} if args else {}))}
            for backend, p, g in counts]


def _read(spans):
    rec = harness.Recorder(traced=True)
    rec.spans = list(spans)
    w = harness.Window(rec=rec, measured=harness.Measured(4, {}), peaks={})
    return spec.load_module(
        "metrics", spec.reader_name("view.permute_share.lm")).read(w)


MIXED = (("xla", 4, 0), ("xla", 0, 2), ("pallas", 0, 0), ("xla", 1, 3),
         ("xla", 4, 1), ("rmsnorm", 0, 0))


@pytest.mark.parametrize("counts, want", [
    (MIXED, 100.0 * 9 / 15),
    ((("xla", 4, 0), ("pallas", 0, 0), ("xla", 2, 0)), 100.0),
    ((("xla", 0, 2),), 0.0),
])
def test_permute_share_by_hand(counts, want):
    assert _read(_blocks(*counts)) == pytest.approx(want)


@pytest.mark.parametrize("spans", [
    [],
    _blocks(*MIXED, args=False),                         # the args absent
    _blocks(("xla", 0, 0), ("pallas", 0, 0)),            # no such view
    [{"name": "stage.execute", "ph": "X", "dur": 7000.0, "args": {}}],
], ids=["no spans", "no args", "no such view", "no blocks"])
def test_permute_share_without_its_views_reads_nothing(spans):
    assert _read(spans) is None


def test_tiny_lm_cell_permutes_every_view_it_does_not_slice(tiny_root):
    """The LM's transposed head views lower to transposes, none to a
    gather."""
    args = bench_run.parse_args(["--workload", "dsllm7b.prefill_mix",
                                 "--seed", "3000000019", "--seconds", "0.5",
                                 "--trace", "1"])
    res = bench_run.run(args, require_tpu=False, root=tiny_root)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["view.permute_share.lm"]["value"] == 100.0
