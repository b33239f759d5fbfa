"""Tiny copies of the benchmark's cells, for driving the harness on the
CPU: the same files, BENCHMARK.json and traffic, with sizes a test run
holds."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent

#: the cells' configurations cut to CPU size; widths are kept apart from
#: these (the harness checks nothing of sizes)
TINY = {
    "heat2d_f32": {"n": 130},
    "dsllm7b": {"hidden_size": 256, "intermediate_size": 512,
                "num_attention_heads": 2, "num_key_value_heads": 2,
                "num_hidden_layers": 2, "vocab_size": 512},
}
TINY_TRAFFIC = {"prefill_mix": {"prompt_lengths": [16, 64],
                                "max_seq_multiple": 16}}


#: the cell held out of BENCHMARK.json (see its file), which the tiny
#: checkout adds so that the array-program driver stays tested
HELD = json.loads((BENCH / "tests" / "data" / "held_heat.json").read_text())


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like directory whose BENCHMARK.json names the real
    cells and the held heat cell, with their configurations and traffic
    cut to tiny sizes and the benchmark's code linked in."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bm[key] = HELD[key] + bm[key]
    bench = tmp_path / "bench"
    for kind in ("configs", "traffic"):
        (bench / kind).mkdir(parents=True)
    for kind in ("systems", "programs", "references", "metrics"):
        (bench / kind).symlink_to(BENCH / kind)
    for c in bm["configs"]:
        cfg = (HELD["config"] if c in HELD["configs"]
               else json.loads((ROOT / c["file"]).read_text()))
        cfg = dict(cfg, **TINY[c["name"]])
        (tmp_path / c["file"]).write_text(json.dumps(cfg))
    for w in bm["workloads"]:
        tr = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                        .read_text())
        tr.update(TINY_TRAFFIC.get(w["traffic"], {}))
        (bench / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(tr))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp_path
