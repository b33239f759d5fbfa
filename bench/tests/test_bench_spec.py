"""The harness finds a cell's parts by name, so a cell, configuration,
traffic mix or metric is added as files and entries alone; and the one
traffic generator gives every seed the same work."""

from __future__ import annotations

import json
from collections import Counter

import numpy as np
import pytest

from bench import generator, harness, spec

from .conftest import ROOT


def test_parts_added_as_files_only_are_found(tmp_path):
    bench = tmp_path / "bench"
    for kind in ("configs", "traffic", "metrics"):
        (bench / kind).mkdir(parents=True)
    (bench / "configs" / "toy.json").write_text(json.dumps({"n": 3}))
    (bench / "traffic" / "steady.json").write_text(
        json.dumps({"kind": "iterate", "clients": 1}))
    (bench / "metrics" / "toy.units.ms.py").write_text(
        "def read(w):\n    return w.measured.units * 2.0\n")
    (bench / "metrics" / "toy.silent.py").write_text(
        "def read(w):\n    return None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.steady", "config": "toy",
                       "traffic": "steady", "chips": 1}],
        "end_to_end": [
            {"name": "toy_ms", "unit": "ms", "workloads": ["toy.steady"]},
            {"name": "other_ms", "unit": "ms", "workloads": ["else"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "toy.units.ms", "unit": "ms", "moves": "toy_ms"},
            {"name": "toy.silent", "unit": "%", "moves": "toy_ms",
             "workloads": ["toy.steady"]},
            {"name": "other.metric", "unit": "%", "moves": "other_ms"}]}))

    cell = spec.resolve("toy.steady", root=tmp_path)
    assert cell.config == {"n": 3}
    assert cell.traffic["kind"] == "iterate"
    assert [m["name"] for m in cell.end_to_end] == ["toy_ms", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["toy.units.ms",
                                                   "toy.silent"]
    window = harness.Window(rec=harness.Recorder(traced=False),
                            measured=harness.Measured(units=21,
                                                      end_to_end={}),
                            peaks={})
    assert harness.read_per_layer(cell, window) == {
        "toy.units.ms": {"value": 42.0, "unit": "ms"}}


def test_every_benchmark_entry_has_its_files():
    bm = spec.load_benchmark(ROOT)
    for w in bm["workloads"]:
        cell = spec.resolve(w["name"])
        system = cell.module("systems", cell.config["system"])
        assert all(callable(getattr(system, f)) for f in
                   ("setup", "window", "verify", "control"))
        cell.module("references", cell.config["reference"])
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)


def test_a_metric_is_read_by_its_own_file_or_its_family(tmp_path):
    """``a.b.c`` is read by ``metrics/a.b.c.py`` where that exists, else
    by the longest dotted prefix that has a file."""
    metrics = tmp_path / "metrics"
    metrics.mkdir()
    for name in ("device.idle", "device.idle.special", "lm"):
        (metrics / f"{name}.py").write_text(
            f"def read(w):\n    return {name!r}\n")
    assert spec.reader_name("device.idle.special", tmp_path) == \
        "device.idle.special"
    assert spec.reader_name("device.idle.decode", tmp_path) == "device.idle"
    assert spec.reader_name("lm.step_mfu", tmp_path) == "lm"
    with pytest.raises(FileNotFoundError):
        spec.reader_name("plan.host_ms.lm", tmp_path)
    assert spec.reader_name("device.idle.lm") == "device.idle"
    assert spec.reader_name("lm.step_mfu") == "lm.step_mfu"


def _take(it, n):
    return [next(it) for _ in range(n)]


def test_every_seed_gets_the_same_work_in_another_order():
    tr = spec.read_json(spec.BENCH / "traffic" / "prefill_mix.json")
    generator.check_requests(tr, "prefill_mix")
    big = 2**31 + 12345
    a = _take(generator.requests(tr, big, 102400), 40)
    b = _take(generator.requests(tr, big, 102400), 40)
    c = _take(generator.requests(tr, 7, 102400), 40)
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    for reqs in (a, c):
        for k in range(0, 40, 4):       # each cycle of 4 holds 3 x 512, 1 x 2048
            assert Counter(r.length for r in reqs[k:k + 4]) == {512: 3,
                                                                 2048: 1}
        assert all(r.max_seq == r.length for r in reqs)
        assert all(0 <= r.tokens.min() and r.tokens.max() < 102400
                   for r in reqs)
    assert [r.length for r in a] != [r.length for r in c] or not all(
        np.array_equal(x.tokens, y.tokens) for x, y in zip(a, c))
    warm = _take(generator.requests(tr, big, 102400, stream=1), 4)
    assert not any(np.array_equal(w.tokens, x.tokens)
                   for w in warm for x in a[:4] if w.length == x.length)


def test_checked_sample_holds_the_longest_request():
    lm = spec.load_module("systems", "lazy_transformer")
    lengths = [512] * 300
    lengths[123] = 2048
    picks = lm.checked(300, lengths, 99)
    assert len(picks) == lm.CHECK_MAX and 123 in picks
    assert picks == lm.checked(300, lengths, 99)
    assert lm.checked(10, [512] * 10, 99) == list(range(10))
