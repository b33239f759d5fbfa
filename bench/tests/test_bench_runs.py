"""A whole run of each cell, driven on the CPU at tiny sizes: the harness's
look for a chip is skipped, everything else runs as on the chip.  A sound
run is correct; a run whose timed path is broken underneath is not; the
control computed in the next lower precision fails its limits; and the
command refuses to run without a TPU or without the program."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import generator
from bench import run as bench_run
from bench import spec

from .conftest import BENCH, ROOT

CELLS = ("heat2d.iterate", "dsllm7b.prefill_mix")


def _run(root, workload, trace=0, seconds=0.5, seed=3_000_000_019):
    args = bench_run.parse_args(["--workload", workload, "--seed", str(seed),
                                 "--seconds", str(seconds),
                                 "--trace", str(trace)])
    return bench_run.run(args, require_tpu=False, root=root)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_reports_its_metrics(tiny_root, workload):
    res = _run(tiny_root, workload)
    cell = spec.resolve(workload, root=tiny_root)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "setup_s" in res["metrics"]
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    if cell.traffic["kind"] == "requests":      # closes on whole cycles
        assert res["attempted"] % generator.cycle_length(cell.traffic) == 0


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reads_program_spans_and_counters(tiny_root, workload):
    """Off the chip the profiler's trace has no TPU plane: the readers of
    device metrics find nothing and are left out, never read as 0."""
    res = _run(tiny_root, workload, trace=1)
    cell = spec.resolve(workload, root=tiny_root)
    assert res["correct"] is True, res["checks"]
    by_source = {m["name"]: m["source"] for m in cell.per_layer}
    assert set(res["metrics"]) == {n for n, s in by_source.items()
                                   if s == "program_span"
                                   or s == "program_counter"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert "breakdown" not in res and "busy_s" not in res["device"]


def _alter_answer(monkeypatch):
    """An answer altered where it is produced: the first element of every
    array the runtime hands to the host is off by one."""
    from repro.core.lazy import Runtime
    real = Runtime.materialize

    def altered(self, view):
        out = np.array(real(self, view))
        out.reshape(-1)[0] += 1.0
        return out
    monkeypatch.setattr(Runtime, "materialize", altered)


def _unchanged_state(monkeypatch):
    """A step that returns its state unchanged: each fused loop's run
    hands back the state it was given."""
    from repro.core.executor import BlockExecutor
    monkeypatch.setattr(BlockExecutor, "run_loop",
                        lambda self, lp, state, *a, **k: list(state))


@pytest.mark.parametrize("workload,fault", [
    ("heat2d.iterate", _alter_answer),
    ("heat2d.iterate", _unchanged_state),
    ("dsllm7b.prefill_mix", _alter_answer),
])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, workload,
                                          fault):
    fault(monkeypatch)
    res = _run(tiny_root, workload)
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_control_reads_the_numbers_a_run_compares(tiny_root, workload):
    """``control.py`` takes a cell's control from its system module; it
    reads the same numbers a run compares, each finite."""
    res = _run(tiny_root, workload)
    cell = spec.resolve(workload, root=tiny_root)
    system = cell.module("systems", cell.config["system"])
    out = system.control(cell, 5, 2)
    assert set(res["checks"]) <= set(out)
    assert all(np.isfinite(v) for v in out.values())


def test_heat_control_in_bfloat16_fails_the_limit():
    """The heat reference computed in bfloat16 in the program's place."""
    ref = spec.load_module("references", "heat", BENCH)
    prog = spec.load_module("programs", "heat2d", BENCH)
    initial = prog.initial({"n": 130, "dtype": "float32"}, 5)
    want = ref.reference(initial, 500, "float32")
    (check,) = ref.compare(ref.reference(initial, 500, "bfloat16"), want)
    assert not check.ok and check.value > 10 * check.limit
    (same,) = ref.compare(want, want)
    assert same.ok and same.value == 0.0


def test_lm_control_in_three_bfloat16_passes_fails_a_limit():
    """The dense reference with ``high``-precision products (three
    bfloat16 passes) in the program's place, at a size a test holds."""
    lm = spec.load_module("systems", "lazy_transformer", BENCH)
    ref = spec.load_module("references", "dense_mha", BENCH)
    cfg = dict(spec.read_json(BENCH / "configs" / "dsllm7b.json"),
               hidden_size=512, intermediate_size=1024,
               num_attention_heads=4, num_key_value_heads=4,
               num_hidden_layers=2, vocab_size=4096)
    w = lm.make_weights(cfg, 11)
    rng = np.random.default_rng(11)
    got, want = [], []
    for _ in range(4):
        tokens = rng.integers(0, cfg["vocab_size"], 256, dtype=np.int32)
        want.append(np.asarray(ref.logits(w, tokens, cfg)))
        got.append(np.asarray(ref.logits(w, tokens, cfg, matmul="bf16x3")))
    checks = ref.compare(got, want)
    assert not all(c.ok for c in checks), checks
    assert all(c.ok for c in ref.compare(want, want))


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dsllm7b.prefill_mix",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_compile_cache_is_kept_whole_in_the_checkout(tmp_path):
    """The harness puts JAX's persistent cache in the checkout and lifts a
    size limit from the environment, which would evict a cell's programs
    between runs; a directory the environment names is kept."""
    probe = ("import jax; from bench import run; "
             "run.configure_jax_environment(); "
             "print(jax.config.jax_compilation_cache_max_size, "
             "jax.config.jax_compilation_cache_dir)")

    def configured(cache_dir=None):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
                   JAX_COMPILATION_CACHE_MAX_SIZE="1000")
        if cache_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        done = subprocess.run([sys.executable, "-c", probe], env=env,
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stderr
        return done.stdout.split()

    assert configured() == ["-1", str(ROOT / ".jax_cache")]
    given = str(tmp_path / "jax")
    assert configured(given) == ["-1", given]


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    done = _command(ROOT)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _command(tmp_path)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
