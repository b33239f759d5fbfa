"""Distribution-layer tests: sharding rules, cache specs, and the pod-axis
pipeline (run in a subprocess with 8 fake host devices)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import (RULES_SERVE, RULES_TRAIN,
                                        logical_to_mesh, params_specs)
from repro.models.transformer import abstract_params


@pytest.fixture(scope="module")
def mesh():
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))


def test_logical_to_mesh_divisibility():
    # a dim not divisible by its mesh axis falls back to replication
    # (duck-typed mesh: logical_to_mesh only reads mesh.shape)
    from types import SimpleNamespace
    fake = SimpleNamespace(shape={"data": 16, "model": 16})
    spec = logical_to_mesh((2, 64), ("kv_heads", "embed"), RULES_TRAIN, fake)
    assert spec == P(None, "data")
    spec = logical_to_mesh((32, 64), ("kv_heads", "embed"), RULES_TRAIN, fake)
    assert spec == P("model", "data")


def test_params_specs_cover_all_archs(mesh):
    for arch in ("qwen3-4b", "rwkv6-3b", "jamba-v0.1-52b", "whisper-tiny",
                 "qwen3-moe-235b-a22b"):
        cfg = get_config(arch, smoke=True)
        shapes, axes = abstract_params(cfg)
        for rules in (RULES_TRAIN, RULES_SERVE):
            specs = params_specs(shapes, axes, rules, mesh)
            # every leaf got a PartitionSpec of matching rank
            def check(leaf, spec):
                assert isinstance(spec, P)
                assert len(spec) <= len(leaf.shape)
            jax.tree.map(check, shapes, specs)


def test_fsdp_shards_embed_on_production_mesh():
    """On the 16×16 production mesh the training rules must shard d_model
    over data (FSDP) and heads/ffn/vocab over model (TP)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        import jax, json
        from repro.configs import get_config
        from repro.models.transformer import abstract_params
        from repro.distributed.sharding import RULES_TRAIN, params_specs
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh()
        cfg = get_config("qwen3-4b")
        shapes, axes = abstract_params(cfg)
        specs = params_specs(shapes, axes, RULES_TRAIN, mesh)
        wq = specs["groups"]["l0"]["mixer"]["wq"]
        emb = specs["embed"]
        head = specs["lm_head"]
        print(json.dumps({"wq": list(wq), "embed": list(emb),
                          "lm_head": list(head)}))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**os.environ, "PYTHONPATH": "src"})
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["wq"] == [None, "data", "model"]      # (layers, embed, heads)
    assert got["embed"] == [None, "model"]           # gather-local table
    assert got["lm_head"] == ["data", "model"]


def test_pipeline_pod_axis():
    """GPipe over a 4-way axis must equal the sequential composition."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("pod", "data"),
                             axis_types=(AxisType.Auto,) * 2)
        n_stages, m, d = 4, 6, 16
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (n_stages, d, d)) * 0.3

        def stage_fn(wi, x):
            return jnp.tanh(x @ wi)

        x = jax.random.normal(jax.random.PRNGKey(1), (m, 8, d))
        got = pipeline_apply(stage_fn, w, x, mesh=mesh, axis="pod")
        want = x
        for s in range(n_stages):
            want = jax.vmap(lambda mb: stage_fn(w[s], mb))(want)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        print("PIPELINE-OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**os.environ, "PYTHONPATH": "src"})
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINE-OK" in out.stdout


def test_bubble_fraction():
    from repro.distributed.pipeline import bubble_fraction
    assert bubble_fraction(2, 8) == pytest.approx(1 / 9)
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)


def test_elastic_reshard_across_meshes():
    """Elastic scaling: checkpoint from one topology restores (bit-exact)
    onto another — run in a subprocess with 8 fake devices so the meshes
    actually differ."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.configs import get_config
        from repro.models.transformer import init_params
        from repro.runtime.elastic import reshard_params
        from repro.checkpoint.manager import CheckpointManager
        import tempfile
        cfg = get_config("qwen3-4b", smoke=True)
        params, axes = init_params(cfg, jax.random.PRNGKey(0))
        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        mesh_b = jax.make_mesh((2, 4), ("data", "model"))
        pa = reshard_params(params, axes, mesh_a)
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)
        mgr.save(1, pa, blocking=True)
        pb_like = reshard_params(params, axes, mesh_b)   # target topology
        _, pb = mgr.restore(1, pb_like)
        for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print("ELASTIC-OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**os.environ, "PYTHONPATH": "src"})
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ELASTIC-OK" in out.stdout


def test_serve_launcher_smoke(capsys):
    from repro.launch.serve import main as serve_main
    serve_main(["--arch", "qwen3-4b", "--requests", "4", "--batch", "2",
                "--max-prompt", "16", "--new-tokens", "4"])
