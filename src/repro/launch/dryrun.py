import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh and record the roofline inputs.

MUST be run as a module:  PYTHONPATH=src python -m repro.launch.dryrun \
    --arch qwen3-4b --shape train_4k --mesh single

The lines above run BEFORE any other import (jax locks the device count
on first init); 512 placeholder host devices back the 16×16 single-pod and
2×16×16 multi-pod meshes.

Per cell it writes ``experiments/dryrun/<arch>__<shape>__<mesh>.json`` with:
  * compiled.memory_analysis()  — bytes/device proof-of-fit
  * compiled.cost_analysis()    — HLO FLOPs / bytes accessed
  * collective bytes parsed from the optimized HLO (all-gather/all-reduce/
    reduce-scatter/all-to-all/collective-permute), with while-loop trip
    counts folded in (XLA's static analysis reports loop bodies once)
  * static workload facts (params, active params, tokens) for §Roofline.
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, SHAPES, cell_enabled, get_config, input_specs
from ..launch.mesh import make_production_mesh
from ..launch.steps import (batch_specs_tree, cache_specs, make_serve_steps,
                            make_train_step)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|"
                       r"u64|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")
_COLL_RE = re.compile(r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
                      r"collective-permute)(?:-start|-done)?\b")
_TRIP_RE = re.compile(
    r'known_trip_count"?\s*[:=]\s*\{\s*"?n"?\s*[:=]\s*"?(\d+)')


def _type_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _computations(hlo: str):
    """Split HLO text into computations: name -> list of body lines."""
    comps = {}
    cur = None
    decl = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
    for line in hlo.splitlines():
        if line.rstrip().endswith("{") and "->" in line:
            m = decl.match(line)
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line)
    return comps


_CALL_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")


def _multiplicities(hlo: str):
    """Execution count per computation: ENTRY=1; while bodies multiply by
    known_trip_count; fusions/calls inherit the caller's count."""
    comps = _computations(hlo)
    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY") or line.lstrip().startswith("ENTRY"):
            m = re.match(r"^\s*ENTRY\s+%?([\w.\-]+)", line)
            if m:
                entry = m.group(1)
                break
    mult = {name: 0 for name in comps}
    if entry is None and comps:
        entry = next(iter(comps))
    mult[entry] = 1
    # iterate to fixpoint (call graph is a DAG; few passes suffice)
    for _ in range(30):
        changed = False
        for name, lines in comps.items():
            base = mult.get(name, 0)
            if base == 0:
                continue
            for line in lines:
                trip = 1
                if " while(" in line:
                    t = _TRIP_RE.search(line)
                    trip = int(t.group(1)) if t else 1
                for cm in _CALL_RE.finditer(line):
                    callee = cm.group(1)
                    want = base * (trip if " while(" in line else 1)
                    if mult.get(callee, 0) < want:
                        mult[callee] = want
                        changed = True
        if not changed:
            break
    return comps, mult


def parse_collectives(hlo: str) -> Dict[str, float]:
    """Sum collective result bytes over the optimized HLO, scaling each op
    by its computation's execution count (call graph × while trip counts —
    XLA's static analysis reports loop bodies once)."""
    comps, mult = _multiplicities(hlo)
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    counts: Dict[str, int] = {k: 0 for k in out}
    for name, lines in comps.items():
        scale = mult.get(name, 0)
        if scale == 0:
            continue
        for line in lines:
            cm = _COLL_RE.search(line)
            if cm and "=" in line and "-done" not in cm.group(0):
                kind = cm.group(1)
                lhs = line.split("=", 1)[1]
                out[kind] += _type_bytes(lhs.split(" ", 2)[1]
                                         if lhs else lhs) * scale
                counts[kind] += scale
    out["counts"] = counts
    return out


def top_buffers(hlo: str, k: int = 12):
    """Largest per-device tensors in the optimized HLO (memory forensics)."""
    best: Dict[str, int] = {}
    for line in hlo.splitlines():
        if "=" not in line:
            continue
        lhs, rhs = line.split("=", 1)
        name = lhs.strip().lstrip("%")
        ty = rhs.strip().split(" ")[0]
        b = _type_bytes(ty)
        if b > best.get(name, 0):
            best[name] = b
    top = sorted(best.items(), key=lambda kv: -kv[1])[:k]
    return [{"name": n, "gb": round(b / 1e9, 4)} for n, b in top]


_DOT_RE = re.compile(r"=\s*(\(?[a-z0-9]+\[[0-9,]*\][^ ]*)\s+dot\(")
_CDIM_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DOT_OPND_RE = re.compile(r"dot\(\s*%?([\w.\-]+)")


def parse_dot_flops(hlo: str) -> float:
    """Per-device matmul FLOPs with the call-graph execution counts folded
    in (XLA's cost_analysis counts loop/fusion bodies once)."""
    comps, mult = _multiplicities(hlo)
    # name -> dims of its result shape (first shape in the type)
    shapes: Dict[str, list] = {}
    for lines in comps.values():
        for line in lines:
            if "=" not in line:
                continue
            lhs, rhs = line.split("=", 1)
            nm = lhs.strip().lstrip("%")
            m = _SHAPE_RE.search(rhs.strip().split(" ")[0])
            if m:
                shapes[nm] = [int(d) for d in m.group(2).split(",") if d]
    # computation parameters: map "param.N" inside a computation to the
    # declared parameter types on the decl line is skipped — operand shapes
    # for dots are almost always locally-defined instructions.
    total = 0.0
    for name, lines in comps.items():
        scale = mult.get(name, 0)
        if scale == 0:
            continue
        for line in lines:
            dm = _DOT_RE.search(line)
            if dm is None:
                continue
            out_elems = 1
            ms = _SHAPE_RE.search(dm.group(1))
            if ms:
                for d in ms.group(2).split(","):
                    if d:
                        out_elems *= int(d)
            contract = 1
            op = _DOT_OPND_RE.search(line)
            cd = _CDIM_RE.search(line)
            if op and cd:
                dims = shapes.get(op.group(1))
                if dims:
                    for ci in (int(c) for c in cd.group(1).split(",") if c):
                        if ci < len(dims):
                            contract *= dims[ci]
            total += 2.0 * out_elems * contract * scale
    return total


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: str = "experiments/dryrun",
             attn_chunk: Optional[int] = None) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    enabled, why = cell_enabled(arch, shape_name)
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                 "kind": shape.kind, "seq_len": shape.seq_len,
                 "global_batch": shape.global_batch,
                 "n_params": cfg.n_params(),
                 "n_active_params": cfg.active_params()}
    if not enabled:
        rec["skipped"] = why
        _write(rec, out_dir)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = int(np.prod(list(mesh.shape.values())))
    rec["n_devices"] = n_dev
    t0 = time.time()

    if shape.kind == "train":
        train_step, specs = make_train_step(cfg, mesh)
        batch_shapes = input_specs(cfg, shape)
        bspecs = batch_specs_tree(batch_shapes, mesh)
        ns = lambda s: jax.tree.map(lambda p: NamedSharding(mesh, p), s)  # noqa: E731
        jitted = jax.jit(
            train_step,
            in_shardings=(ns(specs["params"]), ns(specs["opt"]), ns(bspecs)),
            out_shardings=(ns(specs["params"]), ns(specs["opt"]),
                           NamedSharding(mesh, P())),
            donate_argnums=(0, 1))
        from ..optim.adamw import adamw_init
        oshapes = specs["oshapes"]
        lowered = jitted.lower(specs["pshapes"], oshapes, batch_shapes)
    else:
        prefill, decode, specs = make_serve_steps(
            cfg, mesh, max_seq=shape.seq_len, batch=shape.global_batch)
        ns = lambda s: jax.tree.map(lambda p: NamedSharding(mesh, p), s)  # noqa: E731
        ins = input_specs(cfg, shape)
        if shape.kind == "prefill":
            bspecs = batch_specs_tree(ins, mesh)
            jitted = jax.jit(prefill,
                             in_shardings=(ns(specs["params"]), ns(bspecs)),
                             out_shardings=(NamedSharding(mesh, P()),
                                            ns(specs["cache"])))
            lowered = jitted.lower(specs["pshapes"], ins)
        else:
            tok_spec = ins["token"]
            cache_shapes = ins["cache"]
            cspecs = cache_specs(cache_shapes, mesh, shape.global_batch)
            args = [specs["pshapes"], cache_shapes, tok_spec]
            in_sh = [ns(specs["params"]), ns(cspecs),
                     NamedSharding(mesh, P())]
            fn = decode
            if "enc_out" in ins:        # whisper cross-attention source
                fn = lambda p, c, t, e: decode(p, c, t, enc_out=e)  # noqa
                args.append(ins["enc_out"])
                bs = P(batch_specs_tree({"x": ins["enc_out"]}, mesh)["x"][0])
                in_sh.append(NamedSharding(
                    mesh, P(bs[0], None, None)))
            jitted = jax.jit(
                fn,
                in_shardings=tuple(in_sh),
                out_shardings=(NamedSharding(mesh, P()), ns(cspecs)),
                donate_argnums=(1,))
            lowered = jitted.lower(*args)

    rec["t_lower_s"] = time.time() - t0
    t1 = time.time()
    compiled = lowered.compile()
    rec["t_compile_s"] = time.time() - t1

    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(ma, k)) for k in
            ("generated_code_size_in_bytes", "argument_size_in_bytes",
             "output_size_in_bytes", "alias_size_in_bytes",
             "temp_size_in_bytes") if hasattr(ma, k)}
    except Exception as e:      # CPU backend may not implement it
        rec["memory_analysis"] = {"error": str(e)}
    try:
        ca = compiled.cost_analysis()
        rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                if isinstance(v, (int, float))}
    except Exception as e:
        rec["cost_analysis"] = {"error": str(e)}
    t2 = time.time()
    hlo = compiled.as_text()
    rec["hlo_bytes"] = len(hlo)
    rec["collectives"] = parse_collectives(hlo)
    rec["dot_flops_per_device"] = parse_dot_flops(hlo)
    rec["top_buffers"] = top_buffers(hlo)
    rec["t_parse_s"] = time.time() - t2
    del hlo
    _write(rec, out_dir)
    return rec


def _write(rec: Dict, out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=float)
    print(f"[dryrun] wrote {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", choices=("all",) + ARCHS)
    ap.add_argument("--shape", default="all",
                    choices=("all",) + tuple(SHAPES))
    ap.add_argument("--mesh", default="single", choices=("single", "multi"))
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    archs = ARCHS if args.arch == "all" else (args.arch,)
    shapes = tuple(SHAPES) if args.shape == "all" else (args.shape,)
    failures = []
    for a in archs:
        for s in shapes:
            print(f"=== {a} × {s} × {args.mesh} ===", flush=True)
            try:
                rec = run_cell(a, s, args.mesh, out_dir=args.out)
                if "skipped" in rec:
                    print(f"    skipped: {rec['skipped']}")
                else:
                    print(f"    ok: compile {rec['t_compile_s']:.1f}s, "
                          f"flops={rec['cost_analysis'].get('flops', 0):.3g}")
            except Exception as e:
                traceback.print_exc()
                failures.append((a, s, str(e)))
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("dry-run complete: all cells lowered and compiled")


if __name__ == "__main__":
    main()
