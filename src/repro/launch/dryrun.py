import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# The lines above MUST run before any jax import (jax locks the platform and
# the device count at first init).  The dry run compiles on 512 host devices
# and never takes a chip; the per-cell children inherit both settings.
# Everything below may import jax freely.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/collective analysis.

  python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both      # subprocess per cell

Results are cached as JSON under benchmarks/results/dryrun/ and consumed by
launch/roofline.py and EXPERIMENTS.md.
"""
import argparse
import gzip
import json
import pathlib
import re
import subprocess
import sys
import time
from typing import Dict, Optional

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo: str) -> Dict[str, int]:
    """Per-device bytes moved by each collective kind (result-shape proxy),
    summed over all occurrences in the post-SPMD module.  Ops inside while
    bodies (scan over layers / microbatches) are counted once per appearance;
    the roofline layer multiplies by trip counts recorded separately."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo.splitlines():
        s = line.strip()
        if s.startswith("%") or s.startswith("ROOT"):
            m = re.search(r"=\s*(.+?)\s+(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(?:-start|-done)?\(", s)
            if m:
                kind = m.group(2)
                if "-done" in s.split("(")[0]:
                    continue  # avoid double counting start/done pairs
                out[kind] += _shape_bytes(m.group(1))
                counts[kind] += 1
    return {"bytes": out, "counts": counts}


def while_trip_counts(hlo: str):
    """Rough scan trip counts (layers groups, microbatches) from while loops:
    XLA encodes them as constants compared in loop conditions; we grep
    `constant(N)` in condition computations named *cond*."""
    trips = []
    for m in re.finditer(r"%constant[^\n]*= s32\[\] constant\((\d+)\)", hlo):
        trips.append(int(m.group(1)))
    return sorted(set(trips))


def memory_analysis_dict(compiled) -> Dict[str, float]:
    out = {}
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if ma is None:
        return {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes", "host_argument_size_in_bytes",
                  "host_output_size_in_bytes", "host_temp_size_in_bytes"):
        v = getattr(ma, field, None)
        if v is not None:
            out[field] = int(v)
    if not out:
        out["repr"] = str(ma)
    return out


def cost_analysis_dict(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in dict(ca).items()
            if isinstance(v, (int, float))}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             save_hlo: bool = False, out_dir: Optional[pathlib.Path] = None
             ) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import SHAPES, TRAIN_MICROBATCHES, cell_status, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch import steps as steps_lib
    from repro.models.model import input_specs
    from repro.optim import adamw

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                 "mode": shape.mode, "seq_len": shape.seq_len,
                 "global_batch": shape.global_batch,
                 "active_params": cfg.active_params(),
                 "total_params": cfg.total_params()}
    skip = cell_status(cfg, shape)
    if skip:
        rec.update(ok=True, skipped=skip)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec["mesh_shape"] = list(mesh.devices.shape)
    t0 = time.time()
    try:
        with mesh:
            if shape.mode == "train":
                micro = TRAIN_MICROBATCHES.get((arch, shape_name), 1)
                rec["microbatches"] = micro
                opt_cfg = adamw.AdamWConfig()
                jitted, (st_shapes, st_sh, b_sh) = steps_lib.jit_train_step(
                    cfg, opt_cfg, mesh,
                    input_specs(cfg, shape.global_batch, shape.seq_len, "train"),
                    microbatches=micro)
                lowered = jitted.lower(
                    st_shapes, input_specs(cfg, shape.global_batch,
                                           shape.seq_len, "train"))
            elif shape.mode == "prefill":
                jitted, (pshapes, p_sh, b_sh) = steps_lib.jit_prefill_step(
                    cfg, mesh,
                    input_specs(cfg, shape.global_batch, shape.seq_len, "prefill"))
                lowered = jitted.lower(
                    pshapes, input_specs(cfg, shape.global_batch,
                                         shape.seq_len, "prefill"))
            else:  # decode
                bshapes = input_specs(cfg, shape.global_batch, shape.seq_len,
                                      "decode")
                jitted, (pshapes, p_sh, b_sh) = steps_lib.jit_serve_step(
                    cfg, None, mesh, bshapes)
                lowered = jitted.lower(pshapes, bshapes)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)

            hlo = compiled.as_text()
            rec["collectives"] = parse_collectives(hlo)
            rec["while_trip_counts"] = while_trip_counts(hlo)[-8:]
            rec["memory"] = memory_analysis_dict(compiled)
            rec["cost"] = cost_analysis_dict(compiled)
            from repro.launch.hlo_analysis import analyze as hlo_analyze
            rec["hlo_stats"] = hlo_analyze(hlo)  # trip-aware per-device cost
            rec["ok"] = True
            if save_hlo and out_dir is not None:
                hpath = out_dir / f"{arch}__{shape_name}__{mesh_kind}.hlo.gz"
                with gzip.open(hpath, "wt") as f:
                    f.write(hlo)
                rec["hlo_path"] = str(hpath)
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
    return rec


def cell_path(out_dir: pathlib.Path, arch: str, shape: str, mesh: str) -> pathlib.Path:
    return out_dir / f"{arch}__{shape}__{mesh}.json"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        from repro.configs import ARCH_MODULES, SHAPES  # light import
        failures = 0
        for arch in ARCH_MODULES:
            for shape in SHAPES:
                for mesh in meshes:
                    p = cell_path(out_dir, arch, shape, mesh)
                    if p.exists() and not args.force:
                        print(f"[cached] {p.name}")
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--mesh", mesh,
                           "--out", str(out_dir)]
                    if args.save_hlo:
                        cmd.append("--save-hlo")
                    print(f"[run] {arch} x {shape} x {mesh}", flush=True)
                    r = subprocess.run(cmd)
                    if r.returncode:
                        failures += 1
        return 1 if failures else 0

    rec = run_cell(args.arch, args.shape, args.mesh if args.mesh != "both" else "single",
                   save_hlo=args.save_hlo, out_dir=out_dir)
    p = cell_path(out_dir, args.arch, args.shape, rec["mesh"])
    p.write_text(json.dumps(rec, indent=2))
    status = "SKIP" if rec.get("skipped") else ("OK" if rec.get("ok") else "FAIL")
    print(f"[{status}] {args.arch} x {args.shape} x {rec['mesh']}  "
          f"lower={rec.get('lower_s')}s compile={rec.get('compile_s')}s")
    if rec.get("error"):
        print("  error:", rec["error"][:500])
    if rec.get("memory"):
        print("  memory:", {k: f"{v/2**30:.2f}GiB" for k, v in rec["memory"].items()
                            if isinstance(v, int) and v > 2**20})
    if rec.get("cost"):
        fl = rec["cost"].get("flops")
        by = rec["cost"].get("bytes accessed")
        print(f"  per-device flops={fl:.3e} bytes={by:.3e}" if fl and by else "")
    if rec.get("collectives"):
        print("  collectives:", rec["collectives"]["bytes"])
    return 0 if rec.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
