"""Readings that the limits of ``correct`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        [--variants program highest e4m3 e5m2 half_batch]

For each seed, in one process on the chip, the plain reference's first
three steps and those of each variant, at the cell's sizes:

  * ``program``: the driver (the timed path) as the benchmark runs it;
  * ``highest``: the driver under ``jax.default_matmul_precision("highest")``,
    which runs its float32 matrix products in full float32 (a witness for
    where the program's gaps come from);
  * ``e4m3``, ``e5m2``: the controls, the reference computed in float8 in
    the program's place;
  * ``half_batch``: a planted fault, the reference with half of each batch
    left out.

Prints one JSON line per seed: for each variant every number as
``bench.py`` compares it, whether ``bench.py`` would call it correct under
the configuration's limits, the worst leaf of ``grad_gap`` and each leaf's
``grad_diff``.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench  # noqa: E402
import drive  # noqa: E402

VARIANTS = ("program", "highest", "e4m3", "e5m2", "half_batch")


def readings_of(got, ref, limits):
    checks = bench.training_checks(got, ref, limits)
    _, correct = bench.judge(checks)
    g, p = ref["grad"]["norms"], got["grad"]["norms"]
    med = sorted(g.values())[len(g) // 2]
    out = {k: v["value"] for k, v in checks.items()}
    out["correct"] = correct
    out["worst_grad_gap"] = max(g, key=lambda k: abs(p[k] - g[k])
                                / max(g[k], med))
    out["grad_diffs"] = {k: round(v, 5) for k, v in bench.leaf_diffs(
        got["grad"]["samples"], ref["grad"]["samples"], list(g)).items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", choices=VARIANTS,
                    default=list(VARIANTS))
    a = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = bench.load_cell(a.workload, False)
    try:
        devs = bench.setup_jax(cell.entry["chips"])
    except bench.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    import jax
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    from repro.launch import train
    c = cell.config
    argv_ = c["driver_args"] + cell.workload["driver_args"]
    args = train.build_parser().parse_args(argv_)
    n = bench.REFERENCE_STEPS
    train_ref = lambda seed, **kw: cell.ref.train_readings(
        c, seed, args.batch, args.seq, n, n, devices=devs, **kw)

    def driver(seed, precision):
        watch = drive.StepWatch()
        probes = bench.attach_probes(watch, cell, seed)
        ctx = (jax.default_matmul_precision(precision) if precision
               else contextlib.nullcontext())
        with drive.stamped_stdout(drive.LineClock(sys.stderr)), \
                drive.hooks(c, seed, cell.ref, watch), ctx:
            res = train.run(argv_ + ["--steps", str(n)])
        got = {"losses": res.losses[:n], **probes}
        del res
        gc.collect()
        return got

    for seed in a.seeds:
        got = {}
        for v in a.variants:
            if v in ("program", "highest"):
                got[v] = driver(seed, "highest" if v == "highest" else None)
        ref = train_ref(seed)
        for v in a.variants:
            if v in ("e4m3", "e5m2"):
                got[v] = train_ref(seed, precision=v)
            elif v == "half_batch":
                got[v] = train_ref(seed, half_batch=True)
        line = {"seed": seed, "reference_losses": ref["losses"]}
        for v in a.variants:
            line[v] = readings_of(got[v], ref, c["limits"])
            line[v]["losses"] = got[v]["losses"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
