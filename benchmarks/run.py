"""Benchmark harness — one section per paper table/figure plus the dry-run /
roofline reports.  Prints ``name,us_per_call,derived`` CSV rows; ``--json``
additionally writes them as ``{name: {"us_per_call": ..., "derived": ...}}``
plus a ``_meta`` entry naming the result schema and the analysis collapse
mode the rows were measured under (the scaling sweep in
``benchmarks/analysis_scale.py`` emits the flat ``BENCH_6.json`` the CI
perf-smoke job diffs, with the same ``_meta`` convention).

    PYTHONPATH=src python -m benchmarks.run [--st-scale 1.0] [--skip-kernels]
                                           [--json out.json]
"""
import argparse
import json
import pathlib
import sys
import time

import numpy as np

RESULTS = pathlib.Path(__file__).resolve().parent / "results"

ROWS = {}   # name -> {"us_per_call": float, "derived": str}


def row(name: str, us: float, derived: str = "") -> None:
    ROWS[name] = {"us_per_call": round(us, 1), "derived": derived}
    print(f"{name},{us:.1f},{derived}")


# ---------------------------------------------------------------------------
# Paper §5.1 — ST (Figs 9-15, Tables 2-3)
# ---------------------------------------------------------------------------

def bench_st(scale: float) -> None:
    from repro.perfdbg.workloads.st import STWorkload, run_st, st_region_tree
    tree = st_region_tree()
    t0w = time.perf_counter()
    rec, rep, t_orig = run_st(STWorkload(scale=scale))
    analysis_us = (time.perf_counter() - t0w) * 1e6
    taus = run_st.last_taus
    kinds = rep.external.clustering.clusters
    fig9_ok = kinds == ((0,), (1, 2), (3,), (4, 6), (5, 7))
    row("st_fig9_similarity", analysis_us,
        f"kinds={len(kinds)} paper_exact={fig9_ok} S={rep.external.severity:.4f}"
        f" (paper 0.783958)")
    row("st_fig9_ccr_chain", 0,
        f"CCCR={rep.external.cccrs} via 14 (paper: region 11 via region 14)")
    row("st_table2_ext_core", 0,
        f"core={rep.external_root_causes.core.cores} (paper {{a5}})")
    row("st_fig13_internal", 0,
        f"CCCRs={rep.internal.cccrs} (paper {{8,11}})")
    row("st_table3_int_core", 0,
        f"core={rep.internal_root_causes.core.cores} (paper {{a2,a3}})")

    variants = [("external_fixed", dict(balance_region11=True), 40),
                ("internal_fixed", dict(optimize_locality=True,
                                        buffer_io=True), 90),
                ("both_fixed", dict(balance_region11=True,
                                    optimize_locality=True,
                                    buffer_io=True), 170)]
    for name, kw, paper in variants:
        rec_v, rep_v, t_v = run_st(STWorkload(scale=scale, taus=taus, **kw))
        cost = rec_v.measurements().wall_time.sum(axis=1).max()
        cost0 = rec.measurements().wall_time.sum(axis=1).max()
        speedup = (cost0 / cost - 1) * 100
        row(f"st_fig15_{name}", t_v * 1e6,
            f"speedup=+{speedup:.0f}% (paper +{paper}%) "
            f"S={rep_v.external.severity:.4f}")


# ---------------------------------------------------------------------------
# Paper §5.2 — NPAR1WAY (Figs 16-19)
# ---------------------------------------------------------------------------

def bench_npar1way(scale: float) -> None:
    from repro.perfdbg.workloads.npar1way import (NPAR1WAYWorkload,
                                                  npar1way_region_tree,
                                                  run_npar1way)
    t0 = time.perf_counter()
    rec, rep, t_orig = run_npar1way(NPAR1WAYWorkload(scale=scale))
    us = (time.perf_counter() - t0) * 1e6
    taus = run_npar1way.last_taus
    row("npar_fig16_similarity", us,
        f"clusters={rep.external.clustering.n_clusters} (paper 1)")
    row("npar_fig18_internal", 0,
        f"CCCRs={rep.internal.cccrs} (paper {{3,12}})")
    row("npar_core", 0,
        f"core={rep.internal_root_causes.core.cores} (paper {{a4,a5}})")
    rec_o, _, t_opt = run_npar1way(
        NPAR1WAYWorkload(scale=scale, eliminate_redundancy=True, taus=taus))
    cost = lambda r: r.measurements().wall_time.sum(axis=1).max()
    speedup = (cost(rec) / cost(rec_o) - 1) * 100
    ids = list(npar1way_region_tree().ids())
    i3, i12 = ids.index(3), ids.index(12)
    d3 = (1 - rec_o.measurements().instructions[0, i3]
          / rec.measurements().instructions[0, i3]) * 100
    d12 = (1 - rec_o.measurements().instructions[0, i12]
           / rec.measurements().instructions[0, i12]) * 100
    row("npar_fig19_optimized", t_opt * 1e6,
        f"speedup=+{speedup:.0f}% (paper +20%); instr r3 -{d3:.1f}% "
        f"(paper -36.3%) r12 -{d12:.1f}% (paper -16.9%)")


# ---------------------------------------------------------------------------
# Lightweight-data claim (125*n*m bytes) + analysis scalability
# ---------------------------------------------------------------------------

def bench_overhead() -> None:
    from repro.core import RegionTree
    from repro.perfdbg import RegionRecorder, PAPER_BYTES_PER_CELL
    tree = RegionTree()
    for i in range(1, 15):
        tree.add(f"r{i}", rid=i)
    for m in (8, 256, 4096):
        rec = RegionRecorder(tree, m)
        budget = PAPER_BYTES_PER_CELL * 14 * m
        row(f"recorder_footprint_m{m}", 0,
            f"{rec.packed_size()}B of {budget}B budget "
            f"({rec.packed_size()/budget:.0%})")
    # analysis wall time at pod scale (the lightweight claim is what makes
    # per-shard collection feasible at 4k ranks)
    from repro.core import analyze_external
    rng = np.random.default_rng(0)
    for m in (8, 256, 1024):
        perf = np.tile(rng.uniform(5, 10, 14), (m, 1))
        perf[: m // 8, 3] *= 3.0
        t0 = time.perf_counter()
        analyze_external(tree, perf)
        row(f"external_analysis_m{m}", (time.perf_counter() - t0) * 1e6, "")


# ---------------------------------------------------------------------------
# Core algorithm micro-benchmarks
# ---------------------------------------------------------------------------

def bench_core() -> None:
    from repro.core import cluster, kmeans_1d, extract_core, DecisionTable
    rng = np.random.default_rng(0)
    perf = rng.uniform(0, 10, (64, 14))
    t0 = time.perf_counter()
    for _ in range(20):
        cluster(perf)
    row("optics_cluster_64x14", (time.perf_counter() - t0) / 20 * 1e6, "")
    vals = rng.uniform(0, 5, 200)
    t0 = time.perf_counter()
    for _ in range(20):
        kmeans_1d(vals)
    row("kmeans_exact_n200_k5", (time.perf_counter() - t0) / 20 * 1e6, "")
    tbl = DecisionTable.build(
        tuple(f"a{i}" for i in range(5)),
        [tuple(rng.integers(0, 2, 5)) for _ in range(24)],
        list(rng.integers(0, 2, 24)))
    t0 = time.perf_counter()
    for _ in range(20):
        extract_core(tbl)
    row("roughset_core_24x5", (time.perf_counter() - t0) / 20 * 1e6, "")


# ---------------------------------------------------------------------------
# Pallas kernels (interpret mode: correctness + analytic traffic)
# ---------------------------------------------------------------------------

def bench_kernels() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    B, S, dh = 2, 256, 64
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B * 4, S, dh), jnp.float32)
    t0 = time.perf_counter()
    got = ops.flash_attention(q, q, q, causal=True, block_q=64, block_k=64,
                              interpret=True)
    us = (time.perf_counter() - t0) * 1e6
    want = ref.flash_attention_ref(q, q, q, causal=True)
    err = float(jnp.max(jnp.abs(got - want)))
    # analytic HBM traffic: kernel streams q,k,v once + writes o
    naive = (S * S * 4 + 3 * S * dh * 4) * B * 4       # score matrix via HBM
    kern = 4 * S * dh * 4 * B * 4                      # q,k,v,o only
    row("flash_attention_256", us,
        f"maxerr={err:.2e}; HBM bytes {kern:.2e} vs naive {naive:.2e} "
        f"({naive/kern:.0f}x less traffic)")
    a = jax.random.uniform(key, (2, 256, 128), jnp.float32, 0.2, 0.99)
    b = jax.random.normal(key, (2, 256, 128), jnp.float32)
    t0 = time.perf_counter()
    h = ops.rglru_scan(a, b, interpret=True)
    us = (time.perf_counter() - t0) * 1e6
    err = float(jnp.max(jnp.abs(h - ref.rglru_scan_ref(a, b))))
    row("rglru_scan_256", us, f"maxerr={err:.2e}; 1 pass vs ~2log2(S) passes")
    from repro.models.rwkv6 import wkv6_sequential
    r = 0.5 * jax.random.normal(key, (1, 128, 2, 64), jnp.float32)
    lw = -jnp.exp(jnp.clip(r, -3, 0.5))
    u = jnp.zeros((2, 64))
    t0 = time.perf_counter()
    y, _ = ops.wkv6(r, r, r, lw, u, interpret=True)
    us = (time.perf_counter() - t0) * 1e6
    want, _ = wkv6_sequential(r, r, r, lw, u)
    err = float(jnp.max(jnp.abs(y - want)))
    row("wkv6_128", us, f"maxerr={err:.2e}")


# ---------------------------------------------------------------------------
# Dry-run + roofline reports (read cached sweep results)
# ---------------------------------------------------------------------------

def bench_dryrun() -> None:
    d = RESULTS / "dryrun"
    if not d.exists():
        row("dryrun", 0, "no cached results; run repro.launch.dryrun --all")
        return
    ok = fail = skip = 0
    worst = (0.0, "")
    for p in sorted(d.glob("*.json")):
        r = json.loads(p.read_text())
        if r.get("skipped"):
            skip += 1
        elif r.get("ok"):
            ok += 1
            t = r.get("memory", {}).get("temp_size_in_bytes", 0) or 0
            if t > worst[0]:
                worst = (t, f"{r['arch']}/{r['shape']}/{r['mesh']}")
        else:
            fail += 1
        if r.get("ok") and not r.get("skipped"):
            row(f"dryrun_{r['arch']}_{r['shape']}_{r['mesh']}",
                (r.get("compile_s") or 0) * 1e6,
                f"temp={r.get('memory', {}).get('temp_size_in_bytes', 0)/2**30:.2f}GiB")
    row("dryrun_summary", 0,
        f"ok={ok} skip={skip} fail={fail}; worst temp {worst[0]/2**30:.1f}GiB"
        f" ({worst[1]})")


def bench_roofline() -> None:
    from repro.launch.roofline import build_table
    d = RESULTS / "dryrun"
    if not d.exists():
        row("roofline", 0, "no cached dry-run results")
        return
    rows = build_table(d)
    (RESULTS / "roofline.json").write_text(json.dumps(rows, indent=2))
    for r in rows:
        if r.get("skipped") or r.get("mesh") != "single":
            continue
        row(f"roofline_{r['arch']}_{r['shape']}", 0,
            f"dom={r['dominant']} compute={r['compute_s']:.3f}s "
            f"mem={r['memory_s']:.3f}s coll={r['collective_s']:.3f}s "
            f"useful={r['useful_ratio']:.3f} frac={r['roofline_fraction']:.3f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--st-scale", type=float, default=1.0)
    ap.add_argument("--skip-kernels", action="store_true")
    ap.add_argument("--json", type=pathlib.Path, default=None,
                    help="also write the rows to this JSON file")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    bench_st(args.st_scale)
    bench_npar1way(args.st_scale)
    bench_overhead()
    bench_core()
    if not args.skip_kernels:
        bench_kernels()
    bench_dryrun()
    bench_roofline()
    if args.json is not None:
        from repro.core import COLLAPSE_AUTO
        out = dict(ROWS)
        out["_meta"] = {"schema": "benchmarks/run/rows/v1",
                        "collapse": COLLAPSE_AUTO}
        args.json.write_text(json.dumps(out, indent=2, sort_keys=True) + "\n")
        print(f"# wrote {len(ROWS)} rows to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
