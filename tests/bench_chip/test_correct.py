"""What decides ``correct``, at a size a CPU holds: a whole run of the
harness passes, both float8 controls fail the limits, and a run with its
timed path broken underneath comes out not correct.  The look for a chip is
skipped; everything else runs as on the chip.

The limits here are this size's own, set like the cells' (PERF.md) from
``calibrate.py`` on seeds 5, 7, 8 and 2147483999: above what the driver
reads (loss gap <= 8.3e-4, change gap <= 9.6e-4, median-leaf gradient
difference <= 0.0162) and below the controls (e4m3 >= 1.7e-3, 5.1e-3,
0.195; e5m2 >= 7.0e-3, 6.3e-3, 0.375)."""
import dataclasses
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "..", "..", "benchmarks", "chip")
sys.path.insert(0, BENCH)

import bench  # noqa: E402
import calibrate  # noqa: E402

SEEDS = (5, 2147483999)
LIMITS = {"loss_gap": 2e-3, "grad_gap": None, "change_gap": 3e-3,
          "grad_diff": 0.06, "change_diff": None}
COMPARED = [k for k, v in LIMITS.items() if v is not None]
DRIVER = ["--batch", "4", "--seq", "64", "--analyze-every", "2",
          "--schema", "tpu", "--costs", "hlo", "--devices", "1"]
POD = ["--sim-ranks", "256", "--inject-bottleneck-at", "1",
       "--inject-factor", "4"]


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """A tiny rwkv6 configuration and its two cells, and the benchmark
    description that lists them."""
    root = tmp_path_factory.mktemp("bench")
    for sub in ("configs", "workloads"):
        (root / sub).mkdir()
    with open(os.path.join(BENCH, "configs", "rwkv6-3b-l4.json")) as f:
        c = json.load(f)
    d, ff, V, L = 128, 384, 2048, 2
    c.update(name="tiny", n_layers=L, d_model=d, n_heads=d // 64, d_ff=ff,
             vocab_size=V, limits=LIMITS,
             driver_args=["--arch", "rwkv6-3b", "--reduced", "--d-model",
                          str(d), "--layers", str(L)])
    (root / "configs" / "tiny.json").write_text(json.dumps(c))
    (root / "workloads" / "tiny.clean.json").write_text(json.dumps(
        {"config": "tiny", "chips": 1, "driver_args": DRIVER}))
    (root / "workloads" / "tiny.pod.json").write_text(json.dumps(
        {"config": "tiny", "chips": 1, "driver_args": DRIVER + POD,
         "expect": {"stragglers": [255], "diagnosis": "compute",
                    "verdict_mismatch_limit": 0}}))
    with open(os.path.join(BENCH, "..", "..", "BENCHMARK.json")) as f:
        desc = json.load(f)
    desc["workloads"] = [{"name": n, "config": "tiny", "chips": 1}
                         for n in ("tiny.clean", "tiny.pod")]
    for m in desc["end_to_end"] + desc["per_layer"]:
        if "workloads" in m:       # the pod cell's metrics go to tiny.pod
            m["workloads"] = ["tiny.pod" for w in m["workloads"]
                              if "pod" in w]
    return str(root), desc


@pytest.fixture(autouse=True)
def no_chip_look(monkeypatch):
    import jax
    monkeypatch.setattr(bench, "setup_jax",
                        lambda chips: jax.devices()[:chips])


def run(files, cell, seed=SEEDS[0], **kw):
    root, desc = files
    return bench.run_cell(cell, seed, 0.2, False, bench=desc, files=root,
                          peaks={"bf16_flops": 1e12}, log=open(os.devnull, "w"),
                          **kw)


def test_a_whole_run_is_correct(files):
    res = run(files, "tiny.pod")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "step_ms_p95",
                                   "verdict_ms_p90", "setup_s"}
    assert res["checks"]["verdict_mismatch"]["value"] == 0
    assert list(res)[-1] == "checks"


def test_the_controls_fail_where_the_driver_passes(files, monkeypatch,
                                                   capsys):
    root, desc = files
    load = bench.load_cell
    monkeypatch.setattr(bench, "load_cell",
                        lambda name, trace, *a: load(name, trace, desc, root))
    calibrate.main(["--workload", "tiny.clean", "--seeds",
                    *map(str, SEEDS), "--variants", "program", "e4m3",
                    "e5m2"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert len(lines) == len(SEEDS)
    for r in lines:
        assert r["program"]["correct"], r["program"]
        assert all(r["program"][k] <= LIMITS[k] for k in COMPARED)
        for control in ("e4m3", "e5m2"):
            assert not r[control]["correct"], r[control]
            assert r[control]["grad_diff"] > LIMITS["grad_diff"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(files):
    import jax.numpy as jnp
    import jax

    def unchanged(step, state, batch):
        copy = jax.tree_util.tree_map(jnp.copy, state)
        _, metrics = step(copy, batch)
        return state, metrics
    res = run(files, "tiny.clean", fault=unchanged)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] > LIMITS["change_gap"]


def test_half_the_batch_left_out_is_not_correct(files):
    import numpy as np

    def half(step, state, batch):
        # rows 2-3 repeat rows 0-1: the mean is over half of the batch
        n = batch["tokens"].shape[0] // 2
        return step(state, {k: np.concatenate([v[:n], v[:n]])
                            for k, v in batch.items()})
    res = run(files, "tiny.clean", fault=half)
    assert not res["correct"], res["checks"]


def test_an_altered_verdict_is_not_correct(files):
    res = run(files, "tiny.pod", verdict_fault=lambda v: dataclasses.replace(
        v, stragglers=(254,)))
    assert not res["correct"]
    assert res["checks"]["verdict_mismatch"]["value"] > 0
