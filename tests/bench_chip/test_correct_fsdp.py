"""What decides ``correct`` on a four-chip FSDP cell, at a size a CPU
holds: the harness drives a tiny rwkv6 configuration over four host
devices with ``--devices 4 --pod-gather``, as ``rwkv6-3b-fsdp4.clean``
drives rwkv6-3b over four chips, and checks it against the plain reference
split over the same four devices (``configs/rwkv6.py`` ``placement``).

The four devices exist only in a process whose XLA flags ask for them
before jax starts, so the runs happen in one child process (this file run
as a script) and the tests read what it printed.  The limits are the tiny
size's own, those of ``test_correct.py``."""
import contextlib
import io
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "chip")

SEED = 2147483999
LIMITS = {"loss_gap": 2e-3, "grad_gap": None, "change_gap": 3e-3,
          "grad_diff": 0.06, "change_diff": None}
DRIVER = ["--batch", "4", "--seq", "64", "--analyze-every", "2",
          "--schema", "tpu", "--costs", "hlo"]


def write_files(root):
    """A tiny rwkv6 configuration, a one-device and a four-device cell of
    it, and the benchmark description that lists them."""
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(root, sub), exist_ok=True)
    with open(os.path.join(BENCH, "configs", "rwkv6-3b-fsdp4.json")) as f:
        c = json.load(f)
    d, ff, V, L = 128, 384, 2048, 2
    c.update(name="tiny", n_layers=L, d_model=d, n_heads=d // 64, d_ff=ff,
             vocab_size=V, limits=LIMITS,
             driver_args=["--arch", "rwkv6-3b", "--reduced", "--d-model",
                          str(d), "--layers", str(L)])
    with open(os.path.join(root, "configs", "tiny.json"), "w") as f:
        json.dump(c, f)
    cells = {"tiny.one": (1, ["--devices", "1"]),
             "tiny.fsdp4": (4, ["--devices", "4", "--pod-gather"])}
    for name, (chips, extra) in cells.items():
        with open(os.path.join(root, "workloads", name + ".json"), "w") as f:
            json.dump({"config": "tiny", "chips": chips,
                       "driver_args": DRIVER + extra}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        desc = json.load(f)
    desc["workloads"] = [{"name": n, "config": "tiny", "chips": ch}
                         for n, (ch, _) in cells.items()]
    desc["end_to_end"] = [m for m in desc["end_to_end"]
                          if "workloads" not in m]
    return desc


def driver_readings(cell, seed):
    """The driver's first three steps as the benchmark probes them: the
    losses, the first gradient and each leaf's change."""
    import bench
    import drive
    from repro.launch import train
    c = cell.config
    argv = c["driver_args"] + cell.workload["driver_args"]
    watch = drive.StepWatch()
    probes = bench.attach_probes(watch, cell, seed)
    with drive.stamped_stdout(drive.LineClock(open(os.devnull, "w"))), \
            drive.hooks(c, seed, cell.ref, watch):
        res = train.run(argv + ["--steps", str(bench.REFERENCE_STEPS)])
    return {"losses": res.losses[:bench.REFERENCE_STEPS], **probes}


def child(root):
    """Runs in the four-device process; prints one JSON line per result."""
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import bench
    import calibrate
    assert jax.device_count() == 4
    bench.setup_jax = lambda chips: jax.devices()[:chips]
    desc = write_files(root)
    load = bench.load_cell
    bench.load_cell = lambda name, trace, *a: load(name, trace, desc, root)
    devnull = open(os.devnull, "w")
    res = bench.run_cell("tiny.fsdp4", SEED, 0.2, False, bench=desc,
                         files=root, peaks={"bf16_flops": 1e12}, log=devnull)
    print("RUN " + json.dumps(res), flush=True)
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        calibrate.main(["--workload", "tiny.fsdp4", "--seeds", str(SEED),
                        "--variants", "program", "half_batch"])
    print("CALIBRATE " + said.getvalue().splitlines()[-1], flush=True)
    one = driver_readings(bench.load_cell("tiny.one", False), SEED)
    four = driver_readings(bench.load_cell("tiny.fsdp4", False), SEED)
    readings = bench.training_checks(four, one, LIMITS)
    checks, same = bench.judge(readings)
    print("DEVICES " + json.dumps({"checks": checks, "same": same,
                                   "grad_gap": readings["grad_gap"]["value"],
                                   "first_loss": [one["losses"][0],
                                                  four["losses"][0]]}),
          flush=True)


@pytest.fixture(scope="module")
def printed(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_fsdp")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, __file__, str(root)], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return {line.split(" ", 1)[0]: json.loads(line.split(" ", 1)[1])
            for line in out.stdout.splitlines()
            if line.startswith(("RUN ", "CALIBRATE ", "DEVICES "))}


def test_a_whole_four_device_run_is_correct(printed):
    res = printed["RUN"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) >= {"train_tokens_per_s", "step_ms_p95",
                                   "setup_s"}


def test_half_the_batch_left_out_is_not_correct_over_four_devices(printed):
    line = printed["CALIBRATE"]
    assert line["program"]["correct"], line["program"]
    assert not line["half_batch"]["correct"], line["half_batch"]


def test_four_devices_train_as_one_does(printed):
    got = printed["DEVICES"]
    assert got["same"], got["checks"]
    one, four = got["first_loss"]
    assert abs(one - four) <= LIMITS["loss_gap"]
    # every leaf's first-gradient norm, against the norm gaps' limit
    assert got["grad_gap"] <= LIMITS["change_gap"]


if __name__ == "__main__":
    child(sys.argv[1])
