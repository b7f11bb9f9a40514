"""The program's spans and named scopes: which spans the training driver and
the analysis pipeline open, on which thread and how often, and which scopes
reach the compiled step's ``op_name`` metadata."""
import re
import threading

import jax
import pytest

from repro.configs import reduced_config
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_host_mesh
from repro.models.model import input_specs
from repro.optim import adamw

STEPS, EVERY = 6, 2
DRIVER = ["--steps", str(STEPS), "--batch", "4", "--seq", "16",
          "--d-model", "64", "--layers", "2", "--arch", "rwkv6-3b",
          "--analyze-every", str(EVERY), "--schema", "tpu",
          "--costs", "analytic"]
STAGES = ("analysis.external", "analysis.external_root_causes",
          "analysis.internal", "analysis.internal_root_causes",
          "analysis.diagnosis", "analysis.straggler")


@pytest.fixture
def spans(monkeypatch):
    """Every span opened while the fixture is live, as
    (name, thread name, metadata, open order, close order)."""
    log = []
    clock = iter(range(10 ** 9))
    lock = threading.Lock()

    class Recorder:
        def __init__(self, name, **meta):
            self.rec = {"name": name, "thread": threading.current_thread().name,
                        "meta": dict(meta)}

        def set_metadata(self, **meta):
            self.rec["meta"].update(meta)

        def __enter__(self):
            with lock:
                self.rec["open"] = next(clock)
                log.append(self.rec)
            return self

        def __exit__(self, *exc):
            with lock:
                self.rec["close"] = next(clock)

    class StepRecorder(Recorder):
        pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", StepRecorder)
    return log


def named(log, name):
    return [r for r in log if r["name"] == name]


def inside(inner, outer):
    return outer["open"] < inner["open"] and inner["close"] < outer["close"]


@pytest.mark.parametrize("pod", [["--sim-ranks", "32"],
                                 ["--data-hosts", "2"], []],
                         ids=["sim-pod", "partitioned", "one-rank"])
def test_the_driver_opens_its_spans_on_their_threads(spans, pod):
    from repro.launch.train import run
    res = run(DRIVER + pod)
    assert res.windows_analyzed == STEPS // EVERY
    main = threading.main_thread().name
    steps = named(spans, "train")
    assert [r["meta"]["step_num"] for r in steps] == list(range(STEPS))
    assert {r["thread"] for r in steps} == {main}
    for region in ("region.data", "region.step", "region.checkpoint"):
        got = named(spans, region)
        assert len(got) == STEPS and {r["thread"] for r in got} == {main}
        assert all(inside(r, s) for r, s in zip(got, steps))
    # one recording span per region exit and program exit, whatever the
    # number of ranks it records
    records = named(spans, "perfdbg.record")
    assert len(records) == 4 * STEPS
    assert {r["thread"] for r in records} == {main}
    flushes = named(spans, "perfdbg.flush")
    assert [r["meta"]["submission"] for r in flushes] == \
        list(range(STEPS // EVERY))
    assert {r["thread"] for r in flushes} == {main}
    assert all(any(inside(f, s) for s in steps) for f in flushes)
    windows = named(spans, "analysis.window")
    assert sorted(r["meta"]["submission"] for r in windows) == \
        list(range(STEPS // EVERY))
    assert all(r["thread"].startswith("perfdbg-analysis-") for r in windows)
    for stage in STAGES:
        got = named(spans, stage)
        assert len(got) == STEPS // EVERY, stage
        assert all(any(inside(r, w) for w in windows) for r in got), stage


def test_the_sync_path_analyzes_inside_the_flush(spans):
    from repro.launch.train import run
    run(DRIVER + ["--sync-analysis"])
    flushes = named(spans, "perfdbg.flush")
    windows = named(spans, "analysis.window")
    assert [w["meta"]["submission"] for w in windows] == \
        [f["meta"]["submission"] for f in flushes] == \
        list(range(STEPS // EVERY))
    assert all(inside(w, f) for w, f in zip(windows, flushes))
    assert {w["thread"] for w in windows} == {threading.main_thread().name}


def test_the_pooled_path_spans_preparation_and_assembly(spans):
    from repro.launch.train import run
    run(DRIVER + ["--sim-ranks", "8", "--analysis-workers", "2"])
    n = STEPS // EVERY
    for name in ("analysis.window", "analysis.assemble"):
        got = named(spans, name)
        assert sorted(r["meta"]["submission"] for r in got) == list(range(n))
        assert all(r["thread"].startswith("perfdbg-analysis-") for r in got)
    assert len(named(spans, "analysis.diagnosis")) == n


def test_no_span_is_opened_for_a_process_that_never_imported_jax():
    import subprocess
    import sys
    code = ("import sys; from repro.core.spans import span\n"
            "with span('analysis.window', submission=1) as s:\n"
            "    s.set_metadata(submission=2)\n"
            "assert 'jax' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_the_compiled_step_carries_the_region_scopes():
    """Scopes reach the optimized HLO's op_name metadata, forward and
    backward (``transpose(jvp(loss))``), for every named region."""
    cfg = reduced_config("rwkv6-3b", d_model=64, n_heads=1, n_kv_heads=1,
                         d_ff=128, vocab_size=256, n_layers=2)
    mesh = make_host_mesh(n_devices=1)
    bshapes = input_specs(cfg, 2, 32, "train")
    with mesh:
        jitted, (st_shapes, _, _) = steps_lib.jit_train_step(
            cfg, adamw.AdamWConfig(), mesh, bshapes)
        text = jitted.lower(st_shapes, bshapes).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("embed", "layers", "mix", "wkv", "ffn", "loss",
                  "optimizer"):
        rx = re.compile(rf"(^|[/(]){scope}($|[/)])")
        assert any(rx.search(p) for p in paths), scope
        assert any(rx.search(p) and "transpose" in p for p in paths) or \
            scope == "optimizer", scope
    # the WKV recurrence sits inside the time-mix
    assert any("/mix/wkv/" in p for p in paths)


def test_a_cached_step_keeps_its_own_scopes(tmp_path):
    """An executable found in the persistent compile cache carries the
    metadata of the code that asked for it: a program traced without the
    scopes does not get one compiled with them, nor the other way round."""
    import os
    import subprocess
    import sys
    code = ("import contextlib, sys, jax, jax.numpy as jnp\n"
            "from repro.launch import steps\n"
            "steps.use_compile_cache()\n"
            "if sys.argv[1] == 'plain':\n"
            "    jax.named_scope = lambda name: contextlib.nullcontext()\n"
            "def f(x):\n"
            "    with jax.named_scope('wkv'):\n"
            "        return jnp.sin(x) * 2\n"
            "print('/wkv/' in jax.jit(f).lower(jnp.ones(8)).compile()"
            ".as_text())\n")
    env = dict(os.environ, PYTHONPATH="src",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    said = [subprocess.run([sys.executable, "-c", code, kind], env=env,
                           capture_output=True, text=True, timeout=300,
                           check=True).stdout.split()
            for kind in ("scoped", "plain", "scoped")]
    assert any(tmp_path.iterdir())
    assert said == [["True"], ["False"], ["True"]]


def test_the_step_span_carries_the_compiled_collective_bytes():
    """On a two-device FSDP mesh every ``train`` span carries one chip's
    collective bytes of the compiled step and the layer stack's remat split,
    as the ``[costs]`` line prints them.  Subprocess: the device count is
    set before jax starts."""
    import json
    import os
    import subprocess
    import sys
    code = ("import json, jax\n"
            "seen = []\n"
            "class Step:\n"
            "    def __init__(self, name, **meta): seen.append(meta)\n"
            "    def __enter__(self): return self\n"
            "    def __exit__(self, *exc): return None\n"
            "jax.profiler.StepTraceAnnotation = Step\n"
            "from repro.launch.train import run\n"
            "run(sys_argv)\n"
            "print('SPANS ' + json.dumps(seen))\n")
    assert DRIVER[-2:] == ["--costs", "analytic"]
    argv = DRIVER[:-1] + ["hlo", "--devices", "2"]
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run(
        [sys.executable, "-c", f"sys_argv = {argv!r}\n" + code], env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    costs = re.search(r"^\[costs\] hlo step:.* collective_bytes=(\S+)",
                      out.stdout, re.M)
    spans = json.loads(out.stdout.split("SPANS ", 1)[1].splitlines()[0])
    assert [s["step_num"] for s in spans] == list(range(STEPS))
    got = {s["collective_bytes"] for s in spans}
    assert len(got) == 1 and got.pop() > 0
    assert spans[0]["collective_bytes"] == pytest.approx(
        float(costs.group(1)), rel=1e-3)
    remat = re.search(r"^\[costs\] hlo step:.* remat=(\S+)$", out.stdout,
                      re.M)
    assert remat.group(1) == "2x1"
    assert {s["remat"] for s in spans} == {"2x1"}
