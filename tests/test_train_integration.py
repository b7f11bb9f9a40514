"""Integration tests: sharded train/serve steps on the host mesh, the
training driver loop, and mixed-precision optimizer state."""
import dataclasses
import json
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.integration

from repro.configs import reduced_config
from repro.data.pipeline import SyntheticTokens
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_host_mesh
from repro.models.model import input_specs
from repro.optim import adamw


def _train_setup(arch="yi-34b", batch=2, seq=32, **overrides):
    cfg = reduced_config(arch, **overrides)
    mesh = make_host_mesh()
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=2, decay_steps=50)
    bshapes = input_specs(cfg, batch, seq, "train")
    with mesh:
        jitted, (st_shapes, st_sh, b_sh) = steps_lib.jit_train_step(
            cfg, opt_cfg, mesh, bshapes, microbatches=1)
    state = steps_lib.init_state(cfg, opt_cfg)
    return cfg, mesh, jitted, state


class TestTrainStep:
    def test_loss_decreases_on_repeated_batch(self):
        cfg, mesh, jitted, state = _train_setup()
        data = SyntheticTokens(cfg.vocab_size, 2, 32, seed=0)
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
        losses = []
        with mesh:
            for _ in range(8):
                state, metrics = jitted(state, batch)
                losses.append(float(metrics["loss"]))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]  # memorizes a repeated batch

    def test_microbatched_matches_full_grad_direction(self):
        cfg = reduced_config("yi-34b")
        mesh = make_host_mesh()
        opt_cfg = adamw.AdamWConfig()
        bshapes = input_specs(cfg, 4, 32, "train")
        with mesh:
            j1, (st_shapes, *_rest) = steps_lib.jit_train_step(
                cfg, opt_cfg, mesh, bshapes, microbatches=1)
            j2, _ = steps_lib.jit_train_step(cfg, opt_cfg, mesh, bshapes,
                                             microbatches=2)
        data = SyntheticTokens(cfg.vocab_size, 4, 32, seed=1)
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
        s1 = steps_lib.init_state(cfg, opt_cfg)
        s2 = jax.tree_util.tree_map(jnp.copy, s1)
        with mesh:
            _, m1 = j1(s1, batch)
            _, m2 = j2(s2, batch)
        # same data, same params: loss identical, grad norm close
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-2)
        assert float(m1["grad_norm"]) == pytest.approx(
            float(m2["grad_norm"]), rel=5e-2)

    def test_mixed_precision_state_has_master(self):
        cfg = reduced_config("mixtral-8x7b")  # param_dtype=bfloat16
        assert cfg.param_dtype == "bfloat16"
        opt_cfg = adamw.AdamWConfig()
        state = steps_lib.init_state(cfg, opt_cfg)
        assert "master" in state["opt"]
        p_leaf = jax.tree_util.tree_leaves(state["params"])[0]
        m_leaf = jax.tree_util.tree_leaves(state["opt"]["master"])[0]
        assert p_leaf.dtype == jnp.bfloat16
        assert m_leaf.dtype == jnp.float32

    def test_mixed_precision_trains(self):
        cfg, mesh, jitted, state = _train_setup("mixtral-8x7b")
        data = SyntheticTokens(cfg.vocab_size, 2, 32, seed=0)
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
        with mesh:
            for _ in range(6):
                state, metrics = jitted(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        # master stays fp32 and moves with updates
        m_leaf = jax.tree_util.tree_leaves(state["opt"]["master"])[0]
        assert m_leaf.dtype == jnp.float32


class TestServeStep:
    def test_serve_step_runs_and_updates_cache(self):
        cfg = reduced_config("yi-34b")
        mesh = make_host_mesh()
        s_buf = 16
        bshapes = input_specs(cfg, 2, s_buf, "decode")
        with mesh:
            jitted, (pshapes, p_sh, b_sh) = steps_lib.jit_serve_step(
                cfg, None, mesh, bshapes)
        from repro.models.model import init_params
        from repro.models.transformer import init_cache
        params = init_params(cfg, 0)
        batch = {"tokens": jnp.zeros((2, 1), jnp.int32),
                 "pos": jnp.asarray(0, jnp.int32),
                 "cache": init_cache(cfg, 2, s_buf)}
        with mesh:
            out = jitted(params, batch)
        assert out["logits"].shape == (2, 1, cfg.vocab_size)
        # the cache received the new KV at position 0
        k0 = jax.tree_util.tree_leaves(out["cache"])[0]
        assert bool(jnp.any(k0 != 0))

    def test_serve_rules_replicate_small_models(self):
        cfg = reduced_config("yi-34b")
        mesh = make_host_mesh()
        rules = steps_lib.serve_rules(cfg, mesh)
        assert rules is not None and rules["embed"] == ()


class TestDriver:
    def test_train_main_smoke(self, tmp_path):
        from repro.launch.train import main
        rc = main(["--arch", "yi-34b", "--steps", "4", "--batch", "2",
                   "--seq", "32", "--d-model", "128",
                   "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
                   "--analyze-every", "2"])
        assert rc == 0
        from repro.ckpt import checkpoint as ckpt
        assert ckpt.latest_step(tmp_path) == 4

    def test_tpu_schema_hlo_costs_end_to_end(self):
        """--schema tpu --costs hlo on a 2-device host platform: the
        recorded hlo_flops / collective_bytes must be HLO-measured and
        nonzero (the all-reduces of the sharded grad sync).  Subprocess:
        the forced device count must be set before jax initializes."""
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=2",
                   PYTHONPATH="src")
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.train", "--steps", "4",
             "--batch", "2", "--seq", "32", "--d-model", "128",
             "--analyze-every", "2", "--schema", "tpu", "--costs", "hlo"],
            capture_output=True, text=True, env=env, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]

        def val(line_tag, key):
            m = re.search(rf"\[{line_tag}\][^\n]*\b{key}=([\d.e+-]+)",
                          out.stdout)
            assert m, f"no {key} on the [{line_tag}] line:\n{out.stdout}"
            return float(m.group(1))

        # provider-advertised (compiled-module) costs...
        assert val("costs", "hlo_flops") > 0
        assert val("costs", "collective_bytes") > 0
        # ...and the attributes actually recorded into the tpu schema
        assert val("report", "hlo_flops") > 0
        assert val("report", "collective_bytes") > 0
        assert "coverage" in out.stdout

    def test_reshard_actuation_repartitions_sim_shards(self, capsys):
        """The reshard demo's closed loop: a skewed simulated partition
        (rank 0 handed 3x the tokens) drives the external core to the work
        attribute, ReshardPolicy fires, and the driver repartitions the
        shard-size vector back to uniform — after which the straggler
        verdict clears."""
        from repro.launch.train import main
        rc = main(["--steps", "12", "--batch", "2", "--seq", "32",
                   "--d-model", "128", "--analyze-every", "2",
                   "--sim-ranks", "4", "--sim-shard-skew", "3.0",
                   "--policies", "reshard", "--policy-window-k", "2",
                   "--schema", "tpu", "--costs", "analytic"])
        out = capsys.readouterr().out
        assert rc == 0
        assert re.search(r"simulated pod: 4 ranks, shards \[32, 11, 11, 11\]",
                         out)
        m = re.search(r"applied reshard from window (\d+) \(work attr "
                      r"'hlo_flops'\): shards -> uniform \[16, 16, 16, 16\]",
                      out)
        assert m, f"reshard never actuated:\n{out}"
        # severity collapses once the partition is uniform again
        post = [float(s) for s in
                re.findall(r"S=([\d.]+)", out.split("applied reshard")[1])]
        assert post and post[-1] < 0.2

    def test_rebalance_actuation_repartitions_real_pipeline(self, capsys):
        """A slow host in the REAL partitioned pipeline: the rebalance
        policy's 1/cpu-time weights are applied to the live pipeline via
        set_partition — the [actuate] audit line fires and the slow host's
        weight drops below uniform."""
        from repro.launch.train import main
        rc = main(["--steps", "16", "--batch", "8", "--seq", "32",
                   "--d-model", "128", "--analyze-every", "2",
                   "--data-hosts", "4", "--inject-bottleneck-at", "3",
                   "--policies", "rebalance", "--policy-window-k", "2"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "[train] partitioned pipeline: 4 hosts" in out
        m = re.search(r"\[actuate\] rebalance/rebalance @w(\d+) "
                      r"evidence=\[[\d, ]+\]: pipeline partition "
                      r"(\[[\d., ]+\]) -> (\[[\d., ]+\]) "
                      r"\(rows (\[[\d, ]+\])/batch\)", out)
        assert m, f"rebalance never actuated the pipeline:\n{out}"
        before = json.loads(m.group(2))
        after = json.loads(m.group(3))
        assert before == [0.25, 0.25, 0.25, 0.25]
        assert after[3] < 0.25          # the injected-slow host reads less
        assert sum(json.loads(m.group(4))) == 8   # rows still cover the batch

    def test_reshard_actuation_2host_fire_repartition_restore(self, tmp_path):
        """ISSUE 7's end-to-end proof, as a 2-device subprocess: inject a
        3:1 skewed partition -> straggler verdict fires the reshard policy
        -> the LIVE pipeline repartitions to uniform -> severity collapses
        and the pod rate improves -- then a restart restores the *actuated*
        partition (not the --data-skew flag default) and stays clean."""
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=2",
                   PYTHONPATH="src")
        base = [sys.executable, "-m", "repro.launch.train",
                "--batch", "8", "--seq", "32", "--d-model", "128",
                "--analyze-every", "2", "--policy-window-k", "2",
                "--data-hosts", "2", "--data-skew", "3",
                "--policies", "reshard", "--ckpt-dir", str(tmp_path),
                "--ckpt-every", "4"]
        out = subprocess.run(base + ["--steps", "16"], capture_output=True,
                             text=True, env=env, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        log = out.stdout
        assert re.search(r"partitioned pipeline: 2 hosts, weights "
                         r"\[0\.75, 0\.25\], rows \[6, 2\]/batch", log)
        fire = re.search(r"\[actuate\] reshard/reshard @w\d+ "
                         r"evidence=\[[\d, ]+\]: pipeline partition "
                         r"\[0\.75, 0\.25\] -> \[0\.5, 0\.5\] "
                         r"\(rows \[4, 4\]/batch\)", log)
        assert fire, f"reshard never actuated:\n{log}"
        sev = [float(s) for s in re.findall(r"S=([\d.]+)", log)]
        pre = [float(s) for s in
               re.findall(r"S=([\d.]+)", log[:fire.start()])]
        assert pre and pre[0] > 0.5      # the injected skew is visible
        assert sev[-1] < 0.15            # below SEVERITY_ALERT post-fire
        # the before/after pod-rate assertion the CI job greps for
        m = re.search(r"pod rate pre-fire ([\d,]+) tok/s \(window \d+\) -> "
                      r"post ([\d,]+) tok/s: improved", log)
        assert m, f"no pod-rate improvement verdict:\n{log}"
        assert int(m.group(2).replace(",", "")) > \
            int(m.group(1).replace(",", ""))

        # kill/restore: the resumed run must come back with the ACTUATED
        # uniform partition and never re-fire
        out2 = subprocess.run(base + ["--steps", "24", "--resume"],
                              capture_output=True, text=True, env=env,
                              timeout=600)
        assert out2.returncode == 0, out2.stderr[-2000:]
        assert "data partition restored: [0.5, 0.5]" in out2.stdout
        assert "[actuate]" not in out2.stdout
        sev2 = [float(s) for s in re.findall(r"S=([\d.]+)", out2.stdout)]
        assert sev2 and max(sev2) < 0.15

    def test_train_resume(self, tmp_path):
        from repro.launch.train import main
        main(["--arch", "yi-34b", "--steps", "3", "--batch", "2",
              "--seq", "32", "--d-model", "128", "--ckpt-dir", str(tmp_path)])
        rc = main(["--arch", "yi-34b", "--steps", "5", "--batch", "2",
                   "--seq", "32", "--d-model", "128",
                   "--ckpt-dir", str(tmp_path), "--resume"])
        assert rc == 0
        from repro.ckpt import checkpoint as ckpt
        assert ckpt.latest_step(tmp_path) == 5


class TestPublishedWidth:
    """``--no-reduced`` reaches the published config; ``--layers`` cuts only
    its depth, in whole block_pattern periods."""

    def _cfg(self, argv):
        from repro.launch.train import build_parser, model_config
        return model_config(build_parser().parse_args(argv))

    def test_reduced_stays_the_default(self):
        cfg = self._cfg(["--arch", "rwkv6-3b", "--d-model", "128"])
        assert cfg.d_model == 128 and cfg.vocab_size == 2048

    @pytest.mark.parametrize("arch,layers,want", [
        ("rwkv6-3b", 4, 4),               # one-block period
        ("recurrentgemma-9b", 5, 3),      # (rec, rec, attn): whole periods
        ("recurrentgemma-9b", 1, 3),      # never below one period
        ("yi-34b", 0, 60),                # no cut
    ])
    def test_no_reduced_cuts_depth_only(self, arch, layers, want, capsys):
        from repro.configs import get_config
        full = get_config(arch)
        cfg = self._cfg(["--arch", arch, "--no-reduced", "--d-model", "128",
                         "--layers", str(layers)])
        assert cfg.n_layers == want
        assert cfg == dataclasses.replace(full, n_layers=want)
        if want != full.n_layers:
            assert (f"reduced: n_layers {full.n_layers}→{want}"
                    in capsys.readouterr().out)

    def test_run_returns_what_it_measured(self):
        from repro.launch.train import run
        res = run(["--arch", "rwkv6-3b", "--steps", "4", "--batch", "2",
                   "--seq", "32", "--d-model", "128", "--analyze-every", "2",
                   "--schema", "tpu", "--costs", "hlo"])
        assert len(res.losses) == 4 and np.all(np.isfinite(res.losses))
        assert res.windows_analyzed == 2
        assert res.step_attrs["hlo_flops"] > 0
        assert res.coverage.startswith("step:")
        assert res.compile_s > 0 and res.median_step_ms > 0
        assert len(res.bytes_in_use) == len(jax.devices())

    def test_sharded_init_matches_eager_init(self):
        cfg = reduced_config("rwkv6-3b")
        opt_cfg = adamw.AdamWConfig()
        mesh = make_host_mesh()
        with mesh:
            _, (_, st_sh, _) = steps_lib.jit_train_step(
                cfg, opt_cfg, mesh, input_specs(cfg, 2, 32, "train"))
        eager = steps_lib.init_state(cfg, opt_cfg, seed=3)
        placed = steps_lib.init_state(cfg, opt_cfg, seed=3, shardings=st_sh)
        for a, b, sh in zip(jax.tree_util.tree_leaves(eager),
                            jax.tree_util.tree_leaves(placed),
                            jax.tree_util.tree_leaves(st_sh)):
            # the same draws; jit may fuse the init scaling one ulp apart
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
            assert b.sharding == sh


class TestCompileCache:
    """``steps.use_compile_cache``: the environment's directory wins; else
    the checkout's fixed ``.jax_cache``.  Subprocesses: the cache directory
    is read once per process."""

    SNIPPET = ("import jax, jax.numpy as jnp\n"
               "from repro.launch import steps\n"
               "print(steps.use_compile_cache())\n"
               "print(jax.config.jax_compilation_cache_dir)\n")

    def _run(self, env, code):
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, env=env, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.split()

    def test_environment_directory_holds_the_entries(self, tmp_path):
        env = dict(os.environ, PYTHONPATH="src",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
        said = self._run(env, self.SNIPPET
                         + "jax.jit(lambda x: x * 2)(jnp.ones(3))\n")
        assert said == [str(tmp_path), str(tmp_path)]
        assert any(tmp_path.iterdir())

    def test_default_is_the_checkout_cache(self):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["PYTHONPATH"] = "src"
        said = self._run(env, self.SNIPPET)
        assert said == [str(steps_lib.CACHE_DIR)] * 2
        assert steps_lib.CACHE_DIR.name == ".jax_cache"
        assert steps_lib.CACHE_DIR.parent == \
            pathlib.Path(__file__).resolve().parents[1]
