"""Pallas kernel tests: interpret=True vs the pure-jnp oracles, sweeping
shapes and dtypes per the deliverable spec."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan_kernel

TOL = dict(rtol=2e-2, atol=2e-2)      # bf16 inputs
TOL32 = dict(rtol=1e-5, atol=1e-5)    # f32 inputs


def _qkv(key, bh, sq, sk, dh, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (bh, sq, dh), dtype)
    k = jax.random.normal(kk, (bh, sk, dh), dtype)
    v = jax.random.normal(kv, (bh, sk, dh), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("sq,sk,dh,blk", [
        (128, 128, 64, 64), (256, 256, 128, 128), (64, 64, 32, 32),
    ])
    def test_causal_shapes_dtypes(self, dtype, sq, sk, dh, blk):
        q, k, v = _qkv(jax.random.PRNGKey(0), 4, sq, sk, dh, dtype)
        got = flash_attention(q, k, v, causal=True, block_q=blk, block_k=blk,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        tol = TOL if dtype == jnp.bfloat16 else TOL32
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol)

    def test_non_causal(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), 2, 128, 128, 64, jnp.float32)
        got = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(got, want, **TOL32)

    def test_sliding_window(self):
        q, k, v = _qkv(jax.random.PRNGKey(2), 2, 256, 256, 64, jnp.float32)
        got = flash_attention(q, k, v, causal=True, window=64,
                              block_q=64, block_k=64, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=64)
        np.testing.assert_allclose(got, want, **TOL32)

    def test_softcap(self):
        q, k, v = _qkv(jax.random.PRNGKey(3), 2, 128, 128, 64, jnp.float32)
        got = flash_attention(q, k, v, causal=True, softcap=50.0,
                              block_q=64, block_k=64, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, softcap=50.0)
        np.testing.assert_allclose(got, want, **TOL32)

    def test_gqa_expansion_via_ops(self):
        B, S, H, K, dh = 2, 128, 8, 2, 64
        key = jax.random.PRNGKey(4)
        q = jax.random.normal(key, (B, S, H, dh), jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(5), (B, S, K, dh), jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(6), (B, S, K, dh), jnp.float32)
        got = ops.attention(q, k, v, causal=True, interpret=True)
        # oracle: the model's mha fallback
        from repro.models.layers import mha
        want = mha(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-3, atol=1e-3)

    def test_blocks_must_divide(self):
        q, k, v = _qkv(jax.random.PRNGKey(0), 1, 100, 100, 32, jnp.float32)
        with pytest.raises(ValueError):
            flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)


class TestRGLRUScan:
    @pytest.mark.parametrize("dtype", [jnp.float32])
    @pytest.mark.parametrize("B,S,W,bt", [(2, 64, 128, 16), (4, 128, 64, 64),
                                          (1, 256, 512, 128)])
    def test_matches_sequential(self, dtype, B, S, W, bt):
        key = jax.random.PRNGKey(0)
        a = jax.random.uniform(key, (B, S, W), dtype, 0.2, 0.99)
        b = jax.random.normal(jax.random.PRNGKey(1), (B, S, W), dtype)
        got = rglru_scan_kernel(a, b, block_b=min(B, 2), block_t=bt,
                                block_w=min(W, 64), interpret=True)
        want = ref.rglru_scan_ref(a, b)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_initial_state(self):
        B, S, W = 2, 32, 64
        a = jnp.full((B, S, W), 0.9)
        b = jnp.zeros((B, S, W))
        h0 = jnp.ones((B, W))
        got = ops.rglru_scan(a, b, h0, interpret=True)
        want = ref.rglru_scan_ref(a, b, h0)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got[:, 0], 0.9 * np.ones((B, W)), rtol=1e-6)

    def test_matches_model_assoc_scan(self):
        """Kernel vs the model's associative-scan implementation."""
        from repro.models.rglru import rglru_scan as assoc
        B, S, W = 2, 64, 32
        key = jax.random.PRNGKey(7)
        a = jax.random.uniform(key, (B, S, W), jnp.float32, 0.1, 0.999)
        b = jax.random.normal(jax.random.PRNGKey(8), (B, S, W), jnp.float32)
        got = ops.rglru_scan(a, b, interpret=True)
        want = assoc(a, b)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def _wkv_inputs(B, T, H, dh, logw=None, state=False, seed=0):
    """r, k, v, log-decay inside ``_decay``'s clip, u, and a state carried
    in (or None)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    mk = lambda i: 0.5 * jax.random.normal(ks[i], (B, T, H, dh), jnp.float32)
    if logw is None:
        logw = -jnp.exp(jnp.clip(2.0 * mk(3), -8.0, 0.2))
    u = 0.3 * jax.random.normal(ks[4], (H, dh), jnp.float32)
    s0 = (0.3 * jax.random.normal(ks[5], (B, H, dh, dh), jnp.float32)
          if state else None)
    return mk(0), mk(1), mk(2), logw, u, s0


def _sequential(*args):
    from repro.models.rwkv6 import wkv6_sequential
    with jax.default_matmul_precision("highest"):
        return wkv6_sequential(*args)


def _close(got, want, rtol=1e-4):
    """Equal to a share of the oracle's largest magnitude: the kernels run
    the oracle's f32 arithmetic in another order."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rtol * max(np.abs(want).max(), 1.0))


# the log-decay at the clip's ends: the fastest decay across whole chunks,
# where exp(-P) in the factored form nears its f32 range, and the slowest
CLIP_ENDS = {"fastest": -float(np.exp(0.2)), "slowest": -float(np.exp(-8.0))}


class TestWKV6:
    @pytest.mark.parametrize("B,T,H,dh", [(1, 64, 4, 32),
                                          (2, 128, 4, 64)])
    def test_matches_sequential_ref(self, B, T, H, dh):
        r, k, v, logw, u, _ = _wkv_inputs(B, T, H, dh)
        got, s_got = ops.wkv6(r, k, v, logw, u, interpret=True)
        want, s_want = _sequential(r, k, v, logw, u)
        _close(got, want)
        _close(s_got, s_want)

    def test_matches_chunked_model(self):
        """Output and final state (the prefill's) against the model's jnp
        form, which streams r, k and v in bf16: equal to bf16's rounding."""
        from repro.models.rwkv6 import wkv6_chunked
        r, k, v, logw, u, s0 = _wkv_inputs(1, 192, 2, 64, state=True)
        got, s_got = ops.wkv6(r, k, v, logw, u, s0, interpret=True)
        want, s_want = wkv6_chunked(r, k, v, logw, u, s0)
        _close(got, want, rtol=2e-2)
        _close(s_got, s_want, rtol=2e-2)
        bf = lambda x: x.astype(jnp.bfloat16)
        got, s_got = ops.wkv6(bf(r), bf(k), bf(v), logw, u, s0,
                              interpret=True)
        assert got.dtype == jnp.bfloat16 and s_got.dtype == jnp.float32
        _close(s_got, s_want, rtol=1e-4)

    def test_state_threading(self):
        """Splitting a sequence in two with state carry == one pass."""
        r, k, v, logw, u, _ = _wkv_inputs(1, 256, 2, 64)
        full, s_full = ops.wkv6(r, k, v, logw, u, interpret=True)
        half = lambda x, i: x[:, i * 128:(i + 1) * 128]
        y1, s1 = ops.wkv6(*(half(x, 0) for x in (r, k, v, logw)), u,
                          interpret=True)
        y2, s2 = ops.wkv6(*(half(x, 1) for x in (r, k, v, logw)), u, s1,
                          interpret=True)
        _close(jnp.concatenate([y1, y2], axis=1), full, rtol=1e-5)
        _close(s2, s_full, rtol=1e-5)

    @pytest.mark.parametrize("decay", ["drawn", "fastest", "slowest"])
    @pytest.mark.parametrize("state", [False, True])
    def test_gradients_match_sequential(self, decay, state):
        """jax.grad of a scalar of both outputs through the kernels against
        jax.grad through the sequential recurrence, for r, k, v, the
        log-decay, u and the state carried in; three chunks."""
        B, T, H, dh = 2, 192, 2, 64
        logw = (None if decay == "drawn"
                else jnp.full((B, T, H, dh), CLIP_ENDS[decay], jnp.float32))
        args = _wkv_inputs(B, T, H, dh, logw=logw, state=True)
        if not state:
            args = args[:5]
        wy = jnp.sin(jnp.arange(B * T * H * dh, dtype=jnp.float32)
                     ).reshape(B, T, H, dh)

        def scalar(fn):
            def f(*a):
                y, s = fn(*a)
                return jnp.sum(y * wy) + 0.5 * jnp.sum(s * s)
            return jax.grad(f, argnums=tuple(range(len(args))))
        got = scalar(lambda *a: ops.wkv6(*a, interpret=True))(*args)
        want = scalar(_sequential)(*args)
        for g, w in zip(got, want):
            assert bool(jnp.all(jnp.isfinite(g)))
            _close(g, w, rtol=2e-4)

    def test_shapes_that_do_not_tile_are_refused(self):
        from repro.kernels.wkv6 import fits
        assert fits(1024, 40, 64) and fits(64, 4, 32)
        assert not fits(96, 2, 64)         # part of a chunk
        assert not fits(128, 1, 64)        # half the lanes
        r, k, v, logw, u, _ = _wkv_inputs(1, 96, 2, 64)
        with pytest.raises(ValueError):
            ops.wkv6(r, k, v, logw, u, interpret=True)



class TestTimeMixPath:
    """Which WKV form ``apply_time_mix`` runs, from what it can observe:
    the backend, the sequence length and the tile shapes."""

    @staticmethod
    def _block(seq, batch=2):
        from repro.configs import reduced_config
        from repro.models.layers import build_params
        from repro.models.rwkv6 import rwkv6_spec
        cfg = reduced_config("rwkv6-3b", d_model=128, n_heads=2,
                             n_kv_heads=1, d_ff=128)
        p = build_params(rwkv6_spec(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (batch, seq, 128),
                              jnp.float32)
        return p, x, cfg

    def test_the_kernels_only_on_a_tpu_and_where_they_tile(self,
                                                           monkeypatch):
        from repro.models import rwkv6
        assert not rwkv6.wkv6_kernel_fits(1024, 40, 64)      # this CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert rwkv6.wkv6_kernel_fits(1024, 40, 64)
        assert not rwkv6.wkv6_kernel_fits(1000, 40, 64)
        assert not rwkv6.wkv6_kernel_fits(1024, 1, 64)

    @pytest.mark.parametrize("return_state", [False, True])
    def test_time_mix_on_the_kernels_matches_the_jnp_path(self, monkeypatch,
                                                          return_state):
        """Training and prefill through the kernels (interpreted) against
        the jnp chunked form: output, final state and the gradient of the
        block's parameters; decode (one token) keeps the sequential form."""
        from repro.models import rwkv6
        p, x, cfg = self._block(128)

        def run(p, x):
            out = rwkv6.apply_time_mix(p, x, cfg, return_state=return_state)
            y, st = out if return_state else (out, {"wkv": jnp.zeros(())})
            return jnp.sum(y * jnp.cos(y)) + jnp.sum(st["wkv"]), out

        want_l, want = run(p, x)
        want_g = jax.grad(lambda p: run(p, x)[0])(p)
        calls, wkv6 = [], ops.wkv6

        def kernel(*a):
            calls.append(a[0].shape)
            return wkv6(*a, interpret=True)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(rwkv6.ops, "wkv6", kernel)
        got_l, got = run(p, x)
        got_g = jax.grad(lambda p: run(p, x)[0])(p)
        assert calls and calls[0] == (2, 128, 2, 64)
        for g, w in zip(jax.tree_util.tree_leaves((got, got_g)),
                        jax.tree_util.tree_leaves((want, want_g))):
            _close(g, w, rtol=3e-2)
        n = len(calls)
        st = {"shift": jnp.zeros((2, 128)), "wkv": jnp.zeros((2, 2, 64, 64))}
        rwkv6.apply_time_mix(p, x[:, :1], cfg, state=st, return_state=True,
                             use_chunked=False)
        assert len(calls) == n

    def test_the_kernels_run_per_batch_shard_on_a_mesh(self):
        """On a mesh of two devices the kernels run under shard_map over the
        batch axis, and the gradients, u's summed over the shards, equal
        those of one call.  Subprocess: the device count must be set before
        jax initializes."""
        code = (
            "import functools, jax, jax.numpy as jnp, numpy as np\n"
            "from jax.sharding import Mesh\n"
            "from repro.kernels import ops\n"
            "from repro.runtime import batch_map, sharding_context\n"
            "assert jax.device_count() == 2\n"
            "B, T, H, dh = 2, 128, 2, 64\n"
            "ks = jax.random.split(jax.random.PRNGKey(0), 5)\n"
            "r, k, v, w = [0.5 * jax.random.normal(x, (B, T, H, dh))\n"
            "              for x in ks[:4]]\n"
            "w = -jnp.exp(jnp.clip(w, -8.0, 0.2))\n"
            "u = 0.3 * jax.random.normal(ks[4], (H, dh))\n"
            "s0 = jnp.zeros((B, H, dh, dh))\n"
            "fn = functools.partial(ops.wkv6, interpret=True)\n"
            "def loss(*a):\n"
            "    y, s = batch_map(fn, *a, replicated=(4,))\n"
            "    return jnp.sum(y * y) + jnp.sum(s)\n"
            "grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))\n"
            "want = jax.jit(grad)(r, k, v, w, u, s0)\n"
            "mesh = Mesh(np.asarray(jax.devices()).reshape(2, 1),\n"
            "            ('data', 'model'))\n"
            "def sharded(*a):\n"
            "    with sharding_context(mesh):\n"
            "        return grad(*a)\n"
            "text = jax.jit(sharded).lower(r, k, v, w, u, s0).as_text()\n"
            "assert 'shmap' in text or 'manual' in text, text[:2000]\n"
            "got = jax.jit(sharded)(r, k, v, w, u, s0)\n"
            "for g, x in zip(got, want):\n"
            "    np.testing.assert_allclose(g, x, rtol=1e-5, atol=1e-5)\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-3000:]
