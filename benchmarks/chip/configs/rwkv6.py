"""Plain reference of the RWKV-6 "Finch" language model and its training step.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
written from the equations of Peng et al., "Eagle and Finch" (arXiv:2404.05892),
Section 4, and from nothing of the system under test.  The weights are made
here from the seed (``init_params``); the benchmark hands the same weights to
the system, so both start from one state.

Departures from the paper, each one what the system under test computes:
  * token positions also enter as a sinusoidal code added to the scaled
    embedding (``x = E[t] * sqrt(d) + pos``), and LayerNorm scales are
    stored as ``1 + scale``;
  * the token-shift mix uses ``x + (x_prev - x) * 0.5`` as the LoRA input
    (the paper learns that lerp weight);
  * the per-channel log decay ``-exp(w0 + lora(x_w))`` has its exponent
    clipped to ``[-8, 0.2]``;
  * the output GroupNorm has a scale and no bias, with eps 64e-5.

The WKV recurrence is the paper's sequential form,
    y_t = r_t (S_{t-1} + diag(u) k_t^T v_t),   S_t = diag(w_t) S_{t-1} + k_t^T v_t,
scanned token by token; chunks of it are checkpointed so the backward pass
holds one chunk's states at a time, which changes no number.

``precision="e4m3"`` or ``"e5m2"`` is the control, the step below the
configuration's bfloat16 compute type: every value that type holds
(activations, matrix operands and products) is rounded to that float8 on the
way forward, and the gradient that comes back through it is rounded alike,
each tensor under one scale that maps its largest magnitude to the format's
largest value, as float8 training scales them.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MIXES = 5                # r, k, v, w, g
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def leaf_specs(c: Dict) -> List[Tuple[Tuple[str, ...], Tuple[int, ...], str, float]]:
    """(path, shape, init, scale) for every parameter, in a fixed order.
    Paths name the nested dicts of the parameter tree; ``groups`` holds the
    layers stacked on a leading axis."""
    d, f, V, L = c["d_model"], c["d_ff"], c["vocab_size"], c["n_layers"]
    H, dh = c["n_heads"], c["head_dim"]
    lm, lw = c["lora_mix_dim"], c["lora_decay_dim"]
    sd, sf = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    layer = [
        (("ln1", "scale"), (d,), "zeros", 0.0),
        (("ln1", "bias"), (d,), "zeros", 0.0),
        (("ln2", "scale"), (d,), "zeros", 0.0),
        (("ln2", "bias"), (d,), "zeros", 0.0),
        (("tm", "mu"), (MIXES, d), "normal", 0.5),
        (("tm", "mix_lora_a"), (d, MIXES * lm), "normal", sd),
        (("tm", "mix_lora_b"), (MIXES, lm, d), "normal", 0.01),
        (("tm", "w0"), (d,), "zeros", 0.0),
        (("tm", "w_lora_a"), (d, lw), "normal", sd),
        (("tm", "w_lora_b"), (lw, d), "normal", 0.01),
        (("tm", "u"), (H, dh), "normal", 0.5),
        (("tm", "wr", "w"), (d, d), "normal", sd),
        (("tm", "wk", "w"), (d, d), "normal", sd),
        (("tm", "wv", "w"), (d, d), "normal", sd),
        (("tm", "wg", "w"), (d, d), "normal", sd),
        (("tm", "wo", "w"), (d, d), "normal", sd),
        (("tm", "ln_scale"), (d,), "ones", 0.0),
        (("tm", "ck", "w"), (d, f), "normal", sd),
        (("tm", "cv", "w"), (f, d), "normal", sf),
        (("tm", "cr", "w"), (d, d), "normal", sd),
        (("tm", "mu_ck"), (d,), "normal", 0.5),
        (("tm", "mu_cr"), (d,), "normal", 0.5),
    ]
    out = [(("embed", "table"), (V, d), "normal", 1.0),
           (("final_norm", "scale"), (d,), "zeros", 0.0),
           (("final_norm", "bias"), (d,), "zeros", 0.0),
           (("logits", "w"), (d, V), "normal", sd)]
    out += [(("groups", "pos0") + p, (L,) + s, k, sc) for p, s, k, sc in layer]
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)


def init_leaf(key: jax.Array, index: int, shape, kind: str, scale: float):
    if kind == "zeros":
        return jnp.zeros(shape, F32)
    if kind == "ones":
        return jnp.ones(shape, F32)
    return scale * jax.random.normal(jax.random.fold_in(key, index), shape, F32)


def _nest(flat: Dict[Tuple[str, ...], jax.Array]) -> Dict:
    tree: Dict = {}
    for path, v in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    tree["groups"] = (tree["groups"],)     # one group of stacked layers
    return tree


def flatten(tree: Dict) -> Dict[Tuple[str, ...], jax.Array]:
    """Inverse of the nesting: {path: leaf}, with ``groups[0]`` as ``groups``."""
    out = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (k,))
        elif isinstance(node, (tuple, list)):
            walk(node[0], path)
        else:
            out[path] = node
    walk(tree, ())
    return out


def init_params(c: Dict, key: jax.Array) -> Dict:
    """Every parameter from the seed's ``key``, leaf i from
    ``fold_in(key, i)``.  The key is an argument, not a constant, so one
    compiled program makes the weights of every seed."""
    return _nest({p: init_leaf(key, i, s, k, sc)
                  for i, (p, s, k, sc) in enumerate(leaf_specs(c))})


# ---------------------------------------------------------------------------
# Data: the driver's synthetic next-token batches, regenerated from the seed
# ---------------------------------------------------------------------------

def batch_at(c: Dict, seed: int, step: int, batch: int, seq: int):
    """Tokens and labels of step ``step`` (0-based): uniform ids from
    ``numpy.random.default_rng(SeedSequence([seed, step]))``, the next
    token as label."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    toks = rng.integers(0, c["vocab_size"], size=(batch, seq + 1),
                        dtype=np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

FP8 = {"e4m3": jnp.float8_e4m3fn, "e5m2": jnp.float8_e5m2}


def _to_fp8(x, dtype):
    """``x`` rounded to the float8 ``dtype`` under one scale for the tensor,
    saturating as float8 training casts do.  The scale stays finite (for a
    tensor all but zero the format's largest over its largest magnitude
    overflows float32), and the scaled tensor is clipped to the format's
    range (its largest element may round a hair past it, which the cast
    turns into NaN or infinity)."""
    fmax = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.minimum(fmax / amax, float(jnp.finfo(F32).max))
    return (jnp.clip(x * scale, -fmax, fmax).astype(dtype).astype(F32)
            / scale)


def _fp8_rounder(dtype):
    @jax.custom_vjp
    def rnd(x):
        return _to_fp8(x, dtype)
    rnd.defvjp(lambda x: (_to_fp8(x, dtype), None),
               lambda _, g: (_to_fp8(g, dtype),))
    return rnd


def _rounder(precision):
    """How a value of the configuration's compute type is held: unchanged
    in the reference; in the control rounded to float8, forward and back."""
    if precision == "f32":
        return lambda x: x
    return _fp8_rounder(FP8[precision])


def _mm(spec, a, b, q):
    return q(jnp.einsum(spec, q(a), q(b), precision=HIGHEST))


def _layer_norm(x, p, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * (1.0 + p["scale"]) + p["bias"]


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _sinusoid(seq: int, d: int):
    pos = jnp.arange(seq, dtype=F32)[:, None]
    half = d // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=F32)
                    / max(half - 1, 1))
    return jnp.concatenate([jnp.sin(pos * freqs), jnp.cos(pos * freqs)], -1)


def wkv(r, k, v, logw, u, chunk: int = 32):
    """The sequential WKV recurrence.  r, k, v, logw: (B, T, H, dh)."""
    B, T, H, dh = r.shape
    chunk = math.gcd(T, chunk)

    def token(S, inp):
        r_t, k_t, v_t, w_t = inp                       # (B, H, dh)
        kv = k_t[..., :, None] * v_t[..., None, :]
        y = jnp.einsum("bhi,bhij->bhj", r_t, S + u[None, :, :, None] * kv,
                       precision=HIGHEST)
        return jnp.exp(w_t)[..., None] * S + kv, y

    @jax.checkpoint
    def block(S, inp):
        return jax.lax.scan(token, S, inp)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape(T // chunk, chunk, B, H, dh)
               for a in (r, k, v, logw))
    _, ys = jax.lax.scan(block, jnp.zeros((B, H, dh, dh), F32), xs)
    return jnp.moveaxis(ys.reshape(T, B, H, dh), 0, 1)


def time_mix(p, x, c, q):
    B, T, d = x.shape
    H, dh = c["n_heads"], c["head_dim"]
    xx = _shift(x)
    lora = q(jnp.tanh(_mm("btd,dk->btk", q(x + (xx - x) * 0.5),
                          p["mix_lora_a"], q))).reshape(B, T, MIXES, -1)
    delta = _mm("btmk,mkd->btmd", lora, p["mix_lora_b"], q)
    xr, xk, xv, xw, xg = (q(x + (xx - x) * q(p["mu"][i] + delta[:, :, i]))
                          for i in range(MIXES))
    r = _mm("btd,de->bte", xr, p["wr"]["w"], q)
    k = _mm("btd,de->bte", xk, p["wk"]["w"], q)
    v = _mm("btd,de->bte", xv, p["wv"]["w"], q)
    g = _mm("btd,de->bte", xg, p["wg"]["w"], q)
    dd = _mm("btk,kd->btd", q(jnp.tanh(_mm("btd,dk->btk", xw, p["w_lora_a"],
                                           q))), p["w_lora_b"], q)
    logw = -jnp.exp(jnp.clip(p["w0"] + dd, -8.0, 0.2))
    heads = lambda a: a.reshape(B, T, H, dh)
    y = q(wkv(heads(r), heads(k), heads(v), heads(logw), p["u"]))
    mu = jnp.mean(y, -1, keepdims=True)
    var = jnp.mean((y - mu) ** 2, -1, keepdims=True)
    y = q(((y - mu) / jnp.sqrt(var + 64e-5)).reshape(B, T, d) * p["ln_scale"])
    return _mm("btd,de->bte", q(y * q(jax.nn.silu(g))), p["wo"]["w"], q)


def channel_mix(p, x, q):
    xx = _shift(x)
    xk = q(x + (xx - x) * p["mu_ck"])
    xr = q(x + (xx - x) * p["mu_cr"])
    kk = q(jax.nn.relu(_mm("btd,df->btf", xk, p["ck"]["w"], q)))
    vv = _mm("btf,fd->btd", q(kk * kk), p["cv"]["w"], q)
    return q(q(jax.nn.sigmoid(_mm("btd,de->bte", xr, p["cr"]["w"], q))) * vv)


def loss(params, c: Dict, tokens, labels, precision: str = "f32"):
    """Mean next-token cross-entropy over the batch.  Every value that the
    configuration's compute type holds passes ``q`` (a no-op at ``f32``);
    norms, the decay, the WKV state and the loss stay float32."""
    B, T = tokens.shape
    d = c["d_model"]
    q = _rounder(precision)
    x = q(q(q(params["embed"]["table"][tokens]) * math.sqrt(d))
          + q(_sinusoid(T, d)))

    @jax.checkpoint
    def block(x, p):
        x = q(x + time_mix(p["tm"], q(_layer_norm(x, p["ln1"])), c, q))
        return q(x + channel_mix(p["tm"], q(_layer_norm(x, p["ln2"])), q)), None

    x, _ = jax.lax.scan(block, x, params["groups"][0]["pos0"])
    h = q(_layer_norm(x, params["final_norm"]))

    @jax.checkpoint
    def nll(h, lab):           # one row at a time: (T, V) logits at most
        logits = _mm("td,dv->tv", h, params["logits"]["w"], q)
        gold = jnp.take_along_axis(logits, lab[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    total = jax.lax.map(lambda a: nll(*a), (h, labels)).sum()
    return total / (B * T)


# ---------------------------------------------------------------------------
# AdamW with global-norm clipping, linear warm-up and cosine decay
# ---------------------------------------------------------------------------

def learning_rate(o: Dict, step: int, total_steps: int) -> float:
    decay = max(total_steps, 10)
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    prog = min(max((step - o["warmup_steps"])
                   / max(decay - o["warmup_steps"], 1), 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * prog))
    return o["lr"] * warm * (o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * cos)


def adamw_leaf(p, g, m, v, scale, lr, step, o: Dict):
    """One AdamW update of one leaf (``step`` counts from 1); ``scale``
    clips the gradient to the global norm."""
    g = g * scale
    m = o["b1"] * m + (1 - o["b1"]) * g
    v = o["b2"] * v + (1 - o["b2"]) * g * g
    mhat = m / (1 - o["b1"] ** step)
    vhat = v / (1 - o["b2"] ** step)
    return p - lr * (mhat / (jnp.sqrt(vhat) + o["eps"])
                     + o["weight_decay"] * p), m, v


SAMPLE = 1 << 16      # elements of each leaf compared one by one


def leaf_readings(tree, key) -> Dict[str, Dict[str, jax.Array]]:
    """Each leaf's norm, and up to ``SAMPLE`` of its elements at places
    drawn from ``key`` and the leaf's name, so that every tree of one
    configuration is sampled at the same places."""
    norms, samples = {}, {}
    for i, (path, v) in enumerate(sorted(flatten(tree).items())):
        name = "/".join(path)
        norms[name] = jnp.sqrt(jnp.sum(jnp.square(v)))
        if v.size <= SAMPLE:
            samples[name] = v.reshape(-1)
        else:
            at = jax.random.randint(jax.random.fold_in(key, i), (SAMPLE,),
                                    0, v.size)
            samples[name] = v[jnp.unravel_index(at, v.shape)]
    return {"norms": norms, "samples": samples}


def to_host(readings, scale: float = 1.0) -> Dict[str, Dict]:
    """Leaf readings as floats and float64 arrays, times ``scale``."""
    return {"norms": {k: float(x) * scale
                      for k, x in readings["norms"].items()},
            "samples": {k: np.asarray(x, np.float64) * scale
                        for k, x in readings["samples"].items()}}


def placement(c: Dict, devices) -> Dict:
    """Where each leaf lives: on one device whole, or over several split
    along its largest dimension that they divide (never the stacked layer
    axis, which the layer scan walks)."""
    if len(devices) == 1:
        one = jax.sharding.SingleDeviceSharding(devices[0])
        return {p: one for p, *_ in leaf_specs(c)}
    mesh = jax.sharding.Mesh(np.asarray(devices), ("d",))
    n = len(devices)
    out = {}
    for p, shape, *_ in leaf_specs(c):
        first = 1 if p[0] == "groups" else 0
        dims = [i for i in range(first, len(shape)) if shape[i] % n == 0]
        spec = [None] * len(shape)
        if dims:
            spec[max(dims, key=lambda i: shape[i])] = "d"
        out[p] = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
            *spec))
    return out


def train_readings(c: Dict, seed: int, batch: int, seq: int, total_steps: int,
                   n_steps: int = 3, precision: str = "f32", devices=None,
                   half_batch: bool = False) -> Dict:
    """Train ``n_steps`` from the seed's weights on the seed's batches.

    Returns the loss of each step, and ``leaf_readings`` of the first
    (clipped) gradient (``grad``) and of each leaf's change after the last
    step (``change``).
    ``half_batch`` is a planted fault: the second half of every batch
    repeats the first, so the mean is over half of the rows.

    The update runs leaf by leaf, so the moments and the gradient of one
    leaf at a time are the only memory it adds to the backward pass.  Over
    several ``devices`` every leaf is split as ``placement`` says and the
    batch by rows."""
    o = c["optimizer"]
    devices = devices or jax.devices()[:1]
    place = placement(c, devices)
    rows = jax.sharding.NamedSharding(
        jax.sharding.Mesh(np.asarray(devices), ("d",)),
        jax.sharding.PartitionSpec("d" if batch % len(devices) == 0 else None))
    key = seed_key(seed)
    params = jax.jit(lambda k: flatten(init_params(c, k)),
                     out_shardings=place)(key)
    zeros = jax.jit(lambda: {p: jnp.zeros(s, F32)
                             for p, s, *_ in leaf_specs(c)},
                    out_shardings=place)
    grad = jax.jit(lambda p, t, l: jax.value_and_grad(loss)(
        _nest(p), c, t, l, precision),
        out_shardings=(None, _nest(dict(place))))
    read = jax.jit(leaf_readings)
    update = jax.jit(lambda p, g, m, v, scale, lr, step: adamw_leaf(
        p, g, m, v, scale, lr, step, o), donate_argnums=(0, 1, 2, 3))
    out: Dict = {"losses": []}
    for s in range(n_steps):
        b = batch_at(c, seed, s, batch, seq)
        if half_batch:
            b = {k: np.concatenate([x[:batch // 2]] * 2) for k, x in b.items()}
        lval, grads = grad(params, jax.device_put(b["tokens"], rows),
                           jax.device_put(b["labels"], rows))
        raw = read(grads, key)
        gnorm = math.sqrt(sum(float(x) ** 2 for x in raw["norms"].values()))
        scale = min(1.0, o["clip_norm"] / max(gnorm, 1e-12))
        flat = flatten(grads)
        if s == 0:                 # the gradient as the optimizer takes it
            out["grad"] = to_host(raw, scale)
            m, v = zeros(), zeros()
        lr = learning_rate(o, s + 1, total_steps)
        for k, g in flat.items():
            params[k], m[k], v[k] = update(params[k], g, m[k], v[k],
                                           scale, lr, s + 1)
        del grads, flat
        out["losses"].append(float(lval))
    del m, v
    out["change"] = change_readings(c, key, _nest(params))
    return out


def change_readings(c: Dict, key: jax.Array, params) -> Dict[str, Dict]:
    """``leaf_readings`` of each leaf's distance from its initial value,
    the initial value regenerated from the seed's key inside the
    reduction."""
    def change(p, k):
        return leaf_readings(jax.tree_util.tree_map(
            lambda a, b: a - b, p, init_params(c, k)), k)
    return to_host(jax.jit(change)(params, key))
