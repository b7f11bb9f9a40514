"""How the benchmark drives the system under test, in its own process.

The training driver ``repro.launch.train.run`` is called as it is.  Three of
the names it looks up are replaced for the length of a call, so that the
benchmark decides the seed and can watch the step it runs:

  * ``repro.launch.steps.init_state`` builds the train state from the
    benchmark's weights (``configs/<architecture>.py`` ``init_params``),
    made on the devices in one jitted call with the driver's shardings,
    the seed's key its argument, so one compiled program serves every seed;
  * ``repro.data.pipeline.SyntheticTokens`` gets the benchmark's seed;
  * ``repro.launch.steps.jit_train_step`` hands back the compiled step
    wrapped in a ``StepWatch``, which calls hooks before and after each
    step (probes of the state, profiler start and stop).

``LineClock`` stands in for ``sys.stdout`` and stamps each line the driver
prints on the host clock as it is written.
"""
from __future__ import annotations

import contextlib
import io
import re
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple


class LineClock(io.TextIOBase):
    """A text stream that records (host time, line) for every whole line,
    per writing thread, and passes the text on to ``sink``."""

    def __init__(self, sink):
        self.sink = sink
        self.lines: List[Tuple[float, str]] = []
        self._part: Dict[int, str] = {}
        self._lock = threading.Lock()

    def writable(self) -> bool:
        return True

    def write(self, s: str) -> int:
        now = time.perf_counter()
        tid = threading.get_ident()
        with self._lock:
            buf = self._part.get(tid, "") + s
            *whole, rest = buf.split("\n")
            self._part[tid] = rest
            for line in whole:
                self.lines.append((now, line))
            self.sink.write(s)
        return len(s)

    def flush(self) -> None:
        self.sink.flush()

    def reset(self) -> None:
        with self._lock:
            self.lines = []

    def stamp(self, pattern: str) -> Optional[float]:
        """Host time of the first line that matches ``pattern``."""
        rx = re.compile(pattern)
        for t, line in self.lines:
            if rx.search(line):
                return t
        return None


class StepWatch:
    """Calls ``before[k](state, batch)`` and ``after[k](state, metrics)``
    around the k-th call (from 1) of the compiled train step, and records in
    ``ended[k]`` the host time at which its loss was ready (the driver waits
    for it right after the call, so waiting here moves nothing).  ``fault``,
    when set, takes the place of the step: ``fault(step, state, batch)``."""

    def __init__(self):
        self.calls = 0
        self.ended: Dict[int, float] = {}
        self.before: Dict[int, Callable] = {}
        self.after: Dict[int, Callable] = {}
        self.fault: Optional[Callable] = None

    def wrap_jit(self, jit_train_step):
        watch = self

        class Compiled:
            def __init__(self, compiled):
                self._c = compiled

            def __call__(self, state, batch):
                watch.calls += 1
                k = watch.calls
                if k in watch.before:
                    watch.before[k](state, batch)
                if watch.fault is not None:
                    state, metrics = watch.fault(self._c, state, batch)
                else:
                    state, metrics = self._c(state, batch)
                metrics["loss"].block_until_ready()
                watch.ended[k] = time.perf_counter()
                if k in watch.after:
                    watch.after[k](state, metrics)
                return state, metrics

            def __getattr__(self, name):
                return getattr(self._c, name)

        class Lowered:
            def __init__(self, lowered):
                self._l = lowered

            def compile(self, *a, **kw):
                return Compiled(self._l.compile(*a, **kw))

            def __getattr__(self, name):
                return getattr(self._l, name)

        class Jitted:
            def __init__(self, jitted):
                self._j = jitted

            def lower(self, *a, **kw):
                return Lowered(self._j.lower(*a, **kw))

            def __getattr__(self, name):
                return getattr(self._j, name)

        def wrapped(*a, **kw):
            jitted, rest = jit_train_step(*a, **kw)
            return Jitted(jitted), rest
        return wrapped


def check_config(cfg, c: Dict) -> None:
    """The driver's model config has the sizes of the configuration file."""
    have = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.d_model // c["head_dim"], "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "param_dtype": cfg.param_dtype,
            "compute_dtype": cfg.compute_dtype}
    bad = {k: (v, c[k]) for k, v in have.items() if v != c[k]}
    if bad:
        raise SystemExit(f"the driver's config differs from the "
                         f"configuration file (driver, file): {bad}")


@contextlib.contextmanager
def hooks(c: Dict, seed: int, ref, watch: StepWatch):
    """Replace the driver's seed, weights and step lookups for the call."""
    import jax
    import jax.numpy as jnp
    from repro.data import pipeline
    from repro.launch import steps

    orig = (steps.init_state, steps.jit_train_step, pipeline.SyntheticTokens)

    def init_state(cfg, opt_cfg, seed_unused=0, shardings=None, **kw):
        check_config(cfg, c)

        def build(key):
            params = ref.init_params(c, key)
            zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
            return {"params": params, "opt": {
                "m": zeros(params), "v": zeros(params),
                "step": jnp.zeros((), jnp.int32)}}
        want = steps.state_specs(cfg, opt_cfg)[0]
        key = ref.seed_key(seed)
        got = jax.eval_shape(build, key)
        if jax.tree_util.tree_structure(got) != \
                jax.tree_util.tree_structure(want) or any(
                    (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                        jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want))):
            raise SystemExit("the benchmark's weights do not fit the "
                             "driver's train state")
        return jax.jit(build, out_shardings=shardings)(key)

    class SeededTokens(orig[2]):
        def __init__(self, *a, **kw):
            kw["seed"] = seed
            super().__init__(*a, **kw)

    steps.init_state = init_state
    steps.jit_train_step = watch.wrap_jit(orig[1])
    pipeline.SyntheticTokens = SeededTokens
    try:
        yield
    finally:
        steps.init_state, steps.jit_train_step, pipeline.SyntheticTokens = orig


@contextlib.contextmanager
def stamped_stdout(clock: LineClock):
    old = sys.stdout
    sys.stdout = clock
    try:
        yield clock
    finally:
        sys.stdout = old
