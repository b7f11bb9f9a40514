"""Collective time of each chip in a traced run, for the ``fsdp.*``
metrics.  Nothing here imports the system under test.

``yardstick.chip_times`` counts the collectives that run as operations of
their own (``all-gather.3``).  The TPU compiler also runs collectives
asynchronously, as an ``async-collective-start.<n>`` and an
``async-collective-done.<n>`` fusion (or ``all-gather-start.<n>`` and
``all-gather-done.<n>``): the transfer is in flight from the start's
beginning to the done's end, while other operations may run.  Here each
such pair counts as one collective over that interval, and the start and
done themselves as no compute; the reduction is otherwise
``yardstick.chip_times``'s."""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

import yardstick

ASYNC = re.compile(r"^(async-collective|all-gather|reduce-scatter|all-reduce|"
                   r"all-to-all|collective-permute)-(start|done)(\.\d+)?$")


def async_spans(ops: List[yardstick.Event]) -> List[Tuple[int, int]]:
    """[start, end) of each asynchronous collective: a start's beginning
    to its done's end (a start without its done is left out)."""
    out, started = [], {}
    for name, s, d in sorted(ops, key=lambda e: e[1]):
        m = ASYNC.match(name)
        if not m:
            continue
        key = (m.group(1), m.group(3))
        if m.group(2) == "start":
            started[key] = s
        elif key in started:
            out.append((started.pop(key), s + d))
    return out


def chip_times(ops: List[yardstick.Event], window: Tuple[int, int]
               ) -> yardstick.ChipTime:
    """One chip's busy, collective and exposed collective time inside the
    window, asynchronous collectives counted from start to done."""
    lo, hi = window
    flight = [(max(s, lo), min(e, hi)) for s, e in async_spans(ops)
              if s < hi and e > lo]
    ops = yardstick.clip(ops, window)
    busy = yardstick.union([(s, s + d) for _, s, d in ops] + flight)
    coll = yardstick.union(
        [(s, s + d) for n, s, d in ops if yardstick.COLLECTIVE.search(n)
         and not ASYNC.match(n)] + flight)
    compute = yardstick.union(
        (s, s + d) for n, s, d in ops
        if not yardstick.COLLECTIVE.search(n) and not ASYNC.match(n)
        and not yardstick.CONTAINER.match(n))
    return yardstick.ChipTime(yardstick.length(busy), yardstick.length(coll),
                              yardstick.length(yardstick.subtract(coll,
                                                                  compute)))


def per_step_ms(run, field: str) -> Optional[float]:
    """``field`` of each chip's ``ChipTime`` per traced step, averaged over
    chips; None without a trace."""
    if run.trace is None or not run.traced_steps or \
            not run.trace.device_ops:
        return None
    times = [chip_times(ops, run.trace_window)
             for _, ops in sorted(run.trace.device_ops.items())]
    return (sum(getattr(ct, field) for ct in times) / len(times)
            / 1e6 / run.traced_steps)

