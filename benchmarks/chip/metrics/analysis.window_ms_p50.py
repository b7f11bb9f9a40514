"""Median duration of the ``analysis.window`` spans that start inside the
traced window: a worker's time from taking a window off the queue to the
printed verdict line."""
from spans import for_run
from yardstick import quantile


def read(run):
    pt = for_run(run)
    if pt is None:
        return None
    lo, hi = run.trace_window
    ms = [d / 1e6 for _, s, d, _ in pt.spans("analysis.window")
          if lo <= s < hi]
    return quantile(ms, 0.5) if ms else None
