"""Median, over the windows whose ``perfdbg.flush`` ends inside the traced
window, of the wait from the end of that flush (the window submitted) to
the start of its ``analysis.window`` (a worker takes it), matched by the
submission number both spans carry."""
from spans import for_run
from yardstick import quantile


def read(run):
    pt = for_run(run)
    if pt is None:
        return None
    lo, hi = run.trace_window
    taken = {a.get("submission"): s
             for _, s, _, a in pt.spans("analysis.window")}
    ms = [(taken[a["submission"]] - (s + d)) / 1e6
          for _, s, d, a in pt.spans("perfdbg.flush")
          if lo <= s + d < hi and a.get("submission") in taken]
    return quantile(ms, 0.5) if ms else None
