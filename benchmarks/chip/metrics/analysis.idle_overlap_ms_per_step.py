"""Device-idle time per traced step of the first chip that lies under an
open ``analysis.window`` span on any thread: how much of the chip's idle
time the analysis could have caused by holding the host."""
from spans import for_run
from yardstick import length, subtract, union


def read(run):
    pt = for_run(run)
    if pt is None or not run.traced_steps or not run.trace.device_ops:
        return None
    windows = union((s, s + d) for _, s, d, _ in pt.spans("analysis.window"))
    if not windows:
        return None
    chip = sorted(run.trace.device_ops)[0]
    busy = union((s, s + d) for _, s, d in run.trace.device_ops[chip])
    idle = subtract([tuple(run.trace_window)], busy)
    under = length(idle) - length(subtract(idle, windows))
    return under / 1e6 / run.traced_steps
