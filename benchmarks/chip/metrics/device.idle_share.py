"""Share of the traced window in which the most idle chip ran nothing."""


def read(run):
    if not run.chip_times or run.trace_window_s <= 0:
        return None
    least = min(ct.busy_ns for ct in run.chip_times) / 1e9
    return 100.0 * (1.0 - least / run.trace_window_s)
