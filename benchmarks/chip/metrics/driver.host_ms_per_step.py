"""Host time per step outside the compiled step: the window's wall time less
the driver's step times, over the steps of the window."""


def read(run):
    n = len(run.window_steps)
    return 1e3 * (run.window_s - sum(run.window_steps)) / n
