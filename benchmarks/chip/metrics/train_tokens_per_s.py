"""Tokens trained in the window over the window's wall time, counting
everything the driver's loop does between its ``[step k]`` lines."""


def read(run):
    return run.tokens_per_step * len(run.window_steps) / run.window_s
