"""95th percentile of the driver's own step time (host clock around the
compiled step, synced on the loss) over every step of the window."""
from yardstick import quantile


def read(run):
    return 1e3 * quantile(run.window_steps, 0.95)
