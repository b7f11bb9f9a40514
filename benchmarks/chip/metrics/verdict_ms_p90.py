"""90th percentile, over every window of the timed window, of the wait from
the end of the window's last step (its loss ready on the host) to the
driver's verdict line for the window."""
from yardstick import quantile


def read(run):
    return quantile(run.verdict_ms, 0.9) if run.verdict_ms else None
