"""Median of the same last-step-to-verdict waits as ``verdict_ms_p90``."""
from yardstick import quantile


def read(run):
    return quantile(run.verdict_ms, 0.5) if run.verdict_ms else None
