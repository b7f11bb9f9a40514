"""Device busy time per step in the traced steps, averaged over chips."""


def read(run):
    if not run.chip_times or not run.traced_steps:
        return None
    busy = sum(ct.busy_ns for ct in run.chip_times) / len(run.chip_times)
    return busy / 1e6 / run.traced_steps
