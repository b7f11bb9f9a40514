"""Host time per traced step that the debugger's collection takes on the
training thread: the union of its ``perfdbg.record`` spans (recording each
region exit and program exit for every rank) and ``perfdbg.flush`` spans
(closing, gathering and submitting a window) inside the traced window."""
from yardstick import clip, length, union

COLLECTION = ("perfdbg.record", "perfdbg.flush")


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    spans = [sp for sp in run.trace.host_spans if sp[0] in COLLECTION]
    if not spans:
        return None
    busy = union((s, s + d) for _, s, d in clip(spans, run.trace_window))
    return length(busy) / 1e6 / run.traced_steps
