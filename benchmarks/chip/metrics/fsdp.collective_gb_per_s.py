"""Achieved collective rate of one chip, in GB/s: the compiled step's
collective bytes a chip, as the ``collective_bytes`` argument of the
``train`` step spans that open in the traced window carries them, over
``fsdp.collective_ms``; None where the spans do not carry the bytes (a
program from before the counter) or no collective ran."""
import spans
from collectives import per_step_ms


def read(run):
    pt = spans.for_run(run)
    if pt is None:
        return None
    lo, hi = run.trace_window
    got = [float(args["collective_bytes"]) for _, s, _, args in
           pt.spans("train") if lo <= s < hi and "collective_bytes" in args]
    ms = per_step_ms(run, "collective_ns")
    if not got or not ms:
        return None
    return max(got) / (ms / 1e3) / 1e9
