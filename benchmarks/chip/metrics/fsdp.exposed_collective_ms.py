"""The part of ``fsdp.collective_ms`` in which no other operation runs on
the chip: collective time the step waits for, per traced step, averaged
over chips."""
from collectives import per_step_ms


def read(run):
    return per_step_ms(run, "exposed_collective_ns")
