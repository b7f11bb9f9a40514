"""Device time per traced step in which a collective is in flight (the
union of the collective operations, and of each asynchronous collective
from its start to its done), averaged over chips."""
from collectives import per_step_ms


def read(run):
    return per_step_ms(run, "collective_ns")
