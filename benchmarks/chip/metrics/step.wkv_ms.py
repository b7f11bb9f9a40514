"""Device time per traced step of the operations traced under the
``wkv`` scope (RWKV-6's WKV recurrence: forward, recomputation and
backward), averaged over chips."""
from spans import scope_ms


def read(run):
    return scope_ms(run, "wkv")
