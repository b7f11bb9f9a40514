"""Device time per traced step of the operations traced under the
``optimizer`` scope (AdamW's update, the gradient norm and clipping
included), averaged over chips."""
from spans import scope_ms


def read(run):
    return scope_ms(run, "optimizer")
