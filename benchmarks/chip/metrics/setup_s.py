"""Process start to the opening of the timed window: imports, the warm-up
and sizing call (compile or cache load, state, HLO costs), and the timed
call's first two analysis windows."""


def read(run):
    return run.setup_s
