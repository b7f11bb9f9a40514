"""Model flops per second over the window (6 x the parameters that multiply
each token x tokens per second) as a share of the chips' bf16 peak."""


def read(run):
    rate = run.flops_per_step * len(run.window_steps) / run.window_s
    return 100.0 * rate / (run.chips * run.peak_flops)
