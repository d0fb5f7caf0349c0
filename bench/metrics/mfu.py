"""Model FLOPs of the window's steps (the configuration's count,
``reference.<name>.flops_per_step``) over the window, as a share of the
chip's bf16 peak (``bench/peaks.json``)."""


def read(run):
    flops = len(run.window_steps) * run.flops_per_step
    return 100.0 * flops / run.window_s / run.peak_flops
