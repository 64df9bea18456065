"""Model FLOP utilization of the whole train step over the window: model
FLOPs per step (3x the forward, recomputation not counted) times the
window's steps, over the window's wall time, over the chips' bf16 peak."""


def read(run):
    peak = run.peak()["bf16_flops_per_s"]
    return (100.0 * run.flops_per_step * run.window_steps
            / run.window_s / (run.chips * peak))
