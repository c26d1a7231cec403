"""Engine step (``serve_step``): operations of the window's ticks (decode
FLOPs over every pool slot plus both kernels' counted operations per
launch) over the window times the chip's bf16 peak."""


def read(run):
    s = run.summary
    if not s.n_steps or s.window_s <= 0:
        return None
    ops = run.step_flops * s.n_steps + sum(
        cost[0] * s.kernels[k]["launches"] for k, cost in run.costs.items())
    return 100.0 * ops / (s.window_s * run.peaks["bf16_flops"])
