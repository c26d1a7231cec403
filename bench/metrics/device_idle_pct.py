"""Device: share of the traced window in which no operation ran on it."""


def read(run):
    s = run.summary
    return 100.0 * (1.0 - s.busy_s / s.window_s) if s.window_s > 0 else None
