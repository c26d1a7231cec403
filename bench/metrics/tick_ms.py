"""Host loop (``ServeLoop.tick``): milliseconds per tick over the traced
window, from the harness's ``tick`` spans."""


def read(run):
    s = run.summary
    return s.window_s / s.n_ticks * 1e3 if s.n_ticks else None
