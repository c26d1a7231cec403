"""Device microseconds per launch of the ``xlb_complete`` kernel."""


def read(run):
    k = run.summary.kernels["xlb_complete"]
    return k["seconds"] / k["launches"] * 1e6 if k["launches"] else None
