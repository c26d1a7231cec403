"""Device microseconds per launch of the ``xlb_admit_commit`` kernel."""


def read(run):
    k = run.summary.kernels["xlb_admit_commit"]
    return k["seconds"] / k["launches"] * 1e6 if k["launches"] else None
