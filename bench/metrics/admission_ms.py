"""Host loop: milliseconds a tick in the program's ``loop.admission`` span
(matured releases, parse, batch, host→device copies), over the traced
window's ticks."""

from bench import phases


def read(run):
    if run.phases is None:
        return None
    return phases.readings(run.phases)["admission_ms"]
