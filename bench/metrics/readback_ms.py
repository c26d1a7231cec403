"""Host loop: milliseconds a tick in the program's ``loop.readback`` span
(the blocking device→host read of the step's outputs), over the traced
window's ticks."""

from bench import phases


def read(run):
    if run.phases is None:
        return None
    return phases.readings(run.phases)["readback_ms"]
