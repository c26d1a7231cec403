"""Host loop: milliseconds a tick in the program's ``loop.dispatch`` span
(the ``serve_step`` call), over the traced window's ticks."""

from bench import phases


def read(run):
    if run.phases is None:
        return None
    return phases.readings(run.phases)["dispatch_ms"]
