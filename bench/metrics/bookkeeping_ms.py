"""Host loop: milliseconds a tick in the program's ``loop.bookkeeping``
span (served tokens, finished and held requests), over the traced
window's ticks."""

from bench import phases


def read(run):
    if run.phases is None:
        return None
    return phases.readings(run.phases)["bookkeeping_ms"]
