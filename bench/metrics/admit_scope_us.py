"""Engine step: device microseconds a ``serve_step`` run under the named
scope ``xlb_admit`` (the admission gate, its predicate and branch with the
admit-commit kernel), the union of its ops' intervals."""

from bench import phases


def read(run):
    if run.phases is None:
        return None
    return phases.readings(run.phases)["admit_scope_us"]
