"""Application decode: device microseconds a ``serve_step`` run under the
named scope ``xlb_decode`` (``decode_step`` through ``argmax``), the union
of its ops' intervals."""

from bench import phases


def read(run):
    if run.phases is None:
        return None
    return phases.readings(run.phases)["decode_us"]
