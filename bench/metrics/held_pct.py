"""Admit-commit kernel: share of the rows taken into admission that came
back unserviced (no free slot on the picked instance, or no route), from
the program's counters (Σ ``held`` on ``loop.bookkeeping`` over Σ
``rows`` on ``loop.admission``)."""

from bench import phases


def read(run):
    if run.phases is None:
        return None
    return phases.readings(run.phases)["held_pct"]
