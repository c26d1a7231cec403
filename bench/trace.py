"""From the profiler's trace of a steady window to the numbers the
per-layer readers take.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain dict of events (device ops with their kernel and named scope,
device module runs, the harness's host spans, the program's ``loop.*``
spans with their counters); ``reduce`` turns such a dict into a
``Summary``, and ``bench.phases.reduce`` into a ``PhaseSummary``.  The
dict is what ``bench/tests/data`` keeps of a recorded trace, so the
reductions are tested on the chip's own events without a chip.

Device events are found by stable names: the kernels' own names (the
kernels whose cost ``bench/costs`` counts for the deployment; an op is a
kernel's launch where its instruction is named the kernel, with or
without a ``.<n>`` suffix) and the jitted ``serve_step``.  The traced
window runs from the start of the first host ``tick`` span to the end of
the last.
"""

from __future__ import annotations

import bisect
import dataclasses
from pathlib import Path

STEP = "serve_step"
SPANS = ("tick", "submit", "serve_step")
PHASE = "loop."              # the program's host spans start so
# innermost first: a piece of the window is named by the first span over
# it, the program's phases between these two
INNER, OUTER = ("serve_step",), ("submit", "tick")
TOP = 10


def newest_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _kernel_of(op: str, kernels) -> str | None:
    """The kernel that the instruction ``op`` launches: ``op`` itself or
    ``op`` less a ``.<n>`` suffix, where that is one of ``kernels``."""
    base, _, n = op.rpartition(".")
    name = base if base and n.isdigit() else op
    return name if name in kernels else None


def _op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` → ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def extract(path, names: dict | None = None, kernels=()) -> dict:
    """Events of one trace file: ``ops`` [[name, kernel or None, start_ns,
    dur_ns]] (the kernel one of ``kernels`` by the op's name) and
    ``scopes`` (each op's named scope or None, in the order of ``ops``,
    from ``names``: instruction name → scope, as
    ``bench.phases.scope_names`` gives it) and ``modules`` [[name,
    start_ns, dur_ns]] from the first device plane; ``kernels`` as given;
    ``host`` [[name, start_ns, dur_ns]] of the harness's spans and
    ``phases`` [[name, start_ns, dur_ns, {counter: value}]] of the
    program's ``loop.*`` spans with their numeric metadata."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    names = names or {}
    out = {"device": None, "kernels": list(kernels), "ops": [],
           "scopes": [], "modules": [], "host": [], "phases": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and out["device"] is None:
            out["device"] = plane.name
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        op = _op_name(e.name)
                        out["ops"].append([op, _kernel_of(op, kernels),
                                           e.start_ns, e.duration_ns])
                        out["scopes"].append(names.get(op))
                elif line.name == "XLA Modules":
                    out["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        out["host"].append([e.name, e.start_ns,
                                            e.duration_ns])
                    elif e.name.startswith(PHASE):
                        out["phases"].append(
                            [e.name, e.start_ns, e.duration_ns,
                             {k: v for k, v in e.stats
                              if isinstance(v, (int, float))}])
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    n_ticks: int               # host tick spans in the window
    n_steps: int               # serve_step runs on the device
    kernels: dict              # kernel → {"launches", "seconds"}
    top_ops: list              # [[name, seconds]] by total device time
    idle_gaps: list            # [[host span, seconds]] of device idle


def _union(iv: list) -> list:
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in iv if e > lo and s < hi]


def idle_by_span(spans: dict, busy: list, lo: float, hi: float) -> dict:
    """Device idle seconds in ``[lo, hi)`` by host span: the window cut at
    every span boundary, each piece named by the innermost span over it
    (``spans``: name → sorted [(start, end)]; ``INNER`` first, then any
    other name, then ``OUTER``), ``harness`` where none is; ``busy`` the
    device's busy intervals, sorted and disjoint."""
    cover = INNER + tuple(sorted(set(spans) - set(INNER + OUTER))) + OUTER
    starts = {n: [a for a, _ in v] for n, v in spans.items()}

    def covering(t: float) -> str:
        for n in cover:
            v = spans.get(n, ())
            i = bisect.bisect_right(starts.get(n, ()), t) - 1
            if i >= 0 and t < v[i][1]:
                return n
        return "harness"

    cuts = sorted({lo, hi} | {t for v in spans.values() for a, b in v
                              for t in (a, b) if lo < t < hi})
    idle, edge = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > edge:
            idle.append((edge, s))
        edge = max(edge, e)
    gaps: dict = {}
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        name = covering((a + b) / 2)
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < b:
            over = min(b, idle[k][1]) - max(a, idle[k][0])
            gaps[name] = gaps.get(name, 0.0) + over * 1e-9
            k += 1
    return gaps


def reduce(ev: dict) -> Summary | None:
    """The window's summary, or None where the trace holds no tick span or
    no device plane."""
    ticks = sorted((s, s + d) for n, s, d in ev["host"] if n == "tick")
    if not ticks or ev["device"] is None:
        return None
    lo, hi = ticks[0][0], ticks[-1][1]
    busy = _union(_clip([[s, s + d] for _, _, s, d in ev["ops"]], lo, hi))
    kernels = {k: {"launches": 0, "seconds": 0.0}
               for k in ev.get("kernels", ())}
    per_op: dict = {}
    for name, k, s, d in ev["ops"]:
        if not (lo <= s < hi):
            continue
        per_op[name] = per_op.get(name, 0.0) + d * 1e-9
        if k is not None:
            kernels.setdefault(k, {"launches": 0, "seconds": 0.0})
            kernels[k]["launches"] += 1
            kernels[k]["seconds"] += d * 1e-9
    n_steps = sum(1 for n, s, _ in ev["modules"]
                  if STEP in n and lo <= s < hi)
    by_name = {n: sorted((s, s + d) for m, s, d in ev["host"] if m == n)
               for n in SPANS}
    gaps = idle_by_span(by_name, busy, lo, hi)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(e - s for s, e in busy) * 1e-9,
        n_ticks=len(ticks), n_steps=n_steps, kernels=kernels,
        top_ops=[[n, v] for n, v in top],
        idle_gaps=sorted(([n, v] for n, v in gaps.items()),
                         key=lambda kv: -kv[1])[:TOP])
