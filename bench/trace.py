"""From the profiler's trace of a steady window to the numbers the
per-layer readers take.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain dict of events (device ops, device module runs, the harness's host
spans); ``reduce`` turns such a dict into a ``Summary``.  The dict is what
``bench/tests/data`` keeps of a recorded trace, so the reduction is tested
on the chip's own events without a chip.

Device events are found by stable names: the kernels' own names
(``KERNELS``) and the jitted ``serve_step``.  The traced window runs from
the start of the first host ``tick`` span to the end of the last.
"""

from __future__ import annotations

import bisect
import dataclasses
from pathlib import Path

KERNELS = ("xlb_admit_commit", "xlb_complete")
STEP = "serve_step"
SPANS = ("tick", "submit", "serve_step")
TOP = 10


def newest_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _kernel_of(name: str, stats) -> str | None:
    for k in KERNELS:
        if k in name:
            return k
    for _, v in stats:
        if isinstance(v, str):
            for k in KERNELS:
                if k in v:
                    return k
    return None


def _op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` → ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def extract(path) -> dict:
    """Events of one trace file: ``ops`` [[name, kernel or None, start_ns,
    dur_ns]], ``modules`` [[name, start_ns, dur_ns]] from the first device
    plane, and ``host`` [[name, start_ns, dur_ns]] of the harness's spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {"device": None, "ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and out["device"] is None:
            out["device"] = plane.name
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out["ops"] += [[_op_name(e.name),
                                    _kernel_of(e.name, e.stats),
                                    e.start_ns, e.duration_ns]
                                   for e in line.events]
                elif line.name == "XLA Modules":
                    out["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events if e.name in SPANS]
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


def reduce(ev: dict) -> Summary | None:
    """The window's summary, or None where the trace holds no tick span or
    no device plane."""
    ticks = sorted((s, s + d) for n, s, d in ev["host"] if n == "tick")
    if not ticks or ev["device"] is None:
        return None
    lo, hi = ticks[0][0], ticks[-1][1]
    busy = _union(_clip([[s, s + d] for _, _, s, d in ev["ops"]], lo, hi))
    kernels = {k: {"launches": 0, "seconds": 0.0} for k in KERNELS}
    per_op: dict = {}
    for name, k, s, d in ev["ops"]:
        if not (lo <= s < hi):
            continue
        per_op[name] = per_op.get(name, 0.0) + d * 1e-9
        if k is not None:
            kernels[k]["launches"] += 1
            kernels[k]["seconds"] += d * 1e-9
    n_steps = sum(1 for n, s, _ in ev["modules"]
                  if STEP in n and lo <= s < hi)
    # the window cut at every host span boundary, each piece named by the
    # innermost span over it; device idle time is summed per name
    by_name = {n: sorted((s, s + d) for m, s, d in ev["host"] if m == n)
               for n in SPANS}
    starts = {n: [a for a, _ in v] for n, v in by_name.items()}

    def covering(t: float) -> str:
        for n in ("serve_step", "submit", "tick"):     # innermost first
            i = bisect.bisect_right(starts[n], t) - 1
            if i >= 0 and t < by_name[n][i][1]:
                return n
        return "harness"

    cuts = sorted({lo, hi} | {t for v in by_name.values() for a, b in v
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
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(e - s for s, e in busy) * 1e-9,
        n_ticks=len(ticks), n_steps=n_steps, kernels=kernels,
        top_ops=[[n, v] for n, v in top],
        idle_gaps=sorted(([n, v] for n, v in gaps.items()),
                         key=lambda kv: -kv[1])[:TOP])
