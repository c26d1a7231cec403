#!/usr/bin/env python3
"""The program's own spans, counters and named scopes in a profiler trace,
reduced to numbers per phase of the host loop and per scope of the step.

``ServeLoop.tick`` writes its host spans a tick, one after another, each
named ``loop.<phase>`` (today's five: ``PHASES``), with counters as their
metadata: ``rows`` (requests taken into the batch) and ``batch`` (its
width) on ``loop.admission``, ``held`` (rows that came back unserviced)
on ``loop.bookkeeping``.  ``serve_step`` runs its admission gate and
branch under the named scope ``xlb_admit`` and its decode under
``xlb_decode``.  A device op event of the trace carries the instruction's
name but not its ``op_name`` metadata (jax 0.9.0 on a TPU v5e gives it
only its offset and duration), so the scopes come from the text of the
module that ran (``compiled_text``): ``scope_names`` maps each
instruction to the first ``xlb_*`` scope on its ``op_name`` path.

``bench.trace.extract`` keeps the ``loop.*`` spans with their numeric
metadata (``phases``) and each op's scope (``scopes``); ``reduce`` gives a
``PhaseSummary``; ``readings`` the per-layer numbers it supports, each
None where the trace holds nothing to read (a program without the spans or
scopes), which ``bench/metrics`` report.  One traced run of a cell on the
chip (``bench/run.py --trace 1``) with what its result line leaves out:

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \\
        [--dump DIR]

prints one JSON object: the seconds of each phase and scope (the unscoped
ops too), the counters, the device's idle time named by the phase over it,
and the window's slow ticks by phase.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace  # noqa: E402

PHASES = ("loop.control", "loop.admission", "loop.dispatch",
          "loop.readback", "loop.bookkeeping")
SCOPE = "xlb_"               # the program's named scopes start so
UNSCOPED = "unscoped"
SLOW_TICK_S = 0.05           # a tick this long is listed with its phases


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$')
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def scope_names(hlo_text: str) -> dict:
    """Instruction name → the first ``xlb_*`` scope on its ``op_name``
    path, the op's own name at its end left out (None where there is
    none), for every instruction of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            o = _OP_NAME.search(m.group(2))
            parts = o.group(1).split("/")[:-1] if o else ()
            out[m.group(1)] = next((s for s in parts
                                    if s.startswith(SCOPE)), None)
    return out


def compiled_text(jitted, *args) -> str:
    """The optimized module of ``jitted`` at ``args`` as this process runs
    it: the executable that served the window, compiled at set-up or
    loaded from the persistent cache (whose text keeps each instruction's
    ``op_name``), found again in memory.  It compiles nothing and leaves
    the compile caches and their settings as they are."""
    return jitted.lower(*args).compile().as_text()


@dataclasses.dataclass
class PhaseSummary:
    window_s: float
    busy_s: float
    n_ticks: int               # host tick spans in the window
    n_steps: int               # serve_step runs on the device
    phases: dict               # phase → {"count", "seconds"} (host)
    scopes: dict               # scope or "unscoped" → device seconds,
    #                            the union of its ops' intervals, an op
    #                            counted under its outermost op's scope
    counters: dict             # rows, batch, held summed over the
    #                            window's spans; batch_admitting: batch
    #                            over the spans that took rows
    idle_gaps: list            # [[span, seconds]] of device idle, by the
    #                            innermost span over it
    slow_ticks: list           # [[start_s in window, seconds,
    #                            {phase: seconds}]] of ticks over
    #                            ``SLOW_TICK_S``


def _seconds(iv: list) -> float:
    return sum(e - s for s, e in iv) * 1e-9


def _outermost_scopes(ops: list, scopes: list | None) -> list:
    """Each op interval with the scope of the outermost op event it runs
    in: ops the compiler placed inside the admission ``conditional`` or
    the decode ``while`` (copies, reshapes, a hoisted convert) are that
    op's device time, whatever their own metadata says."""
    scopes = scopes or [None] * len(ops)
    out, end, outer = [], None, None
    for (s, e), sc in sorted(zip(map(tuple, ops), scopes),
                             key=lambda x: (x[0][0], -x[0][1])):
        if end is None or s >= end:
            end, outer = e, sc
        else:
            end = max(end, e)
        out.append(([s, e], outer))
    return out


def reduce(ev: dict) -> PhaseSummary | None:
    """The window's phases and scopes, or None where the trace holds no
    tick span or no device plane.  The window is ``bench.trace.reduce``'s:
    the first tick span's start to the last one's end."""
    ticks = sorted((s, s + d) for n, s, d in ev["host"] if n == "tick")
    if not ticks or ev["device"] is None:
        return None
    lo, hi = ticks[0][0], ticks[-1][1]
    ops = [[s, s + d] for _, _, s, d in ev["ops"]]
    busy = trace._union(trace._clip(ops, lo, hi))
    by_scope: dict = {}
    for iv, sc in _outermost_scopes(ops, ev.get("scopes")):
        by_scope.setdefault(sc or UNSCOPED, []).append(iv)
    scopes = {k: _seconds(trace._union(trace._clip(v, lo, hi)))
              for k, v in by_scope.items()}
    phases: dict = {}
    counters = {"rows": 0, "batch": 0, "held": 0, "batch_admitting": 0}
    spans: dict = {}
    for n, s, d in ev["host"]:
        spans.setdefault(n, []).append((s, s + d))
    for n, s, d, meta in ev.get("phases", ()):
        spans.setdefault(n, []).append((s, s + d))
        if not lo <= s < hi:
            continue
        p = phases.setdefault(n, {"count": 0, "seconds": 0.0})
        p["count"] += 1
        p["seconds"] += d * 1e-9
        for k in ("rows", "batch", "held"):
            counters[k] += int(meta.get(k, 0))
        if meta.get("rows", 0) > 0:
            counters["batch_admitting"] += int(meta.get("batch", 0))
    for v in spans.values():
        v.sort()
    n_steps = sum(1 for n, s, _ in ev["modules"]
                  if trace.STEP in n and lo <= s < hi)
    slow = []
    for a, b in ticks:
        if (b - a) * 1e-9 > SLOW_TICK_S:
            inside = {}
            for n, s, d, _ in ev.get("phases", ()):
                if a <= s < b:
                    inside[n] = inside.get(n, 0.0) + d * 1e-9
            slow.append([(a - lo) * 1e-9, (b - a) * 1e-9, inside])
    gaps = trace.idle_by_span(spans, busy, lo, hi)
    return PhaseSummary(
        window_s=(hi - lo) * 1e-9, busy_s=_seconds(busy),
        n_ticks=len(ticks), n_steps=n_steps, phases=phases, scopes=scopes,
        counters=counters,
        idle_gaps=sorted(([n, v] for n, v in gaps.items()),
                         key=lambda kv: -kv[1]),
        slow_ticks=slow)


def _per_tick_ms(s: PhaseSummary, phase: str) -> float | None:
    p = s.phases.get(phase)
    return p["seconds"] / s.n_ticks * 1e3 if p else None


def _per_step_us(s: PhaseSummary, scope: str) -> float | None:
    v = s.scopes.get(scope, 0.0)
    return v / s.n_steps * 1e6 if v > 0 and s.n_steps else None


def _pct(num: int, den: int) -> float | None:
    return 100.0 * num / den if den else None


def readings(s: PhaseSummary) -> dict:
    """The per-layer numbers of a traced window, by name: host ms a tick of
    four phases, device µs a ``serve_step`` run under each scope, and the
    share of rows taken that came back held."""
    c = s.counters
    return {
        "admission_ms": _per_tick_ms(s, "loop.admission"),
        "dispatch_ms": _per_tick_ms(s, "loop.dispatch"),
        "readback_ms": _per_tick_ms(s, "loop.readback"),
        "bookkeeping_ms": _per_tick_ms(s, "loop.bookkeeping"),
        "admit_scope_us": _per_step_us(s, "xlb_admit"),
        "decode_us": _per_step_us(s, "xlb_decode"),
        "held_pct": _pct(c["held"], c["rows"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", type=Path, default=None,
                    help="keep the extracted events here")
    args = ap.parse_args(argv)
    from bench import run
    dump = args.dump or run.OUT / "phases"
    try:
        run.execute(args.workload, args.seed, args.seconds, True, dump=dump)
    except run.NoChip as e:
        run.log(f"phases: {e}")
        return 2
    s = reduce(json.loads(
        (dump / f"{args.workload}-{args.seed}.json").read_text()))
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "phases": s.phases,
        "scopes": s.scopes, "counters": s.counters, "idle_gaps": s.idle_gaps,
        "slow_ticks": s.slow_ticks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
