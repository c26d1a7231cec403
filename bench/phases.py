#!/usr/bin/env python3
"""The program's own spans, counters and named scopes in a profiler trace,
reduced to numbers per phase of the host loop and per scope of the step.

``ServeLoop.tick`` writes five host spans a tick, one after another
(``PHASES``), with counters as their metadata: ``rows`` (requests taken
into the batch) and ``batch`` (its width) on ``loop.admission``, ``held``
(rows that came back unserviced) on ``loop.bookkeeping``.  ``serve_step``
runs its admission gate and branch under the named scope ``xlb_admit`` and
its decode under ``xlb_decode`` (``SCOPES``).  A device op event of the
trace carries the instruction's name but not its ``op_name`` metadata
(jax 0.9.0 on a TPU v5e gives it only its offset and duration), so the
scopes come from the compiled module's text: ``scope_names`` maps each
instruction to the first scope on its ``op_name`` path.

``extract`` keeps what ``bench.trace.extract`` keeps, in the same form,
and adds ``phases`` ([[name, start_ns, dur_ns, {counter: value}]]) and
``scopes`` (each op's scope or None, in the order of ``ops``), so that
``bench.trace.reduce`` reads its events unchanged.  ``reduce`` gives a
``PhaseSummary``; ``readings`` the per-layer numbers it supports, each
None where the trace holds nothing to read (a program without the spans or
scopes).  One traced run of a cell on the chip:

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \\
        [--dump DIR]

prints one JSON object: the phases, scopes, counters, refined idle gaps,
the window's slow ticks by phase, and the readings.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import re
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace  # noqa: E402

PHASES = ("loop.control", "loop.admission", "loop.dispatch",
          "loop.readback", "loop.bookkeeping")
SCOPES = ("xlb_admit", "xlb_decode")
UNSCOPED = "unscoped"
# innermost first: a piece of the window is named by the first of these
# whose span covers it
COVER = ("serve_step",) + PHASES + ("submit", "tick")
SLOW_TICK_S = 0.05           # a tick this long is listed with its phases


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$')
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def scope_names(hlo_text: str) -> dict:
    """Instruction name → the first of ``SCOPES`` on its ``op_name`` path
    (None where there is none), for every instruction of a compiled
    module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            o = _OP_NAME.search(m.group(2))
            parts = o.group(1).split("/") if o else ()
            out[m.group(1)] = next((s for s in SCOPES if s in parts), None)
    return out


def compiled_text(jitted, *args) -> str:
    """The optimized module of ``jitted`` at ``args``, compiled afresh: the
    compile caches' keys leave out ``op_name`` metadata, so a cached
    executable may carry another build's scopes."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    return jitted.lower(*args).compile().as_text()


def extract(path, names: dict) -> dict:
    """Events of one trace file: ``bench.trace.extract``'s, plus the
    program's phase spans with their numeric metadata and each op's scope
    by its instruction name (``names``, from ``scope_names``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {"device": None, "ops": [], "modules": [], "host": [],
           "phases": [], "scopes": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and out["device"] is None:
            out["device"] = plane.name
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name = trace._op_name(e.name)
                        out["ops"].append([name,
                                           trace._kernel_of(e.name, e.stats),
                                           e.start_ns, e.duration_ns])
                        out["scopes"].append(names.get(name))
                elif line.name == "XLA Modules":
                    out["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in trace.SPANS:
                        out["host"].append([e.name, e.start_ns,
                                            e.duration_ns])
                    elif e.name in PHASES:
                        out["phases"].append(
                            [e.name, e.start_ns, e.duration_ns,
                             {k: v for k, v in e.stats
                              if isinstance(v, (int, float))}])
    return out


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
    #                            innermost of ``COVER`` over it
    slow_ticks: list           # [[start_s in window, seconds,
    #                            {phase: seconds}]] of ticks over
    #                            ``SLOW_TICK_S``


def _seconds(iv: list) -> float:
    return sum(e - s for s, e in iv) * 1e-9


def _idle_by_span(spans: dict, busy: list, lo: float, hi: float) -> dict:
    """Device idle seconds in ``[lo, hi)`` named by the innermost span of
    ``COVER`` (``spans``: name → sorted [(start, end)]) over each piece,
    ``harness`` where none is."""
    starts = {n: [a for a, _ in v] for n, v in spans.items()}

    def covering(t: float) -> str:
        for n in COVER:
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
    spans = {n: [] for n in COVER}
    for n, s, d in ev["host"]:
        spans[n].append((s, s + d))
    for n, s, d, meta in ev.get("phases", ()):
        spans[n].append((s, s + d))
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
    gaps = _idle_by_span(spans, busy, lo, hi)
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
    four phases, device µs a ``serve_step`` run under each scope, the share
    of the admitting batches' rows filled, and the share of rows taken that
    came back held."""
    c = s.counters
    return {
        "admission_ms": _per_tick_ms(s, "loop.admission"),
        "dispatch_ms": _per_tick_ms(s, "loop.dispatch"),
        "readback_ms": _per_tick_ms(s, "loop.readback"),
        "bookkeeping_ms": _per_tick_ms(s, "loop.bookkeeping"),
        "admit_scope_us": _per_step_us(s, "xlb_admit"),
        "decode_us": _per_step_us(s, "xlb_decode"),
        "admit_fill_pct": _pct(c["rows"], c["batch_admitting"]),
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
    from bench import deploy, driver, run, traffic
    cell = run.load_cell(args.workload)
    jax = run.setup_jax()
    run.device_info(jax, cell.chips, require_chip=True)
    mix = traffic.Mix(cell.mix, args.seed, cell.config["application"]["vocab"])
    dep = deploy.build(cell.config, mix.max_len, args.seed)
    trace_dir = run.OUT / "phases" / f"{args.workload}-{args.seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    drv = driver.Driver(dep, mix, args.seconds, trace_dir=trace_dir)
    drv.compile()
    log = drv.run()
    run.log(f"host stalls: {log.stalls()}")
    loop = dep.loop
    names = scope_names(compiled_text(drv.rec.step, loop.params, loop.state,
                                      drv.rec.samples[0][1]))
    ev = extract(trace.newest_xplane(trace_dir), names)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if args.dump is not None:
        args.dump.mkdir(parents=True, exist_ok=True)
        (args.dump / f"{args.workload}-{args.seed}.json").write_text(
            json.dumps(ev))
    base, s = trace.reduce(ev), reduce(ev)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "tick_ms": base.window_s / base.n_ticks * 1e3,
        "window_s": s.window_s, "busy_s": s.busy_s, "n_ticks": s.n_ticks,
        "n_steps": s.n_steps, "phases": s.phases, "scopes": s.scopes,
        "counters": s.counters, "idle_gaps": s.idle_gaps,
        "harness_idle_gaps": base.idle_gaps, "slow_ticks": s.slow_ticks,
        "ops_not_in_module": sum(o[0] not in names for o in ev["ops"]),
        "readings": readings(s)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
