"""The reduction of the program's phase spans, counters and named scopes,
on hand-made events and on recorded traces of the chip."""

import json
from pathlib import Path

import pytest

from bench import phases, registry, run, trace

DATA = Path(__file__).resolve().parent / "data"


def _events():
    """Two ticks (0-100, 150-250 ns) with a submit span between them, each
    split into the loop's five phases (the first leaves 95-100 to the tick
    alone); the first admits 3 rows of 8 and holds one, the second admits
    none.  Device ops: a scoped admission gate holding the kernel, an
    unscoped copy and a decode-scoped reshape (all three its time), a
    scoped decode loop holding a fusion, and two unscoped ops."""
    ph = [["loop.control", 0, 5, {}],
          ["loop.admission", 5, 15, {"rows": 3, "batch": 8}],
          ["loop.dispatch", 20, 30, {}],
          ["loop.readback", 50, 30, {}],
          ["loop.bookkeeping", 80, 15, {"held": 1}],
          ["loop.control", 150, 2, {}],
          ["loop.admission", 152, 8, {"rows": 0, "batch": 8}],
          ["loop.dispatch", 160, 20, {}],
          ["loop.readback", 180, 50, {}],
          ["loop.bookkeeping", 230, 18, {"held": 0}]]
    ops = [["conditional", None, 25, 20, "xlb_admit"],
           ["xlb_admit_commit", "xlb_admit_commit", 30, 10, "xlb_admit"],
           ["copy.2", None, 40, 4, None],
           ["reshape.1", None, 27, 2, "xlb_decode"],
           ["while.2", None, 50, 30, "xlb_decode"],
           ["fusion.3", None, 60, 10, "xlb_decode"],
           ["custom-call.3", "xlb_complete", 82, 4, None],
           ["copy.1", None, 86, 2, None],
           ["while.2", None, 185, 20, "xlb_decode"],
           ["fusion.9", None, 300, 10, None]]        # after the window
    return {
        "device": "/device:TPU:0",
        "ops": [o[:4] for o in ops],
        "scopes": [o[4] for o in ops],
        "modules": [["jit_serve_step(1)", 25, 63],
                    ["jit_serve_step(1)", 185, 20]],
        "host": [["tick", 0, 100], ["serve_step", 22, 26],
                 ["submit", 100, 50], ["tick", 150, 100],
                 ["serve_step", 161, 18]],
        "phases": ph,
    }


def test_phases_scopes_and_counters():
    s = phases.reduce(_events())
    assert s.window_s == pytest.approx(250e-9)
    assert s.busy_s == pytest.approx(76e-9)   # [25,45] [50,80] [82,88]
    #                                           [185,205]
    assert s.n_ticks == 2 and s.n_steps == 2
    want = {"loop.control": 7, "loop.admission": 23, "loop.dispatch": 50,
            "loop.readback": 80, "loop.bookkeeping": 33}
    assert set(s.phases) == set(want)
    for n, v in want.items():
        assert s.phases[n]["count"] == 2
        assert s.phases[n]["seconds"] == pytest.approx(v * 1e-9)
    # unions, not sums, each op under its outermost op's scope: the gate
    # holds the kernel, a copy and a reshape, the loop the fusion
    assert s.scopes == {"xlb_admit": pytest.approx(20e-9),
                        "xlb_decode": pytest.approx(50e-9),
                        "unscoped": pytest.approx(6e-9)}
    assert sum(s.scopes.values()) == pytest.approx(s.busy_s)
    assert s.counters == {"rows": 3, "batch": 16, "held": 1,
                          "batch_admitting": 8}
    assert s.slow_ticks == []


def test_idle_gaps_named_by_phase_keep_their_total():
    ev = _events()
    s = phases.reduce(ev)
    gaps = dict(s.idle_gaps)
    assert gaps == {"loop.control": pytest.approx(7e-9),
                    "loop.admission": pytest.approx(23e-9),
                    "loop.dispatch": pytest.approx(4e-9 + 2e-9),
                    "serve_step": pytest.approx(6e-9 + 18e-9),
                    "loop.readback": pytest.approx(30e-9),
                    "loop.bookkeeping": pytest.approx(9e-9 + 18e-9),
                    "tick": pytest.approx(5e-9 + 2e-9),
                    "submit": pytest.approx(50e-9)}
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    # the harness's own reduction names the same idle time ``tick`` where
    # a phase now names it
    base = dict(trace.reduce(ev).idle_gaps)
    folded = sum(v for n, v in gaps.items() if n.startswith("loop."))
    assert base["tick"] == pytest.approx(gaps["tick"] + folded)
    assert base["serve_step"] == pytest.approx(gaps["serve_step"])
    assert base["submit"] == pytest.approx(gaps["submit"])


def test_readings_on_hand_made_events():
    r = phases.readings(phases.reduce(_events()))
    assert r == {"admission_ms": pytest.approx(23e-9 / 2 * 1e3),
                 "dispatch_ms": pytest.approx(50e-9 / 2 * 1e3),
                 "readback_ms": pytest.approx(80e-9 / 2 * 1e3),
                 "bookkeeping_ms": pytest.approx(33e-9 / 2 * 1e3),
                 "admit_scope_us": pytest.approx(20e-9 / 2 * 1e6),
                 "decode_us": pytest.approx(50e-9 / 2 * 1e6),
                 "held_pct": pytest.approx(100 * 1 / 3)}


def test_a_slow_tick_is_listed_with_its_phases():
    ev = _events()
    ev["host"][3] = ["tick", 150, 60_000_100]
    ev["phases"][-2] = ["loop.readback", 180, 60_000_000, {}]
    s = phases.reduce(ev)
    assert len(s.slow_ticks) == 1
    start, seconds, inside = s.slow_ticks[0]
    assert start == pytest.approx(150e-9)
    assert seconds == pytest.approx(60_000_100e-9)
    assert inside["loop.readback"] == pytest.approx(0.06)


def test_a_program_without_spans_or_scopes_reads_nothing():
    """The parent program's trace: no phase spans, no scopes, four-field
    ops; the reduction runs and every reading is None."""
    ev = _events()
    del ev["phases"], ev["scopes"]
    s = phases.reduce(ev)
    assert s.phases == {} and s.scopes == {"unscoped": pytest.approx(76e-9)}
    assert all(v is None for v in phases.readings(s).values())
    assert phases.reduce(dict(ev, device=None)) is None


HLO = """\
ENTRY %main.9 (p.1: s32[256]) -> (s32[50,8]) {
  %compare_reduce_fusion = pred[]{:T(512)} fusion(s32[256]{0} %p.1), kind=kLoop, calls=%fc, metadata={op_name="jit(serve_step)/xlb_admit/reduce_or" source_file="a.py"}
  %conditional = (s32[512]{0}) conditional(pred[] %compare_reduce_fusion), branch_computations={%b0, %b1}, metadata={op_name="jit(serve_step)/xlb_admit/cond"}
  %xlb_admit_commit.2 = (s32[512]{0}) custom-call(s32[256]{0} %p.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(serve_step)/xlb_admit/cond/branch_1_fun/jit(_admit_commit)/xlb_admit_commit"}
  %while.2 = (s32[], bf16[400,128]{1,0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(serve_step)/xlb_decode/while" source_file="m.py"}
  %copy.176 = s32[50,8]{1,0} copy(s32[50,8]{1,0} %gte)
  %dot.1 = f32[2]{0} dot(f32[2]{0} %x, f32[2]{0} %y), metadata={op_name="jit(serve_step)/params[\\'blocks\\']/dot"}
  ROOT %fusion.9 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, metadata={op_name="jit(serve_step)/xlb_decode/argmax"}
  %xlb_complete.1 = s32[50,8]{1,0} custom-call(), metadata={op_name="jit(serve_step)/jit(_complete)/xlb_complete"}
}
"""


def test_scopes_are_named_from_the_compiled_module():
    names = phases.scope_names(HLO)
    assert names == {"compare_reduce_fusion": "xlb_admit",
                     "conditional": "xlb_admit",
                     "xlb_admit_commit.2": "xlb_admit",
                     "while.2": "xlb_decode", "copy.176": None,
                     "dot.1": None, "fusion.9": "xlb_decode",
                     "xlb_complete.1": None}


def test_a_new_scope_or_phase_is_read_without_an_edit():
    """Any ``xlb_*`` scope is named, the outermost on the path; any
    ``loop.*`` span is a phase, and names the idle time under it."""
    hlo = ('  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %x), metadata={op_name='
           '"jit(serve_step)/xlb_decode/xlb_moe/dot"}\n'
           '  %fusion.5 = f32[8]{0} fusion(f32[8]{0} %x), metadata={op_name='
           '"jit(serve_step)/xlb_moe"}\n')
    assert phases.scope_names(hlo) == {"fusion.4": "xlb_decode",
                                       "fusion.5": None}
    ev = _events()
    ev["phases"].append(["loop.relay", 95, 5, {"experts": 2}])
    s = phases.reduce(ev)
    assert s.phases["loop.relay"] == {"count": 1,
                                      "seconds": pytest.approx(5e-9)}
    gaps = dict(s.idle_gaps)
    # the relay takes the 95-100 ns that the first tick alone held
    assert gaps["loop.relay"] == pytest.approx(5e-9)
    assert gaps["tick"] == pytest.approx(2e-9)


def test_the_harness_recorded_trace_reduces_as_before():
    """``bench/tests/data/trace-*.json`` predates the program's spans: its
    window, busy time and idle gaps read as ``bench.trace`` reads them."""
    files = sorted(DATA.glob("trace-*.json"))
    assert files
    for f in files:
        ev = json.loads(f.read_text())["events"]
        base, s = trace.reduce(ev), phases.reduce(ev)
        assert s.window_s == pytest.approx(base.window_s)
        assert s.busy_s == pytest.approx(base.busy_s)
        assert s.n_ticks == base.n_ticks and s.n_steps == base.n_steps
        assert dict(s.idle_gaps) == pytest.approx(dict(base.idle_gaps))
        assert s.phases == {}


def _recorded():
    files = sorted(DATA.glob("phases-*.json"))
    assert files, "a recorded trace with the program's spans is kept"
    return [json.loads(f.read_text()) for f in files]


def test_recorded_phases_nest_in_ticks_on_the_device_clock():
    """Each tick holds the five phases in order; the device runs each
    ``serve_step`` after its tick's dispatch begins and before its
    readback ends, so host spans and device events share one clock."""
    for rec in _recorded():
        ev = rec["events"]
        ticks = sorted((s, s + d) for n, s, d in ev["host"] if n == "tick")
        spans = sorted(ev["phases"], key=lambda p: p[1])
        steps = sorted(m[1:] for m in ev["modules"] if trace.STEP in m[0])
        assert len(steps) == len(ticks) > 0
        for (a, b), (s0, sd) in zip(ticks, steps):
            mine = [p for p in spans if a <= p[1] < b]
            assert [p[0] for p in mine] == list(phases.PHASES)
            assert mine[-1][1] + mine[-1][2] <= b
            for x, y in zip(mine, mine[1:]):
                assert x[1] + x[2] <= y[1]
            dispatch, readback = mine[2], mine[3]
            assert dispatch[1] <= s0
            assert s0 + sd <= readback[1] + readback[2]


def test_recorded_scopes_partition_the_busy_time():
    """Under each op's outermost scope the three parts never overlap and
    together make the busy time."""
    for rec in _recorded():
        ev = rec["events"]
        s = phases.reduce(ev)
        lo = min(a for n, a, _ in ev["host"] if n == "tick")
        hi = max(a + d for n, a, d in ev["host"] if n == "tick")
        ops = [[o[2], o[2] + o[3]] for o in ev["ops"]]
        parts = {}
        for iv, sc in phases._outermost_scopes(ops, ev["scopes"]):
            parts.setdefault(sc, []).append(iv)
        parts = {k: trace._union(trace._clip(v, lo, hi))
                 for k, v in parts.items()}
        assert set(parts) == {"xlb_admit", "xlb_decode", None}
        for k, v in parts.items():
            for j, w in parts.items():
                if k != j:
                    assert not any(min(b, d) > max(a, c)
                                   for a, b in v for c, d in w)
        assert sum(s.scopes.values()) == pytest.approx(s.busy_s, rel=0.02)
        assert s.scopes["xlb_decode"] > s.scopes["xlb_admit"] > 0


def test_recorded_phases_summary_and_the_harness_reading():
    """The recorded summary reproduces, and the harness's own reduction
    reads the same kernels and window from the program's new trace."""
    for rec in _recorded():
        ev, want = rec["events"], rec["summary"]
        s, base = phases.reduce(ev), trace.reduce(ev)
        assert s.n_ticks == want["n_ticks"] == base.n_ticks
        assert s.n_steps == want["n_steps"] == s.n_ticks
        assert s.window_s == pytest.approx(want["window_s"])
        assert s.busy_s == pytest.approx(want["busy_s"])
        for n, v in want["phases"].items():
            assert s.phases[n]["count"] == v["count"] == s.n_ticks
            assert s.phases[n]["seconds"] == pytest.approx(v["seconds"])
        assert s.scopes == pytest.approx(want["scopes"])
        assert s.counters == want["counters"]
        for k in ("xlb_admit_commit", "xlb_complete"):
            assert base.kernels[k]["launches"] == s.n_ticks
            assert base.kernels[k]["seconds"] == pytest.approx(
                want["kernels"][k]["seconds"])
        gaps = dict(s.idle_gaps)
        assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
        assert gaps.get("tick", 0.0) < 0.1 * dict(base.idle_gaps)["tick"]


READINGS = ("admission_ms", "dispatch_ms", "readback_ms", "bookkeeping_ms",
            "held_pct", "admit_scope_us", "decode_us")


@pytest.mark.parametrize("name", READINGS)
def test_each_reading_has_its_reader(name):
    """The per-layer reader of each reading returns what ``readings`` gives
    on the recorded trace with the program's spans, and nothing on the
    one without."""
    for rec in _recorded():
        ev = rec["events"]
        s = phases.reduce(ev)
        lr = run.LayerRun(trace.reduce(ev), None, {}, 0.0, s)
        got = registry.load("metrics", name).read(lr)
        assert got is not None
        assert got == phases.readings(s)[name]
    old = json.loads(sorted(DATA.glob("trace-*.json"))[0].read_text())
    ev = old["events"]
    lr = run.LayerRun(trace.reduce(ev), None, {}, 0.0, phases.reduce(ev))
    assert registry.load("metrics", name).read(lr) is None
    assert registry.load("metrics", name).read(
        run.LayerRun(trace.reduce(ev), None, {}, 0.0)) is None


def test_the_readings_are_per_layer_metrics_of_every_cell():
    bm = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bm["per_layer"]}
    for name in READINGS:
        assert "workloads" not in entries[name]
        assert entries[name]["moves"] == "p50_latency_ms"
    assert set(phases.readings(phases.reduce(_events()))) == set(READINGS)
