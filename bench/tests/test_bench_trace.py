"""The reduction from trace events to per-layer numbers, on hand-made
events and on a recorded trace of the chip, and the peak table."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import costs, peaks, phases, registry, run, trace
from bench.apps import dense
from bench.metrics import (admit_commit_roofline_pct, admit_commit_us,
                           complete_us, device_idle_pct, step_mfu_pct,
                           tick_ms)

DATA = Path(__file__).resolve().parent / "data"


def _events():
    """Two ticks (0-100 ns, 150-250 ns) with a submit span between them;
    device ops overlapping inside the first, one op in the second; the
    kernels whose costs were counted, as ``extract`` keeps them."""
    return {
        "device": "/device:TPU:0",
        "kernels": ["xlb_admit_commit", "xlb_complete"],
        "ops": [["xlb_admit_commit", "xlb_admit_commit", 10, 10],
                ["fusion.1", None, 32, 8],
                ["custom-call.3", "xlb_complete", 30, 5],
                ["fusion.1", None, 160, 10],
                ["fusion.9", None, 300, 10]],           # after the window
        "modules": [["jit_serve_step(1)", 10, 30],
                    ["jit_serve_step(1)", 160, 10]],
        "host": [["tick", 0, 100], ["serve_step", 5, 30],
                 ["submit", 100, 50], ["tick", 150, 100],
                 ["serve_step", 155, 20]],
    }


def test_busy_union_kernels_and_gaps():
    s = trace.reduce(_events())
    assert s.window_s == pytest.approx(250e-9)
    assert s.busy_s == pytest.approx(30e-9)        # [10,20] [30,40] [160,170]
    assert s.n_ticks == 2 and s.n_steps == 2
    assert s.kernels["xlb_admit_commit"] == {"launches": 1,
                                             "seconds": pytest.approx(1e-8)}
    assert s.kernels["xlb_complete"]["launches"] == 1
    assert s.top_ops[0] == ["fusion.1", pytest.approx(18e-9)]
    gaps = dict(s.idle_gaps)
    # idle under serve_step: 5-10, 20-30, 155-160, 170-175; under tick
    # alone: 0-5, 40-100, 150-155, 175-250; under submit: 100-150
    assert gaps["serve_step"] == pytest.approx(25e-9)
    assert gaps["tick"] == pytest.approx(145e-9)
    assert gaps["submit"] == pytest.approx(50e-9)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_extract_keeps_the_programs_spans_with_their_counters(tmp_path):
    """A profile taken here (a CPU has no device plane): the harness's
    spans go to ``host``, every ``loop.*`` span to ``phases`` with its
    numeric metadata, whatever its name."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("tick"):
        with jax.profiler.TraceAnnotation("loop.admission") as span:
            span.set_metadata(rows=3, batch=8)
        with jax.profiler.TraceAnnotation("loop.relay") as span:
            span.set_metadata(experts=2, note="text")
            jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.extract(trace.newest_xplane(tmp_path))
    assert [h[0] for h in ev["host"]] == ["tick"]
    assert [(p[0], p[3]) for p in ev["phases"]] == [
        ("loop.admission", {"rows": 3, "batch": 8}),
        ("loop.relay", {"experts": 2})]
    tick = ev["host"][0]
    for _, s, d, _ in ev["phases"]:
        assert tick[1] <= s and s + d <= tick[1] + tick[2]
    assert len(ev["scopes"]) == len(ev["ops"])


def test_a_kernel_is_its_ops_exact_name():
    """An op launches a kernel where its instruction is named the kernel,
    with or without a ``.<n>`` suffix: a kernel whose name begins another's
    takes none of the other's ops."""
    kernels = ("xlb_admit", "xlb_admit_commit", "xlb_complete")
    assert trace._kernel_of("xlb_admit_commit.2", kernels) \
        == "xlb_admit_commit"
    assert trace._kernel_of("xlb_admit.7", kernels) == "xlb_admit"
    assert trace._kernel_of("xlb_admit", kernels) == "xlb_admit"
    assert trace._kernel_of("xlb_complete.1", kernels[:2]) is None
    for op in ("fusion.3", "xlb_admit_commit.2.clone", "xlb_admit_x.1",
               "copy.xlb_complete"):
        assert trace._kernel_of(op, kernels) is None


@pytest.mark.parametrize("recorded", ["trace-fleet50.rpc-closed.json",
                                      "phases-fleet50.rpc-closed.json"])
def test_recorded_ops_keep_their_kernels(recorded):
    """The chip's recorded ops, named again by the kernels that fleet50's
    costs count, get the kernel they were recorded with, and a kernel
    named after the admission scope takes none of them."""
    ops = json.loads((DATA / recorded).read_text())["events"]["ops"]
    kernels = ("xlb_admit", "xlb_admit_commit", "xlb_complete")
    assert [trace._kernel_of(o[0], kernels) for o in ops] == \
        [o[1] for o in ops]
    assert sum(o[1] is not None for o in ops) == 24


def test_no_device_or_no_tick_reads_nothing():
    ev = _events()
    assert trace.reduce(dict(ev, device=None)) is None
    assert trace.reduce(dict(ev, host=[])) is None


def _layer_run(summary):
    return run.LayerRun(summary, peaks.peaks_for("TPU v5 lite"),
                        {"xlb_admit_commit": (1e4, 1e5),
                         "xlb_complete": (1e3, 2e4)}, 1e8)


def test_readers_on_hand_made_events():
    lr = _layer_run(trace.reduce(_events()))
    assert tick_ms.read(lr) == pytest.approx(250e-9 / 2 * 1e3)
    assert device_idle_pct.read(lr) == pytest.approx(88.0)
    assert admit_commit_us.read(lr) == pytest.approx(0.01)
    assert complete_us.read(lr) == pytest.approx(0.005)
    least = 1e5 / 819e9                      # the bytes bound it
    assert admit_commit_roofline_pct.read(lr) == pytest.approx(
        100 * least / 1e-8)
    ops = 2 * 1e8 + 1e4 + 1e3
    assert step_mfu_pct.read(lr) == pytest.approx(
        100 * ops / (250e-9 * 197e12))


def test_a_kernel_that_never_ran_reads_nothing():
    ev = _events()
    ev["ops"] = [o for o in ev["ops"] if o[1] != "xlb_complete"]
    lr = _layer_run(trace.reduce(ev))
    assert complete_us.read(lr) is None


def _raster_busy(ev, lo, hi, step=50):
    """Busy seconds of the device ops by a 50 ns raster of the window, an
    independent check of the interval union."""
    n = (hi - lo) // step + 1
    busy = np.zeros(int(n), bool)
    for _, _, s, d in ev["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            busy[int((a - lo) // step):int(np.ceil((b - lo) / step))] = True
    return busy.sum() * step * 1e-9


def test_recorded_chip_trace():
    files = sorted(DATA.glob("trace-*.json"))
    assert files, "a recorded trace of the chip is kept in bench/tests/data"
    for f in files:
        rec = json.loads(f.read_text())
        s = trace.reduce(rec["events"])
        ticks = [(a, a + d) for n, a, d in rec["events"]["host"]
                 if n == "tick"]
        lo, hi = min(t[0] for t in ticks), max(t[1] for t in ticks)
        # the closed loop launches each kernel and serve_step once a tick
        assert s.n_steps == s.n_ticks == len(ticks)
        for k in ("xlb_admit_commit", "xlb_complete"):
            assert s.kernels[k]["launches"] == s.n_ticks
        assert s.busy_s == pytest.approx(
            _raster_busy(rec["events"], lo, hi), rel=0.02)
        assert sum(v for _, v in s.idle_gaps) == pytest.approx(
            s.window_s - s.busy_s)
        want = rec["summary"]
        assert s.n_ticks == want["n_ticks"]
        assert s.n_steps == want["n_steps"]
        assert s.window_s == pytest.approx(want["window_s"])
        assert s.busy_s == pytest.approx(want["busy_s"])
        for k, v in want["kernels"].items():
            assert s.kernels[k]["launches"] == v["launches"] > 0
            assert s.kernels[k]["seconds"] == pytest.approx(v["seconds"])
        assert 0 < s.busy_s < s.window_s


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes"] == 819e9


# What each reader read at the parent commit, its costs and decode FLOPs
# counted for fleet50's shapes, on the two recorded traces of the chip
# (the second with the program's spans and scopes).
BEFORE = {
    "trace-fleet50.rpc-closed.json": {
        "tick_ms": 8.055495833333334,
        "device_idle_pct": 96.34303599147374,
        "step_mfu_pct": 0.018585410450736854,
        "admit_commit_us": 32.7475,
        "admit_commit_roofline_pct": 0.9618122756530267,
        "complete_us": 2.4803333333333337,
        "complete_roofline_pct": 1.6564003124949234},
    "phases-fleet50.rpc-closed.json": {
        "tick_ms": 6.8545755,
        "device_idle_pct": 95.90768239404468,
        "step_mfu_pct": 0.021841570852447464,
        "admit_commit_us": 32.69291666666667,
        "admit_commit_roofline_pct": 0.9634180950597604,
        "complete_us": 0.6856666666666666,
        "complete_roofline_pct": 5.991869093473372},
}


@pytest.mark.parametrize("name", sorted(BEFORE[min(BEFORE)]))
@pytest.mark.parametrize("recorded", sorted(BEFORE))
def test_existing_readers_read_as_before(fleet_state, recorded, name):
    """Each reader that predates the program's spans reads, from the same
    events, what it read before the harness took the spans in."""
    ev = json.loads((DATA / recorded).read_text())["events"]
    app = json.loads((run.ROOT / "bench" / "configs" / "fleet50.json")
                     .read_text())["application"]
    lr = run.LayerRun(trace.reduce(ev), peaks.peaks_for("TPU v5 lite"),
                      costs.per_launch(fleet_state, {}, app, 256),
                      dense.decode_flops(app, 400, 16), phases.reduce(ev))
    got = registry.load("metrics", name).read(lr)
    assert got == pytest.approx(BEFORE[recorded][name], rel=1e-12)
