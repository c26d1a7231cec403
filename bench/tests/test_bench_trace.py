"""The reduction from trace events to per-layer numbers, on hand-made
events and on a recorded trace of the chip, and the peak table."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import peaks, run, trace
from bench.metrics import (admit_commit_roofline_pct, admit_commit_us,
                           complete_us, device_idle_pct, step_mfu_pct,
                           tick_ms)

DATA = Path(__file__).resolve().parent / "data"


def _events():
    """Two ticks (0-100 ns, 150-250 ns) with a submit span between them;
    device ops overlapping inside the first, one op in the second."""
    return {
        "device": "/device:TPU:0",
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
        for k in trace.KERNELS:
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
