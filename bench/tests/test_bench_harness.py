"""The harness end to end on the CPU at a tiny size: files found by name,
a sound run judged correct, no result off a TPU, the metric arithmetic."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import driver, run
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def test_new_configuration_and_mix_are_found_by_name(tmp_path):
    root = tiny.write_root(tmp_path)
    for name in tiny.CELLS:
        cell = run.load_cell(name, root)
        assert cell.config["instances"] == tiny.CONFIG["instances"]
        assert cell.mix == tiny.MIXES[name.split(".")[1]]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                        "p50_latency_ms"}


def test_the_benchmarks_cells_resolve():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.chips == w["chips"]
        names = {m["name"] for m in cell.end_to_end}
        assert ("throughput_rps" in names) == (w["name"]
                                               == "fleet50.rpc-closed")
    for m in bm["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_a_sound_run_is_correct(tmp_path, cell):
    root = tiny.write_root(tmp_path)
    result, numbers = run.execute(cell, tiny.SEED, 1.5, False, root=root,
                                  require_chip=False)
    assert result["correct"], numbers
    assert result["attempted"] > 20 and result["failed"] == 0
    assert set(result["metrics"]) == {"throughput_rps", "p50_latency_ms",
                                      "p95_latency_ms", "setup_s"}
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(numbers)


def _bench_cmd():
    return [sys.executable, "bench/run.py", "--workload",
            "fleet50.rpc-closed", "--seed", "1", "--seconds", "1",
            "--trace", "0"]


def test_off_a_tpu_it_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(_bench_cmd(), cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_without_the_program_it_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(_bench_cmd(), cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def _log(start, seen, t0=10.0, t1=20.0, done_in=None):
    start, seen = np.asarray(start, float), np.asarray(seen, float)
    return driver.RunLog(start, seen, np.zeros(start.size, bool), t0, t1,
                         ticks_in_window=100,
                         completed_in_window=(done_in if done_in is not None
                                              else int(np.sum(seen < t1))),
                         lateness=np.zeros(0))


def test_latency_percentiles_and_rate():
    start = 10.0 + np.arange(1000) * 0.01          # all in the window
    lat = np.arange(1, 1001) * 1e-3                # 1 .. 1000 ms
    out = run.end_to_end(_log(start, start + lat, done_in=800), 3.5)
    assert out["setup_s"] == 3.5
    assert out["throughput_rps"] == pytest.approx(80.0)
    assert out["p50_latency_ms"] == pytest.approx(500.5)
    assert out["p95_latency_ms"] == pytest.approx(950.05)
    assert out["p99_latency_ms"] == pytest.approx(990.01)


def test_latency_counts_only_the_windows_requests():
    start = [5.0, 12.0, 19.0, 25.0]
    seen = [30.0, 12.5, np.nan, 25.1]
    log = _log(start, seen)
    assert list(log.in_window()) == [False, True, True, False]
    out = run.end_to_end(log, 1.0)
    assert out["p50_latency_ms"] == pytest.approx(500.0)
