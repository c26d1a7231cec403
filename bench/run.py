#!/usr/bin/env python3
"""The XLB benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); the configuration's application is
checked against ``bench/apps/<reference>.py``, each kernel's cost counted
by ``bench/costs/<kernel>.py`` and each per-layer metric read by
``bench/metrics/<metric>.py`` (``bench/registry.py``).  The run:

1. fails, printing no result, unless JAX's first device is a TPU and the
   cell's chips are there;
2. builds the deployment with weights from ``--seed``
   (``bench/deploy.py``), compiles ``serve_step`` and warms up on the
   mix's own traffic;
3. measures for ``--seconds`` (``bench/driver.py``), with the profiler on
   over a few steady seconds when ``--trace 1``;
4. reads the device's peak memory, with ``--trace 1`` reads the named
   scopes from the text of the ``serve_step`` module that ran, drops the
   program's state, and decides ``correct`` against the plain reference
   (``bench/check.py``);
5. prints the numbers compared as the last lines of standard error, and
   one JSON object as the last line of standard output.

``setup_s`` runs from the start of this process to the first measured
tick.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

OUT = ROOT / "bench_out"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------- #
# the cell, found by name
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list          # BENCHMARK.json entries reported here
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration and mix files from ``<root>/bench/``."""
    from bench.deploy import load_json
    bm = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of "
                         f"{sorted(cells)}")
    w = cells[name]
    return Cell(
        name, int(w["chips"]),
        load_json(root / "bench" / "configs" / f"{w['config']}.json"),
        load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        [m for m in bm["end_to_end"] if _applies(m, name)],
        [m for m in bm["per_layer"] if _applies(m, name)])


# --------------------------------------------------------------------------- #
# the device
# --------------------------------------------------------------------------- #


def setup_jax():
    """Compile cache by the repository's rule, every program cached."""
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def device_info(jax, chips: int, require_chip: bool) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if require_chip and (dev.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {dev.platform!r} device(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# --------------------------------------------------------------------------- #
# metrics
# --------------------------------------------------------------------------- #


def end_to_end(log_, setup_s: float) -> dict:
    """Every end-to-end metric the harness takes, by name."""
    import numpy as np
    win = log_.in_window()
    ok = win & ~np.isnan(log_.seen)
    lat = (log_.seen[ok] - log_.start[ok]) * 1e3
    window_s = log_.win_end - log_.win_start
    out = {"setup_s": setup_s,
           "throughput_rps": log_.completed_in_window / window_s}
    if lat.size:
        out["p50_latency_ms"] = float(np.percentile(lat, 50))
        out["p95_latency_ms"] = float(np.percentile(lat, 95))
        out["p99_latency_ms"] = float(np.percentile(lat, 99))
    return out


@dataclasses.dataclass
class LayerRun:
    """What a per-layer reader reads (``bench/metrics``)."""

    summary: object           # bench.trace.Summary
    peaks: dict
    costs: dict               # kernel → (ops, bytes) per launch
    step_flops: float         # decode FLOPs per tick
    phases: object = None     # bench.phases.PhaseSummary: the program's
    #                           spans, counters and scopes


def per_layer(metrics: list, run: LayerRun, root: Path = ROOT) -> dict:
    from bench import registry
    out = {}
    for m in metrics:
        v = registry.load("metrics", m["name"], root).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------------- #


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            root: Path = ROOT, require_chip: bool = True, fault=None,
            control: bool = False,
            dump: Path | None = None) -> tuple[dict, dict]:
    """One run of a cell: (the result object, the numbers compared).

    ``require_chip=False`` and ``fault`` (a function from the compiled
    step to a broken one) are for tests on the CPU; ``control`` judges
    the run with the control in the program's place
    (``bench/check.py::control_numbers``), the result then keeping the
    program's own numbers under ``"control"``; ``dump`` keeps the
    extracted trace events."""
    import numpy as np

    from bench import check, costs, deploy, driver, peaks, phases, traffic
    from bench import trace as tr
    cell = load_cell(workload, root)
    jax = setup_jax()
    device = device_info(jax, cell.chips, require_chip)
    chip_peaks = peaks.peaks_for(device["kind"]) if require_chip else None
    mix = traffic.Mix(cell.mix, seed, cell.config["application"]["vocab"])
    t_build = time.perf_counter()
    dep = deploy.build(cell.config, mix.max_len, seed, root)
    trace_dir = OUT / "trace" / f"{workload}-{seed}" if trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    drv = driver.Driver(dep, mix, seconds, trace_dir=trace_dir)
    drv.rec.fault = fault
    t_compile = time.perf_counter()
    drv.compile()
    t_traffic = time.perf_counter()
    run = drv.run()
    setup_s = run.win_start - T_PROCESS
    log(f"set-up (s): imports and device {t_build - T_PROCESS:.3f}, "
        f"weights and routing {t_compile - t_build:.3f}, compile or cache "
        f"load {t_traffic - t_compile:.3f}, warm-up traffic "
        f"{run.win_start - t_traffic:.3f}")
    device["memory_peak_bytes"] = memory_peak(jax)
    loop = dep.loop
    kernel_costs = costs.per_launch(loop.state, loop.params,
                                    cell.config["application"],
                                    loop.admit_batch, root)
    log(f"kernel costs (operations, bytes a launch): {kernel_costs}")
    names = {}                            # instruction → named scope
    if trace and drv.rec.samples:
        t_scopes = time.perf_counter()
        names = phases.scope_names(phases.compiled_text(
            drv.rec.step, loop.params, loop.state, drv.rec.samples[0][1]))
        log(f"scopes: serve_step's module text read in "
            f"{time.perf_counter() - t_scopes:.3f} s")
    m = loop.state.metrics
    log(f"flow metrics (per admission attempt): requests "
        f"{int(m.requests.sum())}, overflow {int(m.overflow)}, no route "
        f"{int(m.no_route_match)}")
    loop.state = None                     # the program's state is dropped
    numbers = check.run_checks(dep, run, drv.rec.samples, loop.done, seed)
    win = run.in_window()
    attempted = int(win.sum())
    failed = int((win & (run.dropped | np.isnan(run.seen))).sum())
    log(f"run: {attempted} requests in the window, {failed} failed, "
        f"{run.ticks_in_window} ticks in {run.win_end - run.win_start:.3f} s"
        f", {len(loop.done)} done in all, {len(loop.dropped)} dropped, "
        f"held first {loop.held_first}")
    log(f"host stalls: {run.stalls()}")
    if run.lateness.size:
        log(f"generator lateness: p50 {np.median(run.lateness) * 1e3:.3f} "
            f"ms, p99 {np.percentile(run.lateness, 99) * 1e3:.3f} ms, max "
            f"{run.lateness.max() * 1e3:.3f} ms")
    own = None
    if control:
        own = numbers
        numbers, read = check.control_numbers(dep, run, loop.done, seed,
                                              numbers)
        own = {"program_checks": {k: v for k, (v, _) in own.items()},
               **read}
    result = {"correct": check.passed(numbers), "attempted": attempted,
              "failed": failed}
    if own is not None:
        result["control"] = own
    if not trace:
        e2e = end_to_end(run, setup_s)
        log(f"end to end: {json.dumps(e2e)}")
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in e2e}
    else:
        ev = tr.extract(tr.newest_xplane(trace_dir), names,
                        tuple(kernel_costs))
        if dump is not None:
            dump.mkdir(parents=True, exist_ok=True)
            (dump / f"{workload}-{seed}.json").write_text(json.dumps(ev))
        shutil.rmtree(trace_dir, ignore_errors=True)
        summary = tr.reduce(ev)
        if summary is None:
            raise RuntimeError("the trace holds no tick span or no device")
        lr = LayerRun(summary, chip_peaks, kernel_costs,
                      dep.app_ref.decode_flops(cell.config["application"],
                                                 dep.n_slots, dep.max_len),
                      phases.reduce(ev))
        result["metrics"] = per_layer(cell.per_layer, lr, root)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
        log(f"trace: {summary.n_ticks} ticks, {summary.n_steps} serve_step "
            f"runs, kernels {summary.kernels}, "
            f"{sum(o[0] not in names for o in ev['ops'])} of "
            f"{len(ev['ops'])} ops not in the compiled module")
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}
    return result, numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", type=Path, default=None,
                    help="keep the traced run's extracted events here")
    args = ap.parse_args(argv)
    try:
        result, numbers = execute(args.workload, args.seed, args.seconds,
                                  bool(args.trace), dump=args.dump)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    for k, (v, lim) in numbers.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
