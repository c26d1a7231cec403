#!/usr/bin/env python3
"""The knee of an open-loop cell: the highest offered rate that the fleet
serves without a growing backlog over the window.

    python3 bench/sweep.py --workload <cell> --rates 500,1000,2000 \\
        --seconds 8 --seed <n>

For each rate (in one process) it runs the cell's own path with the mix's
``rate_rps`` replaced, and prints one JSON line: offered and completed
requests per second, p50/p99 latency, dropped requests, and the median
latency of the window's first and last thirds.  A rate holds when nothing
is dropped, the last third's median latency is within ``GROWTH`` of the
first third's, and the tail has not run away: p99 within ``TAIL`` times
p50 (held requests backing off behind a full endpoint).  The
sweep stops after two rates in a row that do not hold.  The knee found
here, and 0.8 of it, go into the mix file by hand; the benchmark's own
runs never search for a rate.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

from bench import run  # noqa: E402

GROWTH = 1.5
TAIL = 5.0
DRAIN_S = 10.0               # an overloaded rate's drain is cut short


def at_rate(workload: str, rate: float, seed: int, seconds: float) -> dict:
    import numpy as np

    from bench import deploy, driver, traffic
    driver.GRACE_S = DRAIN_S
    cell = run.load_cell(workload)
    jax = run.setup_jax()
    run.device_info(jax, cell.chips, True)
    spec = dict(cell.mix, rate_rps=rate)
    mix = traffic.Mix(spec, seed, cell.config["application"]["vocab"])
    dep = deploy.build(cell.config, mix.max_len, seed)
    drv = driver.Driver(dep, mix, seconds)
    drv.compile()
    log = drv.run()
    win = log.in_window()
    ok = win & ~np.isnan(log.seen)
    lat = (log.seen - log.start) * 1e3
    third = (log.win_end - log.win_start) / 3
    first = ok & (log.start < log.win_start + third)
    last = ok & (log.start >= log.win_end - third)
    window_s = log.win_end - log.win_start
    row = {
        "rate_rps": rate,
        "completed_rps": log.completed_in_window / window_s,
        "p50_ms": float(np.percentile(lat[ok], 50)),
        "p99_ms": float(np.percentile(lat[ok], 99)),
        "first_third_p50_ms": float(np.median(lat[first])),
        "last_third_p50_ms": float(np.median(lat[last])),
        "dropped": int((win & log.dropped).sum()),
        "held_first": dep.loop.held_first,
        "late_p99_ms": float(np.percentile(log.lateness, 99) * 1e3),
        "ticks": log.ticks_in_window,
    }
    row["holds"] = (row["dropped"] == 0
                    and row["last_third_p50_ms"]
                    <= GROWTH * row["first_third_p50_ms"]
                    and row["p99_ms"] <= TAIL * row["p50_ms"])
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=2**31 + 500)
    args = ap.parse_args(argv)
    knee, misses = None, 0
    for rate in (float(r) for r in args.rates.split(",")):
        row = at_rate(args.workload, rate, args.seed, args.seconds)
        print(json.dumps(row), flush=True)
        if row["holds"]:
            knee, misses = rate, 0
        else:
            misses += 1
            if misses == 2:             # past the knee: stop
                break
    print(json.dumps({"workload": args.workload, "knee_rps": knee,
                      "rate_at_0.8": knee and 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
