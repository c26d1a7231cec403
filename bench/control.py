#!/usr/bin/env python3
"""Readings that the limits of ``bench/check.py`` are set from.

    python3 bench/control.py --workload <cell> --seeds <n> --seconds <s>

For each seed (in one process) it makes one run of the cell's own timed
path, ``bench/run.py::execute`` with ``control=True``, for a short window
at the cell's load.  It prints every number the check compares as the
program reads it (the lower readings), the control's reading (the plain
reference put in the program's place one precision below the one the
configuration serves in, fp8 for bfloat16, ``bench/check.py::CONTROL``,
read at each position of the same prompts and served tokens as the gap of
the token the lower precision puts first), and ``correct`` as the
harness's own comparison decides it with the control in the program's
place, which has to be false.  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

from bench import run  # noqa: E402


def readings(workload: str, seed: int, seconds: float, *,
             root: Path = run.ROOT, require_chip: bool = True) -> dict:
    """The program's numbers compared, the control's logit gap and the
    control's ``correct``, for one seed of a cell."""
    result, numbers = run.execute(workload, seed, seconds, False,
                                  root=root, require_chip=require_chip,
                                  control=True)
    ctl = result["control"]
    return {"seed": seed, "program": ctl["program_checks"],
            "control": {"logit_gap": numbers["logit_gap"][0]},
            "control_correct": result["correct"],
            "control_wrong_tokens": ctl["wrong_tokens"],
            "tokens": ctl["tokens"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    rows = []
    for s in range(args.first_seed, args.first_seed + args.seeds):
        rows.append(readings(args.workload, s, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    lower = {k: max(r["program"][k] for r in rows) for k in rows[0]["program"]}
    upper = {k: min(r["control"][k] for r in rows) for k in rows[0]["control"]}
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "program_max": lower, "control_min": upper,
                      "control_correct": sum(r["control_correct"]
                                             for r in rows)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
