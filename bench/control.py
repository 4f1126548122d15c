"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

Runs the cell on each seed in this one process (compiled programs are
shared, so only the first seed compiles) and, for the same sampled prompts
and served tokens, reads both the program's gaps and the control's (the
reference computed in a lower precision, `--low`: see bench/references/).
Prints one
JSON line per seed and a summary: the largest reading of the program over
the seeds (the lower reading) and the smallest of the control (the upper
reading) for each number the check compares. Each seed's row also gives
the control's `correct`: its readings judged by the harness's own
comparison against the cell's committed limits, which it has to fail.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != HERE]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--low", default="fp8", choices=("fp8", "bfloat16"),
                    help="precision of the control")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = harness.run(cell, seed, args.seconds, False,
                          control=args.low)
        control_ok, control_checks = harness.judge(
            out["readings"]["control"], cell.limits)
        row = {"seed": seed, "correct": out["correct"],
               "control_correct": control_ok,
               "control_checks": control_checks,
               "compiles_in_window": out["compiles_in_window"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               **out["readings"], "window": out["window"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for name in ("max_gap", "mean_gap", "flip_share"):
        lower = max(r["served"][name] for r in rows)
        upper = min(r["control"][name] for r in rows)
        summary[name] = {"lower": lower, "upper": upper,
                         "ratio": upper / lower if lower else None}
    summary["program_correct"] = sum(r["correct"] for r in rows)
    summary["control_correct"] = sum(r["control_correct"] for r in rows)
    summary["seeds"] = len(rows)
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
