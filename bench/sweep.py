"""Find an open-loop cell's knee: the highest rate the system sustains.

    python3 bench/sweep.py --workload <cell> --rates 2,4,8 --seconds 20

Runs the cell's traffic at each rate in turn in this one process (only the
first rate compiles) and prints, per rate, the offered and served output
tokens per second, the tails, and the requests still waiting when the
window closed. The knee is the highest rate whose queue does not grow.
The cell's rate is then set, once, in its traffic file.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = dict(cell.traffic, rate=rate)
        out = harness.run(cell, args.seed, args.seconds, False, details=True)
        print(json.dumps({"rate": rate, "correct": out["correct"],
                          "metrics": {k: v["value"] for k, v in
                                      out["metrics"].items()},
                          "window": out["window"],
                          "attempted": out["attempted"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
