"""The benchmark's command: one run of one cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets the cell up (weights from the seed, the engine and its KV pool, every
bucket the window can reach compiled or loaded from the persistent cache),
offers the cell's traffic for `--seconds`, checks the served tokens against
the plain reference, and prints one JSON object as the last line of stdout.
With `--trace 0` its metrics are the cell's end-to-end metrics; with
`--trace 1` the window runs under the profiler and its metrics are the
cell's per-layer metrics.

Exits non-zero, printing no result, when JAX finds no accelerator listed in
bench/peaks.json, fewer chips than the cell asks for, or no program.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != HERE]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default="",
                    help="also write the step times, compile log and (with "
                         "--trace 1) the raw trace into this directory")
    args = ap.parse_args(argv)

    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START, dump=args.dump or None)
    except harness.BenchError as e:
        print(f"[bench] no result: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
