"""The benchmark's command: one run of one cell on the chip(s) it holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object; the numbers compared
to decide ``correct`` are also the last lines of standard error.  Exits with
code 2, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for, or where the program under test is not in the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program under test at {ROOT / 'src' / 'repro'}",
              file=sys.stderr)
        return 2
    from bench import harness
    from bench.spec import load_cell, load_limits

    cell = load_cell(args.workload)
    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), T_PROCESS,
                               limits=load_limits(cell.name))
    except harness.NoChip as e:
        print(str(e), file=sys.stderr)
        return 2
    for name, c in out["check"].items():
        harness.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
