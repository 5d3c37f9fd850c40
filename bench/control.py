"""Readings that set a cell's limit: the program over many seeds, and the
control over the same samples.

    python3 bench/control.py --workload <name> --seeds 101 102 ... \\
        --control-seeds 3 --seconds 10

One process runs the cell once per seed with a short window at the cell's
own load, compares the sample of served tokens with the float32 reference
(the program's reading), and, for the first ``--control-seeds`` seeds, with
the control: the reference computed with every matrix product's operands in
float8 e4m3 (``bench/reference``).  The benchmark's own runs never run the
control.  Each seed's readings go to standard output as one JSON line, and
the summary last.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.spec import load_cell, load_limits

    cell = load_cell(args.workload)
    lim = load_limits(cell.name)
    program, ctl = [], []
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), limits=lim,
                               control=i < args.control_seeds)
        chk = out["check"]
        gap = chk["widest_logit_gap"]["value"]
        if "readings" in out:         # the control stood in the program's place
            ctl.append(gap)
            program.append(out["readings"]["program_widest_logit_gap"])
        else:
            program.append(gap)
        print(json.dumps({"seed": seed, "check": chk,
                          "readings": out.get("readings", {}),
                          "correct": out["correct"],
                          "metrics": out["metrics"],
                          "run_s": time.perf_counter() - t}), flush=True)
    print(json.dumps({"workload": cell.name, "program_max": max(program),
                      "program": program, "control_min": min(ctl or [0.0]),
                      "control": ctl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
