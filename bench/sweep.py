"""Find a cell's knee once: run its traffic at several session rates.

    python3 bench/sweep.py --workload <name> --rates 1 2 3 --seconds 20

One process, one engine set-up per rate (programs stay compiled in the
process).  For each rate it prints the offered load and the latencies, so
the highest rate with no growing queue (the knee) can be read off.  A cell
judged by its tails offers about four fifths of the knee; one judged by the
tokens per second served offers twice the knee, so that its queue grows
through the window and the program, not the offered load, sets the rate.
Besides the cell's
end-to-end metrics it prints every per-layer metric that reads without a
trace (the latency tails, tokens per step, prefix hits).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.spec import load_cell

    base = load_cell(args.workload)
    for rate in args.rates:
        traffic = copy.deepcopy(base.traffic)
        traffic["arrival"]["rate_per_s"] = rate
        cell = dataclasses.replace(base, traffic=traffic,
                                   e2e=base.e2e + base.layer)
        out = harness.run_cell(cell, args.seed, args.seconds, False,
                               time.perf_counter(), check=False)
        print(json.dumps({"rate_per_s": rate, "attempted": out["attempted"],
                          "failed": out["failed"],
                          "queued_at_end": out["queued_at_end"],
                          "metrics": {k: v["value"]
                                      for k, v in out["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
