"""Helpers the metric readers share."""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def percentile(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts_ms(run) -> List[float]:
    """Time to first token of every request due in the window, from when it
    was due.  A request that never got one counts with the time it waited
    until the run gave up on it."""
    return [((r.times[0] if r.times else run.t_end) - r.due) * 1e3
            for r in run.window_reqs()]


def program_ms(run, pattern: str) -> Optional[float]:
    """Device milliseconds per run of the programs matching ``pattern``, in
    the traced window."""
    if run.trace is None:
        return None
    sec, n = run.trace.program(pattern)
    return sec / n * 1e3 if n else None


def idle_pct(run) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0 or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share


def log_bound(name: str, t_flops: float, t_bytes: float) -> None:
    """Say on standard error which bound a roofline share is against."""
    import sys
    bound = "bytes" if t_bytes >= t_flops else "flops"
    print(f"{name}: {bound}-bound (least time from flops {t_flops:.6g} s, "
          f"from bytes {t_bytes:.6g} s)", file=sys.stderr)
