"""95th percentile of every gap between successive tokens of one request,
over all gaps that end inside the window (host clock, client side)."""
from bench.metrics._read import percentile


def read(run):
    gaps = []
    for r in run.reqs:
        for a, b in zip(r.times, r.times[1:]):
            if run.win0 <= b < run.win1:
                gaps.append((b - a) * 1e3)
    return percentile(gaps, 95)
