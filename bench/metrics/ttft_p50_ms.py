"""Median time to first token of the requests due in the window (host clock,
from the due time)."""
from bench.metrics._read import percentile, ttfts_ms


def read(run):
    return percentile(ttfts_ms(run), 50)
