"""95th percentile of the time to first token of all requests due in the
window (host clock, from the due time; a request never served counts with
its whole wait)."""
from bench.metrics._read import percentile, ttfts_ms


def read(run):
    return percentile(ttfts_ms(run), 95)
