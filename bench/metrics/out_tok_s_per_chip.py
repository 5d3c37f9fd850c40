"""Output tokens that reached the client inside the window, in-flight
requests included, over the window's seconds and the chips."""


def read(run):
    n = sum(1 for r in run.reqs for t in r.times if run.win0 <= t < run.win1)
    return n / run.seconds / run.chips
