"""Share of the window the engine loop spent blocked on a full sidecar queue:
the change of the program's ``stats()["sidecar"]["blocked_s"]`` across the
window over the window's seconds.  Nothing to read from a program that does
not count it."""


def read(run):
    try:
        b0 = run.stats0["sidecar"]["blocked_s"]
        b1 = run.stats1["sidecar"]["blocked_s"]
    except KeyError:
        return None
    if run.seconds <= 0:
        return None
    return 100.0 * (b1 - b0) / run.seconds
