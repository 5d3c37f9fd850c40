"""Tokens each decode step produced, on average over the window: the change
in the engine's ``tokens_out`` over the change in its ``steps``."""


def read(run):
    steps = run.stats1["steps"] - run.stats0["steps"]
    if steps <= 0:
        return None
    return (run.stats1["tokens_out"] - run.stats0["tokens_out"]) / steps
