"""Device milliseconds per run of the paged admit program (prefill of one
admission), in the traced window."""
from bench.metrics._read import program_ms

PROGRAM = r"jit_admit$"


def read(run):
    return program_ms(run, PROGRAM)
