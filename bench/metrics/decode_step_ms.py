"""Device milliseconds per run of the paged decode program, in the traced
window."""
from bench.metrics._read import program_ms

PROGRAM = r"jit_step$"


def read(run):
    return program_ms(run, PROGRAM)
