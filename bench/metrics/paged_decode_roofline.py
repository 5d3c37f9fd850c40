"""Share of its roofline the paged decode program reaches in the traced
window: the least time the chip needs for the decode work served there
(the larger of its FLOPs over the peak rate and its bytes over the peak
bandwidth, from the cell's architecture file in bench/arch/: every weight
once per step and the live K/V of each active row) over the device time of
those decode runs."""
from bench.metrics._read import log_bound

PROGRAM = r"jit_step$"


def read(run):
    if run.trace is None:
        return None
    sec, steps = run.trace.program(PROGRAM)
    tokens, ctx = run.decode_tokens(run.trace_t0, run.trace_t1)
    if not steps or not tokens or sec <= 0:
        return None
    flops, nbytes = run.arch.decode_work(run.shapes, steps, ctx, tokens)
    t_flops = flops / run.peaks["bf16_flops"]
    t_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    log_bound("paged_decode_roofline", t_flops, t_bytes)
    return 100.0 * max(t_flops, t_bytes) / sec
