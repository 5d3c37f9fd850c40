"""Model FLOPs of the tokens prefilled and decoded inside the window (2 N
per token plus attention over the live context, bench/work.py; nothing
recomputed, no padding) over the window's seconds, the chips and the bf16
peak."""
from bench import work


def read(run):
    s = run.shapes
    flops = sum(work.prefill_flops(s, hit, L)
                for L, hit in run.prefills(run.win0, run.win1))
    tokens, ctx = run.decode_tokens(run.win0, run.win1)
    flops += work.decode_work(s, 0, ctx, tokens)[0]
    return 100.0 * flops / (run.seconds * run.chips * run.peaks["bf16_flops"])
