"""Model FLOPs of the tokens prefilled and decoded inside the window (2 N
per token plus attention over the live context, from the cell's
architecture file in bench/arch/; nothing recomputed, no padding) over the
window's seconds, the chips and the bf16 peak."""


def read(run):
    s, arch = run.shapes, run.arch
    flops = sum(arch.prefill_flops(s, hit, L)
                for L, hit in run.prefills(run.win0, run.win1))
    tokens, ctx = run.decode_tokens(run.win0, run.win1)
    flops += arch.decode_work(s, 0, ctx, tokens)[0]
    return 100.0 * flops / (run.seconds * run.chips * run.peaks["bf16_flops"])
