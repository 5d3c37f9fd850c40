"""Speculative vs sequential greedy decode on the paged serve engine.

Speculative decoding turns k+1 sequential decode dispatches into one drafter
scan plus ONE batched k+1-position verify forward of the target
(``serve/speculative.py``, ``ServeConfig.speculative``).  Greedy acceptance
uses the target's own argmax, so committed output is bit-identical to
non-speculative greedy decode — the speedup is pure scheduling, bought with
rollback of the rejected draft suffix.

The bench target is where the technique pays: a model whose deep layers
*refine* rather than redirect the prediction, so a cheap layer-skip drafter
(``draft_model='self:1'`` — the first layer plus the target's own
embed/norm/unembed, parameters shared by slicing) agrees with the full
target on most steps.  We build that regime explicitly: an 8-layer variant
of repro-tiny with every post-first layer's output projections damped, the
shape trained residual-stream models actually exhibit (logit lens /
early-exit literature) and the honest way to show the mechanism without a
trained checkpoint: a deep (16-layer) variant of repro-tiny with every
post-first layer's output projections damped.  Random-init weights at equal
layer scale are the adversarial case — every layer redirects — and
acceptance collapses toward zero there (the engine still stays exact; see
tests/test_serve_speculative).

Reported per engine: wall, decode tok/s, and for the speculative engine the
measured acceptance rate, rollback volume, and speedup vs the sequential
baseline.  The run asserts bit-identical outputs and (full mode) the
``SPEEDUP_FLOOR``.

    PYTHONPATH=src python benchmarks/serve_speculative.py
    PYTHONPATH=src python benchmarks/serve_speculative.py --smoke   # CI
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.config import ServeConfig, get_config
from repro.models.transformer import init_params
from repro.serve import PagedEngine
from repro.launch.compile_cache import enable_compile_cache

from _emit import emit

# Documented floor for the speculative/sequential throughput ratio on the
# refinement-regime bench target (full mode; measured 1.7-1.9x at draft_k=4
# with ~0.85 acceptance on this container).  k=4 acceptance a gives an ideal
# bound of 1+4a committed tokens per macro step; the drafter scan and the
# (k+1)-wide verify forward eat part of it — the deeper the target, the less
# they matter (both are ~depth-independent next to the target's stack).
SPEEDUP_FLOOR = 1.5


def build_target(seed: int, num_layers: int = 16, damp: float = 0.005):
    """Deep repro-tiny variant in the refinement regime: layers 1..L-1
    have their attention+MLP output projections damped so the residual
    stream (and the argmax) is dominated by layer 0 — the regime where a
    layer-skip drafter earns its keep."""
    cfg = dataclasses.replace(get_config("repro-tiny"),
                              num_layers=num_layers)
    params = init_params(jax.random.PRNGKey(seed), cfg)

    def damp_wo(path, leaf):
        if path[-1].key == "wo":            # stacked (num_layers, ...) leaf
            return leaf.at[1:].multiply(damp)
        return leaf

    params["layers"] = jax.tree_util.tree_map_with_path(
        damp_wo, params["layers"])
    return cfg, params


def make_trace(vocab: int, n: int, seed: int, *, mean_prompt: int = 24,
               max_new: int = 48):
    """Decode-heavy trace (speculation accelerates decode, not prefill)."""
    rng = np.random.default_rng(seed)
    lens = np.clip(rng.poisson(mean_prompt, n), 4, 64)
    return [(rng.integers(0, vocab, int(L)).astype(np.int32), max_new)
            for L in lens]


def replay(eng, trace):
    t0 = time.time()
    rids = [eng.submit(p, n) for p, n in trace]
    eng.run()
    eng.executor.drain()
    wall = time.time() - t0
    outs = [eng.request(r).output for r in rids]
    return wall, sum(len(o) for o in outs), outs


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--draft-k", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace, exactness + schema only (CI): wall "
                         "times on a shared runner can't carry the floor")
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 6)
        args.max_new = min(args.max_new, 16)
        args.reps = 1

    cfg, params = build_target(args.seed)
    trace = make_trace(cfg.vocab_size, args.requests, args.seed,
                       max_new=args.max_new)
    horizon = max(len(p) for p, _ in trace) + args.max_new
    base_scfg = ServeConfig(
        max_batch=args.slots, max_seq_len=1 << (horizon - 1).bit_length(),
        max_queue=4 * args.requests, prefill_buckets=(16, 32, 64),
        page_size=16)
    spec_scfg = dataclasses.replace(
        base_scfg, speculative=True, draft_k=args.draft_k,
        draft_model="self:1")

    base = PagedEngine(cfg, params, base_scfg)
    spec = PagedEngine(cfg, params, spec_scfg)

    # Warmup: compile every admit bucket plus the decode/verify programs.
    warm = [np.zeros(L, np.int32)
            for L in sorted({len(p) for p, _ in trace})]
    for w in warm:
        base.generate([w], 2)
        spec.generate([w], args.draft_k + 2)

    runs_b = [replay(base, trace) for _ in range(args.reps)]
    runs_s = [replay(spec, trace) for _ in range(args.reps)]
    b_wall, b_toks, b_outs = min(runs_b, key=lambda r: r[0])
    s_wall, s_toks, s_outs = min(runs_s, key=lambda r: r[0])
    b_tps, s_tps = b_toks / b_wall, s_toks / s_wall
    speedup = s_tps / b_tps
    st = spec.stats()
    sp = st["speculative"]

    print(f"trace: {len(trace)} requests x {args.max_new} new tokens, "
          f"{args.slots} slots, draft_k={args.draft_k} (layer-skip self:1 "
          f"drafter, {cfg.num_layers}-layer refinement-regime target)")
    print(f"{'engine':<12} {'wall_s':>7} {'tok/s':>8} {'accept':>7} "
          f"{'macro':>6}")
    print(f"{'sequential':<12} {b_wall:>7.2f} {b_tps:>8.1f} {'-':>7} "
          f"{'-':>6}")
    print(f"{'speculative':<12} {s_wall:>7.2f} {s_tps:>8.1f} "
          f"{sp['acceptance_rate']:>7.3f} {sp['macro_steps']:>6}")
    print(f"speedup: {speedup:.2f}x   rolled back "
          f"{st['spec_rolled_back_tokens']} draft tokens")

    mismatch = [i for i, (a, b) in enumerate(zip(b_outs, s_outs)) if a != b]
    assert not mismatch, f"speculative != sequential for requests {mismatch}"
    print("speculative outputs identical to sequential: OK")

    emit("serve_speculative", {
        "smoke": args.smoke,
        "trace_requests": len(trace),
        "max_new_tokens": args.max_new,
        "draft_k": args.draft_k,
        "draft_model": "self:1",
        "sequential_tok_s": b_tps,
        "speculative_tok_s": s_tps,
        "speedup_x": speedup,
        "speedup_floor": SPEEDUP_FLOOR,
        "acceptance_rate": sp["acceptance_rate"],
        "proposed": sp["proposed"],
        "accepted": sp["accepted"],
        "rolled_back_tokens": st["spec_rolled_back_tokens"],
        "exact_vs_sequential": True,
    })
    if not args.smoke:
        assert speedup >= SPEEDUP_FLOOR, \
            f"speedup {speedup:.2f}x below documented floor {SPEEDUP_FLOOR}x"
    base.close()
    spec.close()


if __name__ == "__main__":
    main()
