"""Paged vs dense KV-cache serving on a shared-prefix heavy-tailed trace.

The dense continuous engine allocates ``slots x max_seq_len`` cache rows up
front, so concurrency is bounded by worst-case length and prompts sharing a
prefix recompute everything.  The paged engine (block tables over a physical
page pool + hash-keyed prefix reuse + cold-tier spill, see
``serve/kvpool.py``) spends memory on *live tokens*: at the same resident
cache bytes it runs 2x the slots, and shared prefixes prefill only their
suffix.

Trace: a handful of shared "system prompt" prefixes (the prefix-heavy
regime: few-shot prompts, chat templates) with random suffixes and
heavy-tailed (geometric) decode budgets, interleaved in Poisson arrival
order.  Reported per engine: wall time, useful tokens/s, mean TTFT,
resident cache bytes, concurrent slots, and (paged) prefix-hit rate.

``--kv-quant int8`` adds a third engine storing pages quantized (int8 values
+ per-entry f32 scales, ~3.55x pages per byte on repro-tiny): at the same
cache memory as the f32 paged engine it runs 2x the slots again (4x dense),
trading bit-exactness for a measured greedy exact-match rate vs the dense
f32 baseline (asserted >= EXACT_MATCH_FLOOR).

    PYTHONPATH=src python benchmarks/serve_paged.py
    PYTHONPATH=src python benchmarks/serve_paged.py --kv-quant int8
    PYTHONPATH=src python benchmarks/serve_paged.py --smoke   # CI: tiny trace
                                                              # + exactness
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List

import jax
import numpy as np

from repro.config import ServeConfig, get_config
from repro.serve.engine import ContinuousEngine, PagedEngine, QueueFull
from repro.models.transformer import init_params
from repro.launch.compile_cache import enable_compile_cache

from _emit import emit

# Documented floor for the greedy exact-match rate of int8-quantized KV vs
# the dense f32 baseline on the repro-tiny bench trace (token-level; measured
# 0.74-0.91 across seeds).  Greedy decoding compounds: one argmax flip makes
# every later token of that request diverge, so the token-level rate
# *underestimates* per-step agreement badly — first-token agreement is
# 0.97-1.0 on the same runs.  repro-tiny's random-init logits are near
# uniform (tiny argmax gaps); trained checkpoints sit far above this floor.
# Also asserted by tests/test_serve_paged.py.
EXACT_MATCH_FLOOR = 0.60


@dataclasses.dataclass
class TraceItem:
    prompt: np.ndarray
    max_new: int


def make_shared_prefix_trace(vocab: int, n: int, seed: int, *,
                             num_prefixes: int = 3, prefix_len: int = 32,
                             suffix_lens=(4, 8), mean_new: float = 12.0,
                             max_new: int = 32) -> List[TraceItem]:
    """Heavy-tailed budgets over prompts that share a few long prefixes;
    arrival order from interleaved Poisson processes (one per prefix)."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab, prefix_len).astype(np.int32)
                for _ in range(num_prefixes)]
    arrivals = []
    for pi in range(num_prefixes):
        t = 0.0
        for _ in range(n // num_prefixes):
            t += rng.exponential(1.0)
            sl = int(rng.choice(suffix_lens))
            new = int(np.clip(rng.geometric(1.0 / mean_new), 2, max_new))
            arrivals.append((t, pi, sl, new))
    arrivals.sort()
    return [TraceItem(np.concatenate(
                [prefixes[pi], rng.integers(0, vocab, sl).astype(np.int32)]),
                new)
            for _, pi, sl, new in arrivals]


def replay(eng, trace: List[TraceItem]):
    t0 = time.time()
    rids = []
    for it in trace:
        while True:
            try:
                rids.append(eng.submit(it.prompt, it.max_new))
                break
            except QueueFull:
                eng.step()
    eng.run()
    eng.executor.drain()
    wall = time.time() - t0
    useful = sum(len(eng.request(r).output) for r in rids)
    ttfts = [eng.request(r).first_token_at - eng.request(r).submitted_at
             for r in rids]
    return wall, useful, float(np.mean(ttfts)), rids


def outputs_of(eng, rids) -> Dict[int, List[int]]:
    return {i: eng.request(r).output for i, r in enumerate(rids)}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=30)
    ap.add_argument("--dense-slots", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--kv-quant", default="none", choices=("none", "int8"),
                    help="also run an int8-quantized paged engine at the "
                         "same cache memory (2x the f32 paged slots)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace + exactness assertions (CI)")
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 12)
        args.reps = 1

    cfg = get_config("repro-tiny")
    params = init_params(jax.random.PRNGKey(0), cfg)
    C, pg, B = args.max_seq_len, args.page_size, args.dense_slots
    trace = make_shared_prefix_trace(cfg.vocab_size, args.requests, args.seed)

    # Fixed cache memory: the dense engine's B x C cache entries buy the
    # paged engine a pool of B*C/pg pages — on which it runs 2B slots,
    # because residency follows live tokens (~prefix sharing included).
    dense = ContinuousEngine(cfg, params, ServeConfig(
        max_batch=B, max_seq_len=C, max_queue=4 * args.requests,
        prefill_buckets=(8, 16, 32, 64)))
    paged = PagedEngine(cfg, params, ServeConfig(
        max_batch=2 * B, max_seq_len=C, max_queue=4 * args.requests,
        prefill_buckets=(8, 16, 32, 64),
        page_size=pg, num_pages=B * C // pg + 1, cold_pages=256))
    d_bytes, p_bytes = dense.cache_bytes(), paged.cache_bytes()
    assert p_bytes <= d_bytes * (1 + 1.0 / (B * C // pg)), \
        "paged pool must not exceed the dense engine's cache memory"

    paged8 = None
    if args.kv_quant == "int8":
        # Byte-match the quantized pool to the f32 paged engine: an int8
        # page is N + 4 bytes per (entry, head) vs the f32 page's 4N, so the
        # same memory buys ~3.55x pages — run 2x the f32-paged slots on it.
        N = cfg.d_model // cfg.num_heads
        q_pages = (B * C // pg) * (4 * N) // (N + 4)
        paged8 = PagedEngine(cfg, params, ServeConfig(
            max_batch=4 * B, max_seq_len=C, max_queue=4 * args.requests,
            prefill_buckets=(8, 16, 32, 64), kv_quant="int8",
            page_size=pg, num_pages=q_pages + 1, cold_pages=256))
        q_bytes = paged8.cache_bytes()
        assert q_bytes <= p_bytes, \
            (f"int8 pool ({q_bytes}B) must fit the f32 paged engine's cache "
             f"memory ({p_bytes}B)")

    # Warmup: compile every admit bucket every engine will see.
    warm = [np.zeros(L, np.int32)
            for L in sorted({len(it.prompt) for it in trace})]
    for w in warm:
        dense.generate([w], 2)
        paged.generate([w], 2)
        if paged8 is not None:
            paged8.generate([w], 2)

    runs_d = [replay(dense, trace) for _ in range(args.reps)]
    runs_p = [replay(paged, trace) for _ in range(args.reps)]
    d_wall, d_useful, d_ttft, d_rids = min(runs_d, key=lambda r: r[0])
    p_wall, p_useful, p_ttft, p_rids = min(runs_p, key=lambda r: r[0])
    d_tps, p_tps = d_useful / d_wall, p_useful / p_wall
    pstats = paged.stats()

    print(f"trace: {len(trace)} requests, shared prefixes (32 tok) + "
          f"4/8 suffixes, geometric budgets; fixed cache memory")
    print(f"{'engine':<10} {'slots':>5} {'cache_MB':>9} {'wall_s':>7} "
          f"{'tok/s':>7} {'ttft_ms':>8} {'hit_rate':>8}")
    print(f"{'dense':<10} {B:>5} {d_bytes/2**20:>9.2f} {d_wall:>7.2f} "
          f"{d_tps:>7.1f} {1e3*d_ttft:>8.0f} {'-':>8}")
    print(f"{'paged':<10} {2*B:>5} {p_bytes/2**20:>9.2f} {p_wall:>7.2f} "
          f"{p_tps:>7.1f} {1e3*p_ttft:>8.0f} "
          f"{pstats['prefix_hit_rate']:>8.2f}")

    # Exactness: f32 paged decode must reproduce the dense engine's tokens
    # (global attention; greedy sampling; row-independent fast path).
    d_out, p_out = outputs_of(dense, d_rids), outputs_of(paged, p_rids)
    mismatches = [i for i in d_out if d_out[i] != p_out[i]]
    assert not mismatches, f"paged != dense for requests {mismatches}"
    print("paged outputs identical to dense: OK")

    exact_rate = 1.0
    q_payload = None
    if paged8 is not None:
        runs_q = [replay(paged8, trace) for _ in range(args.reps)]
        q_wall, q_useful, q_ttft, q_rids = min(runs_q, key=lambda r: r[0])
        q_tps = q_useful / q_wall
        qstats = paged8.stats()
        print(f"{'paged-int8':<10} {4*B:>5} {q_bytes/2**20:>9.2f} "
              f"{q_wall:>7.2f} {q_tps:>7.1f} {1e3*q_ttft:>8.0f} "
              f"{qstats['prefix_hit_rate']:>8.2f}")
        q_out = outputs_of(paged8, q_rids)
        tok_match = tok_total = 0
        for i in d_out:
            for u, v in zip(d_out[i], q_out[i]):
                tok_match += int(u == v)
            tok_total += len(d_out[i])
        exact_rate = tok_match / max(1, tok_total)
        print(f"slots at fixed cache memory: int8 {4*B} vs f32-paged {2*B} "
              f"(2.0x) vs dense {B} (4.0x)")
        print(f"greedy exact-match rate vs dense f32: {exact_rate:.3f} "
              f"({tok_match}/{tok_total} tokens, floor {EXACT_MATCH_FLOOR})")
        assert exact_rate >= EXACT_MATCH_FLOOR, \
            (f"int8 exact-match rate {exact_rate:.3f} below documented "
             f"floor {EXACT_MATCH_FLOOR}")
        q_payload = {"slots": 4 * B, "cache_bytes": q_bytes,
                     "wall_s": q_wall, "tok_s": q_tps,
                     "mean_ttft_s": q_ttft,
                     "prefix_hit_rate": qstats["prefix_hit_rate"],
                     "kv_pool": qstats["kv_pool"]}
    else:
        print(f"slots at fixed memory: {2*B}/{B} = 2.0x   "
              f"pool: {pstats['kv_pool']}")

    bench_backend = (paged8 or paged).backend
    emit("serve_paged", {
        "trace_requests": len(trace),
        "smoke": args.smoke,
        "kv_quant": args.kv_quant,
        "handoff_bytes": bench_backend.handoff_bytes_for(C),
        "exact_match_rate": exact_rate,
        "exact_match_floor": EXACT_MATCH_FLOOR,
        "dense": {"slots": B, "cache_bytes": d_bytes, "wall_s": d_wall,
                  "tok_s": d_tps, "mean_ttft_s": d_ttft},
        "paged": {"slots": 2 * B, "cache_bytes": p_bytes, "wall_s": p_wall,
                  "tok_s": p_tps, "mean_ttft_s": p_ttft,
                  "prefix_hit_rate": pstats["prefix_hit_rate"],
                  "kv_pool": pstats["kv_pool"]},
        **({"paged_int8": q_payload} if q_payload is not None else {}),
        "exact_vs_dense": True,
    })
    if not args.smoke:
        assert pstats["prefix_hit_rate"] > 0.2, \
            "shared-prefix trace should reuse prefix pages"
    dense.close()
    paged.close()
    if paged8 is not None:
        paged8.close()


if __name__ == "__main__":
    main()
