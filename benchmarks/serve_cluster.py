"""Multi-replica serve cluster: throughput scaling + per-tenant QoS.

Two experiments over ``repro.serve.ServeCluster``:

**Scaling** — one decode-heavy multi-tenant trace (shared-prefix sessions,
heavy-tailed budgets) replayed through a 1-replica and an N-replica cluster
at the same per-replica resources.  Replicas are independent endpoints that
this container must *simulate serially*, so aggregate throughput is reported
against the parallel-world wall clock::

    wall_parallel ~= wall_serial - sum(busy_i) + max(busy_i)

(each endpoint's device-busy seconds overlap on a real pod; only the longest
pole is wall time).  The fixed-shape decode step costs the same at any
occupancy, so N replicas each run ~1/N of the steps: aggregate tok/s should
scale near-linearly.  Outputs are asserted bit-identical to a single
``PagedEngine`` over the same trace — routing must never change tokens.

**QoS** — one replica, paid vs best-effort tenants.  A best-effort flood
fills every slot, then paid requests arrive.  Admission preempts the
youngest best-effort slot per paid request (re-enqueued as a continuation,
not failed), so paid p99 TTFT stays within 1.5x of its uncontended value
while best-effort degrades gracefully — every flooded request still
completes with its full token budget.

    PYTHONPATH=src python benchmarks/serve_cluster.py
    PYTHONPATH=src python benchmarks/serve_cluster.py --smoke   # CI
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List

import jax
import numpy as np

from _emit import emit
from repro.config import ServeConfig, get_config
from repro.serve import PagedEngine, QueueFull, ServeCluster, TenantSpec
from repro.models.transformer import init_params
from repro.launch.compile_cache import enable_compile_cache


@dataclasses.dataclass
class TraceItem:
    prompt: np.ndarray
    max_new: int
    tenant: str = "default"


def make_session_trace(vocab: int, n: int, seed: int, *,
                       num_sessions: int = 4, prefix_len: int = 32,
                       suffix_lens=(4, 8, 16), mean_new: float = 18.0,
                       max_new: int = 48) -> List[TraceItem]:
    """Shared-prefix sessions (each session = one chat template / few-shot
    preamble) with heavy-tailed decode budgets, Poisson-interleaved: the
    decode-heavy regime where replica scaling pays, with enough prefix
    structure for affinity routing to matter."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab, prefix_len).astype(np.int32)
                for _ in range(num_sessions)]
    arrivals = []
    for si in range(num_sessions):
        t = 0.0
        for _ in range(n // num_sessions):
            t += rng.exponential(1.0)
            sl = int(rng.choice(suffix_lens))
            new = int(np.clip(rng.geometric(1.0 / mean_new), 4, max_new))
            arrivals.append((t, si, sl, new))
    arrivals.sort()
    return [TraceItem(np.concatenate(
                [prefixes[si], rng.integers(0, vocab, sl).astype(np.int32)]),
                new)
            for _, si, sl, new in arrivals]


def make_cluster(cfg, params, *, replicas: int, slots: int, seq_len: int,
                 page_size: int, max_queue: int,
                 tenants=None) -> ServeCluster:
    scfg = ServeConfig(
        engine_mode="cluster", num_replicas=replicas, max_batch=slots,
        max_seq_len=seq_len, page_size=page_size,
        num_pages=slots * seq_len // page_size + 1, cold_pages=256,
        max_queue=max_queue, prefill_buckets=(8, 16, 32, 64))
    return ServeCluster(cfg, params, scfg, tenants=tenants)


def replay(clu: ServeCluster, trace: List[TraceItem]):
    """Offered load >> capacity: submit everything, drive to completion."""
    t0 = time.time()
    crids = []
    for it in trace:
        while True:
            try:
                crids.append(clu.submit(it.prompt, it.max_new, it.tenant))
                break
            except QueueFull:
                clu.step()
    clu.run()
    wall = time.time() - t0
    results = [clu.result(c) for c in crids]
    useful = sum(len(r["tokens"]) for r in results)
    return wall, useful, results


def parallel_wall(wall: float, busy: Dict[str, float]) -> float:
    """Serial-simulation correction: endpoint busy intervals overlap on a
    real pod; only the longest pole stays on the wall clock."""
    return max(wall - sum(busy.values()) + max(busy.values()), 1e-9)


def reset_busy(clu: ServeCluster) -> None:
    clu.busy_s = [0.0] * len(clu.replicas)
    clu.prefill_busy_s = 0.0


def run_scaling(cfg, params, trace, *, replicas_hi: int, slots: int,
                seq_len: int, page_size: int, reps: int):
    out = {}
    ref_outputs = None
    for label, R in (("r1", 1), (f"r{replicas_hi}", replicas_hi)):
        clu = make_cluster(cfg, params, replicas=R, slots=slots,
                           seq_len=seq_len, page_size=page_size,
                           max_queue=4 * len(trace))
        # Warmup compiles every admit bucket; programs are cached
        # process-wide, so the first cluster pays and the rest reuse.
        for L in sorted({len(it.prompt) for it in trace}):
            clu.generate([np.zeros(L, np.int32)], 2)
        runs = []
        for _ in range(reps):
            reset_busy(clu)
            wall, useful, results = replay(clu, trace)
            runs.append((wall, useful, results, clu.busy_seconds()))
        wall, useful, results, busy = min(runs, key=lambda r: r[0])
        pw = parallel_wall(wall, busy)
        out[label] = {
            "replicas": R,
            "wall_serial_s": round(wall, 4),
            "wall_parallel_s": round(pw, 4),
            "busy_s": {k: round(v, 4) for k, v in busy.items()},
            "useful_tokens": useful,
            "tok_s_parallel": round(useful / pw, 2),
            "router_picks": dict(clu.router.planner.picks),
        }
        if ref_outputs is None:
            # Exactness reference: a plain single PagedEngine on the trace.
            ref = PagedEngine(cfg, params, ServeConfig(
                max_batch=slots, max_seq_len=seq_len, page_size=page_size,
                num_pages=slots * seq_len // page_size + 1, cold_pages=256,
                max_queue=4 * len(trace), prefill_buckets=(8, 16, 32, 64)))
            ref_reqs = ref.generate([it.prompt for it in trace],
                                    max(it.max_new for it in trace))
            ref_outputs = {i: ref_reqs[i].output[:trace[i].max_new]
                           for i in range(len(trace))}
            ref.close()
        got = {i: r["tokens"] for i, r in enumerate(results)}
        mismatches = [i for i in got if got[i] != ref_outputs[i]]
        assert not mismatches, \
            f"{label}: cluster outputs diverge from single engine at " \
            f"{mismatches[:4]}"
        clu.close()
    out["speedup"] = round(
        out[f"r{replicas_hi}"]["tok_s_parallel"] / out["r1"]["tok_s_parallel"],
        2)
    return out


def run_qos(cfg, params, seed: int, *, slots: int, seq_len: int,
            page_size: int, n_paid: int, n_flood: int):
    """Paid p99 TTFT, uncontended vs under best-effort overload (1 replica:
    QoS is per-admission-plane; replica count is the scaling axis)."""
    rng = np.random.default_rng(seed)
    tenants = [TenantSpec("paid", priority=2),
               TenantSpec("free", priority=0)]
    paid_prompts = [rng.integers(0, cfg.vocab_size, int(L)).astype(np.int32)
                    for L in rng.choice((8, 16), n_paid)]
    flood_prompts = [rng.integers(0, cfg.vocab_size, int(L)).astype(np.int32)
                     for L in rng.choice((8, 16), n_flood)]

    def paid_ttfts(flood: bool):
        clu = make_cluster(cfg, params, replicas=1, slots=slots,
                           seq_len=seq_len, page_size=page_size,
                           max_queue=4 * (n_paid + n_flood), tenants=tenants)
        for L in (8, 16):       # warm the admit buckets
            clu.generate([np.zeros(L, np.int32)], 2)
        flood_crids = []
        if flood:
            for p in flood_prompts:     # long budgets: slots stay occupied
                flood_crids.append(clu.submit(p, 48, "free"))
            for _ in range(4):          # flood admitted, decoding
                clu.step()
        ttfts = []
        crids = []
        for p in paid_prompts:          # paid arrives mid-overload
            crid = clu.submit(p, 8, "paid")
            crids.append(crid)
            clu.step()                  # dispatch (preempting if needed)
        clu.run()
        for crid in crids:
            ttfts.append(clu.result(crid)["ttft_s"])
        stats = clu.stats()
        flood_done = [clu.result(c) for c in flood_crids]
        # Graceful degradation: every preempted best-effort request still
        # completed with its full budget, via continuations.
        short = [r for r in flood_done if len(r["tokens"]) != 48]
        assert not short, \
            f"{len(short)} best-effort requests lost tokens to preemption"
        clu.close()
        return ttfts, stats

    ttft_u, _ = paid_ttfts(flood=False)
    ttft_c, stats_c = paid_ttfts(flood=True)
    p99_u = float(np.percentile(ttft_u, 99))
    p99_c = float(np.percentile(ttft_c, 99))
    return {
        "paid_requests": n_paid,
        "best_effort_flood": n_flood,
        "uncontended_p99_ttft_s": round(p99_u, 4),
        "contended_p99_ttft_s": round(p99_c, 4),
        "ratio": round(p99_c / max(p99_u, 1e-9), 3),
        "preemptions": stats_c["qos"]["preemptions"],
        "best_effort_completed": n_flood,
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots per replica")
    ap.add_argument("--max-seq-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace, exactness + QoS mechanics only (CI)")
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 12)
        args.replicas = 2
        args.reps = 1

    cfg = get_config("repro-tiny")
    params = init_params(jax.random.PRNGKey(0), cfg)
    trace = make_session_trace(cfg.vocab_size, args.requests, args.seed)

    scaling = run_scaling(cfg, params, trace, replicas_hi=args.replicas,
                          slots=args.slots, seq_len=args.max_seq_len,
                          page_size=args.page_size, reps=args.reps)
    hi = f"r{args.replicas}"
    print(f"trace: {len(trace)} requests, {args.slots} slots/replica")
    print(f"{'cluster':<6} {'wall_s':>7} {'par_wall_s':>10} {'tok/s':>8} "
          f"{'picks'}")
    for label in ("r1", hi):
        s = scaling[label]
        print(f"{label:<6} {s['wall_serial_s']:>7.2f} "
              f"{s['wall_parallel_s']:>10.2f} {s['tok_s_parallel']:>8.1f} "
              f"{s['router_picks']}")
    print(f"scaling: {scaling['speedup']:.2f}x aggregate tok/s at "
          f"{args.replicas} replicas (parallel-world wall)")
    print("cluster outputs identical to single engine: OK")

    qos = run_qos(cfg, params, args.seed, slots=args.slots,
                  seq_len=args.max_seq_len, page_size=args.page_size,
                  n_paid=4 if args.smoke else 8,
                  n_flood=8 if args.smoke else 16)
    print(f"qos: paid p99 TTFT {1e3*qos['uncontended_p99_ttft_s']:.0f}ms "
          f"uncontended -> {1e3*qos['contended_p99_ttft_s']:.0f}ms under "
          f"best-effort overload ({qos['ratio']:.2f}x, "
          f"{qos['preemptions']} preemptions, all best-effort completed)")

    emit("serve_cluster", {
        "trace_requests": len(trace),
        "slots_per_replica": args.slots,
        "smoke": args.smoke,
        "scaling": scaling,
        "qos": qos,
    })

    if not args.smoke:
        assert scaling["speedup"] >= 3.0, \
            f"aggregate tok/s must scale >=3x at {args.replicas} replicas " \
            f"(got {scaling['speedup']:.2f}x)"
        assert qos["ratio"] <= 1.5, \
            f"paid p99 TTFT degraded {qos['ratio']:.2f}x under overload " \
            "(bound: 1.5x)"
    assert qos["preemptions"] > 0, \
        "the flood should have forced best-effort preemptions"


if __name__ == "__main__":
    main()
