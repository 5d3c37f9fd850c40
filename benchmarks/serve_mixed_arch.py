"""Mixed-arch serve cluster: transformer + recurrent traffic, one cluster.

The CacheBackend layer gives every arch in ``configs/`` the same serve
plane: block-table KV paging for global-attention archs, the snapshot pool
for recurrent/SWA archs.  This benchmark drives one ``ServeCluster`` with
two model groups — a transformer ("default") and an rwkv6 recurrent arch —
under concurrent interleaved traffic, and reports aggregate and per-group
throughput against the parallel-world wall clock (replicas are independent
endpoints simulated serially here; see benchmarks/serve_cluster.py).

Outputs are asserted bit-identical per group to a plain ``ContinuousEngine``
over the same prompts — mixed-arch routing must never change tokens.

    PYTHONPATH=src python benchmarks/serve_mixed_arch.py
    PYTHONPATH=src python benchmarks/serve_mixed_arch.py --smoke   # CI
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import numpy as np

from _emit import emit
from repro.config import ServeConfig, get_config
from repro.serve import ContinuousEngine, QueueFull, ServeCluster
from repro.models.transformer import init_params
from repro.launch.compile_cache import enable_compile_cache


def make_trace(vocab: int, n: int, seed: int, *, lens=(8, 16, 24),
               mean_new: float = 12.0, max_new: int = 32):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, int(rng.choice(lens))).astype(np.int32),
             int(np.clip(rng.geometric(1.0 / mean_new), 4, max_new)))
            for _ in range(n)]


def parallel_wall(wall: float, busy: Dict[str, float]) -> float:
    return max(wall - sum(busy.values()) + max(busy.values()), 1e-9)


def replay(clu: ServeCluster, traces: Dict[str, list]):
    """Interleave both groups' submissions round-robin, drive to
    completion; returns wall plus {model -> [(crid, result)]}."""
    order: List[tuple] = []
    longest = max(len(t) for t in traces.values())
    for i in range(longest):
        for model, trace in traces.items():
            if i < len(trace):
                order.append((model, trace[i]))
    t0 = time.time()
    crids: Dict[str, list] = {m: [] for m in traces}
    for model, (prompt, max_new) in order:
        while True:
            try:
                crids[model].append(clu.submit(prompt, max_new, model=model))
                break
            except QueueFull:
                clu.step()
    clu.run()
    wall = time.time() - t0
    return wall, {m: [(c, clu.result(c)) for c in cs]
                  for m, cs in crids.items()}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=24,
                    help="requests per model group")
    ap.add_argument("--replicas", type=int, default=2,
                    help="decode replicas per model group")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=96)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace, exactness + mechanics only (CI)")
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 6)
        args.replicas = 1

    t_cfg = get_config("repro-tiny")
    r_cfg = get_config("rwkv6-3b").reduced()
    t_params = init_params(jax.random.PRNGKey(0), t_cfg)
    r_params = init_params(jax.random.PRNGKey(1), r_cfg)

    scfg = ServeConfig(
        engine_mode="cluster", num_replicas=args.replicas,
        max_batch=args.slots, max_seq_len=args.max_seq_len,
        page_size=args.page_size,
        num_pages=args.slots * args.max_seq_len // args.page_size + 1,
        cold_pages=128, max_queue=8 * args.requests,
        prefill_buckets=(8, 16, 32), cluster_prefill=False)
    clu = ServeCluster(t_cfg, t_params, scfg,
                       extra_models={"rwkv6": (r_cfg, r_params)})

    traces = {
        "default": make_trace(t_cfg.vocab_size, args.requests, args.seed),
        "rwkv6": make_trace(r_cfg.vocab_size, args.requests, args.seed + 1),
    }
    # Warmup: compile every admit bucket for both groups.
    for model, trace in traces.items():
        for L in sorted({len(p) for p, _ in trace}):
            clu.generate([np.zeros(L, np.int32)], 2, model=model)
    clu.busy_s = [0.0] * len(clu.replicas)

    wall, results = replay(clu, traces)
    busy = clu.busy_seconds()
    pw = parallel_wall(wall, busy)
    per_group = {}
    for model, recs in results.items():
        toks = sum(len(r["tokens"]) for _, r in recs)
        per_group[model] = {"requests": len(recs), "tokens": toks,
                            "tok_s_parallel": round(toks / pw, 2)}
    total_toks = sum(g["tokens"] for g in per_group.values())

    # Exactness: each group must match its own dense baseline exactly.
    refs = {"default": (t_cfg, t_params), "rwkv6": (r_cfg, r_params)}
    for model, (cfg, params) in refs.items():
        ref = ContinuousEngine(cfg, params, scfg)
        expect = ref.generate([p for p, _ in traces[model]],
                              max(n for _, n in traces[model]))
        for i, (_, rec) in enumerate(results[model]):
            want = expect[i].output[:traces[model][i][1]]
            assert rec["tokens"] == want, \
                f"{model} request {i}: cluster diverges from dense baseline"
        ref.close()
    print("mixed-arch outputs identical to per-arch dense baselines: OK")

    st = clu.stats()
    kinds = {r["model"]: ("snapshot_pool" if "snapshot_pool" in r
                          else "kv_pool") for r in st["replicas"]}
    print(f"groups: {kinds} ({args.replicas} replicas each, "
          f"{args.slots} slots)")
    for model, g in per_group.items():
        print(f"{model:<8} {g['requests']:>3} reqs  {g['tokens']:>5} toks  "
              f"{g['tok_s_parallel']:>8.1f} tok/s")
    print(f"aggregate: {total_toks} tokens, {total_toks / pw:.1f} tok/s "
          f"(parallel wall {pw:.2f}s, serial {wall:.2f}s)")

    emit("serve_mixed_arch", {
        "smoke": args.smoke,
        "replicas_per_group": args.replicas,
        "slots_per_replica": args.slots,
        "backend_kinds": kinds,
        "per_group": per_group,
        "aggregate_tokens": total_toks,
        "aggregate_tok_s_parallel": round(total_toks / pw, 2),
        "wall_serial_s": round(wall, 4),
        "wall_parallel_s": round(pw, 4),
    })
    clu.close()

    assert kinds == {"default": "kv_pool", "rwkv6": "snapshot_pool"}, \
        f"expected one paged + one snapshot group, got {kinds}"
    assert st["completed"] >= 2 * args.requests


if __name__ == "__main__":
    main()
