"""Serving CLI: continuous batching over a mixed-length request stream.

  PYTHONPATH=src python -m repro.launch.serve --arch repro-tiny \
      --requests 16 --mean-prompt-len 32 --mean-new-tokens 16

Requests with random prompt lengths / token budgets are submitted through the
admission plane; the engine interleaves them over the fixed-shape decode
batch and reports per-request TTFT plus aggregate throughput.

Engine selection is one axis: ``--engine-mode
{fixed,continuous,paged,disaggregated,cluster}`` (see
``repro.serve.make_engine``).  Every mode covers every arch: paged /
disaggregated / cluster pick their cache backend per arch (block-table KV
paging or the recurrent snapshot pool).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.config import EngineMode, ServeConfig, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_params
from repro.serve import QueueFull, ServeCluster, make_engine
from repro.serve.sampler import SamplingParams


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="repro-tiny")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--mean-prompt-len", type=int, default=32)
    ap.add_argument("--mean-new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine-mode", default="",
                    choices=[m.value for m in EngineMode] + [""],
                    help="which serve engine to run (default: continuous)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="decode replicas (engine-mode=cluster)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV pool pages (0 -> full residency per slot)")
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--kv-quant", default="none", choices=("none", "int8"),
                    help="paged KV page format: int8 stores pages quantized "
                         "(~3.5x pages per byte; paged-backend archs only)")
    ap.add_argument("--route", default="auto",
                    choices=("auto", "remote", "local"),
                    help="prefill routing: cost model per request (auto) "
                         "or forced (engine-mode=disaggregated)")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative decoding: a drafter proposes "
                         "--draft-k tokens per slot, the target verifies "
                         "them in one batched forward (greedy rows only)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens proposed per macro decode step")
    ap.add_argument("--draft-model", default="self:1",
                    help="drafter spec: 'self:<n>' (first n target layers), "
                         "'self-int8' (int8-quantized target), or a "
                         "registry arch name with the same vocab")
    args = ap.parse_args()
    enable_compile_cache()

    mode = args.engine_mode
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    scfg = ServeConfig(max_batch=args.max_batch,
                       temperature=args.temperature, seed=args.seed,
                       page_size=args.page_size, num_pages=args.num_pages,
                       prefix_cache=not args.no_prefix_cache,
                       kv_quant=args.kv_quant,
                       speculative=args.speculative,
                       draft_k=args.draft_k,
                       draft_model=args.draft_model,
                       disagg_route=args.route,
                       engine_mode=mode or EngineMode.CONTINUOUS.value,
                       num_replicas=args.replicas)
    if (mode or "") == EngineMode.FIXED.value:
        ap.error("--engine-mode fixed is the equal-length benchmark "
                 "baseline (no admission plane); use "
                 "benchmarks/serve_continuous.py to exercise it")
    eng = make_engine(cfg, params, scfg)
    is_cluster = isinstance(eng, ServeCluster)
    sampling = SamplingParams.from_config(scfg)

    rng = np.random.default_rng(args.seed)
    lens = np.clip(rng.poisson(args.mean_prompt_len, args.requests), 1, 256)
    news = np.clip(rng.poisson(args.mean_new_tokens, args.requests), 1, 128)
    fe_shape = None
    if cfg.frontend != "none" and not is_cluster:
        fe_shape = (1, cfg.frontend_seq_len, cfg.frontend_dim)

    t0 = time.time()
    rids = []
    for L, n in zip(lens, news):
        prompt = rng.integers(0, cfg.vocab_size, int(L)).astype(np.int32)
        fe = (rng.standard_normal(fe_shape).astype(np.float32)
              if fe_shape else None)
        while True:
            try:
                if is_cluster:
                    rids.append(eng.submit(prompt, int(n), sampling=sampling))
                else:
                    rids.append(eng.submit(prompt, int(n), sampling,
                                           frontend_embeds=fe))
                break
            except QueueFull:
                eng.step()
    eng.run()
    eng.executor.drain()
    dt = time.time() - t0

    results = [eng.result(r) for r in rids]
    total_new = sum(len(r["tokens"]) for r in results)
    ttfts = [r["ttft_s"] for r in results]
    print(f"requests={args.requests} slots={args.max_batch} "
          f"mean_prompt={args.mean_prompt_len} mean_new={args.mean_new_tokens}")
    print(f"wall={dt:.2f}s  throughput={total_new/dt:.1f} tok/s  "
          f"mean_ttft={1e3*np.mean(ttfts):.0f}ms  stats={eng.stats()}")
    for rid, out in zip(rids[:4], results[:4]):
        print(f"  req{rid}: prompt={out['prompt_len']} "
              f"tokens={out['tokens'][:10]}{'...' if len(out['tokens']) > 10 else ''}")
    if hasattr(eng, "route_plan"):
        print("routing (cost-model placements):")
        print(eng.route_plan().to_table())
    eng.close()


if __name__ == "__main__":
    main()
