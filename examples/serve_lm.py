"""Continuous-batching serving: heterogeneous requests share the decode batch.

    PYTHONPATH=src python examples/serve_lm.py --arch h2o-danube-1.8b --reduced

The --reduced flag serves the smoke variant of any assigned arch — including
the SWA / recurrent ones whose caches are constant-size (ring / O(1) state),
the property that makes long_500k serving possible.  Unlike the old
fixed-batch loop, prompts of different lengths and token budgets are admitted
as slots free up: nothing waits for the whole batch to drain.
"""
import argparse
import time

import jax
import numpy as np

from repro.config import ServeConfig, get_config
from repro.models.transformer import init_params
from repro.serve.engine import ContinuousEngine, QueueFull
from repro.serve.sampler import SamplingParams


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="repro-tiny")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousEngine(cfg, params,
                           ServeConfig(max_batch=args.max_batch))
    sampling = SamplingParams(temperature=0.8, top_k=40)

    rng = np.random.default_rng(0)
    t0 = time.time()
    rids = []
    for _ in range(args.requests):
        prompt_len = int(rng.integers(8, 65))
        new = int(rng.integers(4, args.new_tokens + 1))
        prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
        fe = None
        if cfg.frontend != "none":
            fe = rng.standard_normal(
                (1, cfg.frontend_seq_len, cfg.frontend_dim)).astype(np.float32)
        while True:
            try:
                rids.append(eng.submit(prompt, new, sampling,
                                       frontend_embeds=fe))
                break
            except QueueFull:
                eng.step()          # backpressure: drain a decode step
    eng.run()
    eng.executor.drain()
    dt = time.time() - t0

    n_new = sum(len(eng.request(r).output) for r in rids)
    ttft = min(eng.request(r).first_token_at - eng.request(r).submitted_at
               for r in rids)
    print(f"arch={cfg.arch_id} requests={args.requests} "
          f"slots={args.max_batch}")
    print(f"wall {dt:.2f}s | {n_new/dt:.1f} tok/s | best ttft {ttft*1e3:.0f}ms")
    print("sample:", eng.result(rids[0])["tokens"][:16])
    eng.close()


if __name__ == "__main__":
    main()
