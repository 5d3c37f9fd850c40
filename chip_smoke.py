"""Bring-up check: serve smollm-360m at its published widths on a TPU.

    python chip_smoke.py                # one chip: phases A, B and C
    python chip_smoke.py --four-chips   # four chips: the replica phase only

Everything goes through the entry points a user calls (``get_config`` ->
``init_params`` -> ``make_engine``), with random weights made from a fixed
seed, in this one process: a chip serves one process at a time.

  A  Default policy.  8 greedy requests, prompts of 16-400 tokens (five
     prefill buckets), 32 new tokens each, on a paged engine.  The trace runs
     twice on fresh engines: the first run compiles, the second is timed and
     must repeat the first's tokens.  The engine's decode and admit programs
     (``jit_step``, ``jit_admit``) must hold no copy or transpose of whole
     layers of the K/V page pool: they update it in place.
  B  The same trace with ``ExecPolicy(use_kernel=True)``.  The compiled
     decode program must hold the Pallas paged-attention kernel
     (``tpu_custom_call``), so a ``supported()`` refusal cannot quietly send
     decode to the gather oracle.  Greedy token agreement with A is printed,
     not asserted: random-init bf16 logits are nearly flat, so argmax flips
     between two correct attention paths are expected.
  C  Op parity on the chip: ``paged_attention`` and ``paged_attention_quant``
     against their ``ref.py`` oracles at smollm widths (see PARITY_TOL).

  --four-chips  A 4-replica ``ServeCluster`` (replica i on device i, prompts
     prefilled on device 0 and handed over as host bytes) serves 16 greedy
     requests.  Its tokens must equal one ``PagedEngine``'s on device 0, and
     every replica must still be alive at the end.

Every request must come back with its whole token budget and no error.  The
script exits non-zero when JAX finds no TPU or any check fails; the last line
of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.config import ServeConfig, get_config  # noqa: E402
from repro.kernels.paged_attention import ops as pa  # noqa: E402
from repro.launch.hlo_analysis import pool_relayouts  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.attention import kv_quantize  # noqa: E402
from repro.models.common import dtype_of  # noqa: E402
from repro.models.transformer import ExecPolicy, init_params  # noqa: E402
from repro.serve import make_engine, programs  # noqa: E402

ARCH = "smollm-360m"
SEED = 0
NEW_TOKENS = 32
# Prefill buckets 16, 64, 128, 256 and 512 of the default ServeConfig.
PROMPT_LENS = (16, 40, 64, 100, 150, 220, 300, 400)
# One bucket (32): each device compiles one admit program.
CLUSTER_PROMPT_LENS = tuple(range(17, 33))
# Phase C bound on max|kernel - oracle| / max|oracle|.  Both read the same
# bf16 (or int8 + f32 scale) pages and differ only in where they round to
# bf16: the oracle rounds the softmax weights, and for int8 pools the
# dequantized K/V, to bf16, where the kernel keeps f32 in VMEM.  That is a
# few bf16 ulps (2**-8 ~ 3.9e-3); 2e-2 leaves room for the longest rows.
PARITY_TOL = 2e-2


def _fail(msg: str) -> None:
    raise RuntimeError(msg)


def _complete(res: dict, vocab: int) -> dict:
    """A finished request's result, after checking it is whole."""
    if "error" in res:
        _fail(f"request failed: {res['error']}")
    toks = res["tokens"]
    if len(toks) != NEW_TOKENS:
        _fail(f"request returned {len(toks)} of {NEW_TOKENS} tokens")
    if min(toks) < 0 or max(toks) >= vocab:
        _fail(f"token id outside the vocabulary: {toks}")
    return res


def serve_trace(cfg, params, scfg, prompts, policy):
    """Submit every prompt to a fresh engine and run it dry.  Returns
    (checked result per request, seconds, engine); the engine is left
    open."""
    eng = make_engine(cfg, params, scfg, policy)
    t0 = time.perf_counter()
    rids = [eng.submit(p, NEW_TOKENS) for p in prompts]
    eng.run()
    secs = time.perf_counter() - t0
    return [_complete(eng.result(r), cfg.vocab_size) for r in rids], secs, eng


def _peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use", "not reported")


def _prompts(cfg, lens, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lens]


def compiled_step(eng, cfg, policy):
    """The paged engine's decode program, compiled again from the engine's
    live arguments (the persistent compilation cache makes that a
    lookup)."""
    return programs.paged_decode_program(cfg, policy).lower(
        eng.params, eng.states, eng._key, eng._mirrors,
        jnp.asarray(eng.backend._table)).compile()


def compiled_admit(eng, cfg, policy, bucket: int):
    """The paged engine's admit program for one suffix bucket, compiled
    from the engine's live state and an admission batch of that shape."""
    M = eng.backend.pages_per_seq
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    batch = {"tokens": jnp.zeros((1, bucket), jnp.int32),
             "positions": jnp.arange(bucket, dtype=jnp.int32)[None],
             "length": i32(bucket), "hit_len": i32(0),
             "table": jnp.zeros(M, jnp.int32),
             "assign": jnp.zeros(M, jnp.int32), "slot": i32(0),
             "temp": jnp.asarray(0.0, jnp.float32), "top_k": i32(0),
             "top_p": jnp.asarray(1.0, jnp.float32)}
    return programs.paged_admit_program(
        cfg, policy, eng.scfg.max_seq_len).lower(
            eng.params, eng.states, batch, eng._key, eng._mirrors).compile()


def decode_has_kernel(eng, cfg, policy) -> bool:
    """Whether the paged engine's compiled decode program holds a Pallas
    kernel."""
    return "tpu_custom_call" in compiled_step(eng, cfg, policy).as_text()


def pool_copies(eng, cfg, policy) -> dict:
    """Per program: (pool-sized copies and transposes, scratch bytes)."""
    pool = [leaf for leaf in jax.tree.leaves(eng.states) if leaf.ndim == 3]
    out = {}
    for name, exe in (("jit_step", compiled_step(eng, cfg, policy)),
                      ("jit_admit", compiled_admit(eng, cfg, policy, 16))):
        out[name] = (pool_relayouts(exe.as_text(), pool),
                     exe.memory_analysis().temp_size_in_bytes)
    return out


def phase_a(cfg, params, scfg, dev):
    prompts = _prompts(cfg, PROMPT_LENS, SEED)
    warm, warm_s, eng = serve_trace(cfg, params, scfg, prompts, ExecPolicy())
    eng.close()
    del eng
    res, wall_s, eng = serve_trace(cfg, params, scfg, prompts, ExecPolicy())
    copies = pool_copies(eng, cfg, ExecPolicy())
    eng.close()
    del eng
    toks = [r["tokens"] for r in res]
    if toks != [r["tokens"] for r in warm]:
        _fail("phase A: a second run of the same trace gave other tokens")
    report = " ".join(f"{name}_pool_copies={len(found)} {name}_temp_bytes="
                      f"{temp}" for name, (found, temp) in copies.items())
    print(f"phase A: {report}", flush=True)
    for name, (found, _) in copies.items():
        if found:
            _fail(f"phase A: {name} copies whole layers of the page pool:\n"
                  + "\n".join(line[:200] for line in found))
    print(f"phase A: ok requests={len(prompts)} prompt_lens={PROMPT_LENS} "
          f"new_tokens={NEW_TOKENS} warmup_s={warm_s} (compiles included) "
          f"wall_s={wall_s} peak_bytes_in_use={_peak_bytes(dev)}", flush=True)
    return toks


def phase_b(cfg, params, scfg, dev, toks_a):
    policy = ExecPolicy(use_kernel=True)
    prompts = _prompts(cfg, PROMPT_LENS, SEED)
    res, secs, eng = serve_trace(cfg, params, scfg, prompts, policy)
    toks = [r["tokens"] for r in res]
    has_kernel = decode_has_kernel(eng, cfg, policy)
    eng.close()
    del eng
    if not has_kernel:
        _fail("phase B: the use_kernel decode program holds no "
              "tpu_custom_call; decode ran the gather oracle")
    same = sum(a == b for ra, rb in zip(toks_a, toks) for a, b in zip(ra, rb))
    total = sum(len(r) for r in toks)
    print(f"phase B: ok decode_tpu_custom_call=True wall_s={secs} (compiles "
          f"included) greedy_agreement_with_A={same}/{total}="
          f"{same / total} peak_bytes_in_use={_peak_bytes(dev)}", flush=True)


def phase_c(cfg, scfg):
    J, N = cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // J
    B, page = scfg.max_batch, scfg.page_size
    M = scfg.max_seq_len // page
    P, T = B * M + 1, M * page
    rng = np.random.default_rng(SEED)
    # Unscaled q: logits ~ N(0, N/4), a peaked softmax that a wrong page or
    # head would visibly move.
    q = jnp.asarray(rng.standard_normal((B, J, G, N)) * 0.5,
                    dtype_of(cfg.dtype))
    kf = jnp.asarray(rng.standard_normal((P, page, J, N)), jnp.float32)
    vf = jnp.asarray(rng.standard_normal((P, page, J, N)), jnp.float32)
    table = jnp.asarray(
        1 + np.stack([rng.permutation(P - 1)[:M] for _ in range(B)]),
        jnp.int32)
    lens = [1, page - 1, page, page + 1, T // 3, T // 2 + 3, T - 1, T]
    lengths = jnp.asarray((lens * B)[:B], jnp.int32)
    kq, ks = kv_quantize(kf)
    vq, vs = kv_quantize(vf)
    kb, vb = kf.astype(q.dtype), vf.astype(q.dtype)
    cases = [
        ("bf16", pa.paged_attention, pa.paged_attention_ref,
         (q, kb, vb, table, lengths)),
        ("int8", pa.paged_attention_quant, pa.paged_attention_quant_ref,
         (q, kq, vq, ks, vs, table, lengths)),
    ]
    report = []
    for name, kernel, ref, args in cases:
        if not pa.supported(q, args[1]):
            _fail(f"phase C: supported() refuses the {name} pool at "
                  f"smollm widths")
        if "tpu_custom_call" not in jax.jit(kernel).lower(*args).as_text():
            _fail(f"phase C: {name} kernel did not lower to a TPU kernel")
        out = np.asarray(jax.jit(kernel)(*args), np.float32)
        want = np.asarray(jax.jit(ref)(*args), np.float32)
        if out.shape != want.shape or not np.all(np.isfinite(out)):
            _fail(f"phase C: {name} kernel output {out.shape} is not a "
                  f"finite {want.shape}")
        err = float(np.max(np.abs(out - want)) / np.max(np.abs(want)))
        if not err <= PARITY_TOL:
            _fail(f"phase C: {name} kernel vs oracle error {err} > "
                  f"{PARITY_TOL}")
        report.append(f"{name}_err={err}")
    print(f"phase C: ok B={B} J={J} G={G} N={N} page={page} pages={P} "
          f"{' '.join(report)} tol={PARITY_TOL}", flush=True)


def phase_four_chips(cfg, params, scfg, devs):
    if len(devs) < 4:
        _fail(f"--four-chips needs 4 devices, JAX found {len(devs)}")
    prompts = _prompts(cfg, CLUSTER_PROMPT_LENS, SEED + 1)
    ref_res, ref_s, ref = serve_trace(cfg, params, scfg, prompts,
                                      ExecPolicy())
    ref_toks = [r["tokens"] for r in ref_res]
    ref_dev = jax.tree.leaves(ref.states)[0].devices()
    ref.close()
    del ref
    print(f"reference engine: ok requests={len(prompts)} device={ref_dev} "
          f"s={ref_s} (compiles included)", flush=True)
    cscfg = dataclasses.replace(scfg, engine_mode="cluster", num_replicas=4)
    res, secs, clu = serve_trace(cfg, params, cscfg, prompts, ExecPolicy())
    toks = [r["tokens"] for r in res]
    served = [r["replica"] for r in res]
    placed = []
    for i, rep in enumerate(clu.replicas):
        where = (jax.tree.leaves(rep.params)[0].devices()
                 | jax.tree.leaves(rep.states)[0].devices())
        if len(where) != 1:
            _fail(f"replica {i}: params and decode state on {where}")
        placed.append(where.pop())
        print(f"replica {i}: device={placed[-1]} served={served.count(i)} "
              f"alive={clu.alive[i]}", flush=True)
    alive = list(clu.alive)
    deaths = clu.stats()["qos"]["replica_deaths"]
    clu.close()
    if len(set(placed)) != 4:
        _fail(f"replicas share devices: {placed}")
    if not all(alive) or deaths:
        _fail(f"replica died: alive={alive} deaths={deaths}")
    if toks != ref_toks:
        bad = [i for i, (a, b) in enumerate(zip(toks, ref_toks)) if a != b]
        _fail(f"cluster tokens differ from the single engine's on "
              f"requests {bad}")
    print(f"four chips: ok replicas=4 requests={len(prompts)} "
          f"bit_identical_to_reference=True cluster_s={secs} (compiles "
          f"included)", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica ServeCluster phase")
    args = ap.parse_args()

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    # One compiled program: run op by op, the init dispatches (and compiles)
    # each of its hundreds of ops alone.
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"model: {ARCH} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.head_dim} "
          f"params={n_params} dtype={cfg.dtype} init_s="
          f"{time.perf_counter() - t0}", flush=True)

    if args.four_chips:
        phase_four_chips(cfg, params, ServeConfig(
            engine_mode="paged", max_batch=4, max_seq_len=256), devs)
    else:
        scfg = ServeConfig(engine_mode="paged", max_batch=8,
                           max_seq_len=1024)
        toks_a = phase_a(cfg, params, scfg, devs[0])
        phase_b(cfg, params, scfg, devs[0], toks_a)
        phase_c(cfg, scfg)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
