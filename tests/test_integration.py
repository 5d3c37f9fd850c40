"""Integration: training convergence, resume-equivalence, data pipeline,
serving, offload plan A/B, elastic remesh planning."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (MeshConfig, OffloadConfig, ServeConfig, TrainConfig,
                          get_config)
from repro.data import (PrefetchLoader, SyntheticConfig, SyntheticLMDataset,
                        TokenFileDataset, batches, write_token_file)
from repro.runtime.elastic import remesh_plan
from repro.serve.engine import ServeEngine
from repro.train.steps import init_train_state, make_train_step
from repro.train.trainer import Trainer


def test_loss_decreases_memorization(rng):
    cfg = get_config("repro-tiny")
    tcfg = TrainConfig(global_batch=4, seq_len=32, steps=25, warmup_steps=2)
    state = init_train_state(rng, cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=0)
    toks = jax.random.randint(rng, (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1),
             "loss_mask": jnp.ones((4, 32), jnp.float32)}
    losses = []
    for _ in range(25):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 1.0


def test_microbatch_equivalence(rng):
    """grad accumulation over 2 microbatches == single batch step."""
    cfg = get_config("repro-tiny")
    t1 = TrainConfig(global_batch=4, seq_len=16, microbatches=1, grad_clip=0.0)
    t2 = TrainConfig(global_batch=4, seq_len=16, microbatches=2, grad_clip=0.0)
    s1 = init_train_state(rng, cfg, t1)
    s2 = jax.tree.map(lambda x: x, s1)
    toks = jax.random.randint(rng, (4, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1),
             "loss_mask": jnp.ones((4, 16), jnp.float32)}
    n1, m1 = jax.jit(make_train_step(cfg, t1))(s1, batch)
    n2, m2 = jax.jit(make_train_step(cfg, t2))(s2, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    for a, b in zip(jax.tree.leaves(n1["params"]), jax.tree.leaves(n2["params"])):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_compression_trains(rng):
    cfg = get_config("repro-tiny")
    tcfg = TrainConfig(global_batch=4, seq_len=32, steps=20, warmup_steps=2,
                       grad_compression="int8_ef")
    state = init_train_state(rng, cfg, tcfg)
    assert "ef" in state
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=0)
    toks = jax.random.randint(rng, (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1),
             "loss_mask": jnp.ones((4, 32), jnp.float32)}
    losses = []
    for _ in range(20):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5        # still converges compressed


def test_trainer_resume_continues(tmp_path, rng):
    cfg = get_config("repro-tiny")
    ds = SyntheticLMDataset(SyntheticConfig(cfg.vocab_size, 32))
    tcfg = TrainConfig(global_batch=2, seq_len=32, steps=6, warmup_steps=1,
                       ckpt_every=3, log_every=2)
    tr = Trainer(cfg, tcfg, OffloadConfig(), workdir=str(tmp_path))
    tr.run(batches(ds, 0, 2))
    tr2 = Trainer(cfg, tcfg, OffloadConfig(), workdir=str(tmp_path))
    start = tr2.init_or_resume()
    assert start == 6
    assert int(tr2.state["step"]) == 6
    tr2.finish()


def test_data_pipeline_determinism_and_sharding():
    ds = SyntheticLMDataset(SyntheticConfig(vocab_size=128, seq_len=16, seed=3))
    a = ds.example(shard=1, idx=5)
    b = ds.example(shard=1, idx=5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = ds.example(shard=2, idx=5)
    assert not np.array_equal(a["tokens"], c["tokens"])   # shards differ


def test_memmap_dataset(tmp_path):
    toks = np.arange(1000, dtype=np.int32)
    path = str(tmp_path / "toks.bin")
    write_token_file(path, toks)
    ds = TokenFileDataset(path, seq_len=10)
    ex = ds.example(3)
    np.testing.assert_array_equal(ex["tokens"], np.arange(30, 40))
    np.testing.assert_array_equal(ex["targets"], np.arange(31, 41))
    shards = [list(ds.shard_examples(i, 4)) for i in range(4)]
    allidx = sorted(x for s in shards for x in s)
    assert allidx == list(range(ds.num_examples))         # exact partition


def test_prefetch_loader_yields_all():
    def gen():
        for i in range(10):
            yield {"x": np.full(3, i)}
    loader = PrefetchLoader(gen(), depth=2)
    got = [int(b["x"][0]) for b in loader]
    assert got == list(range(10))


def test_serve_greedy_matches_argmax_rollout(rng):
    cfg = get_config("repro-tiny")
    state = init_train_state(rng, cfg, TrainConfig())
    eng = ServeEngine(cfg, state["params"], ServeConfig(temperature=0.0))
    prompts = [np.arange(6, dtype=np.int32)] * 2
    reqs = eng.generate(prompts, 4)
    assert all(len(r.output) == 4 for r in reqs.values())
    assert reqs[0].output == reqs[1].output     # same prompt -> same greedy

    # manual rollout with full forward
    from repro.models import transformer as tf
    toks = np.arange(6, dtype=np.int32)[None]
    out = []
    cur = toks
    for _ in range(4):
        logits, _, _ = tf.forward(state["params"], cfg, jnp.asarray(cur))
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        cur = np.concatenate([cur, [[nxt]]], axis=1)
    assert out == reqs[0].output


def test_offload_plan_ab():
    """Cost model on vs off: naive mode offloads the critical path; G4 not."""
    from repro.core.planner import OffloadPlanner, Placement
    naive = OffloadPlanner(OffloadConfig(enforce_cost_model=False,
                                         use_accelerators=False))
    wise = OffloadPlanner(OffloadConfig())
    p_naive = naive.plan_training(1e9)
    p_wise = wise.plan_training(1e9)
    assert p_naive.placement("activation_host_cache") == Placement.SIDECAR_SYNC
    assert p_wise.placement("activation_host_cache") == Placement.DEVICE
    assert p_wise.placement("checkpoint_serialize") == Placement.SIDECAR_ASYNC
    assert p_wise.placement("attention_hotspot") == Placement.ACCELERATOR


def test_remesh_plan():
    cfg = get_config("gemma-7b")
    old = MeshConfig(data=16, model=16, pod=2)
    new = MeshConfig(data=16, model=16, pod=1)     # lost a pod
    plan = remesh_plan(cfg, old, new, global_batch=256)
    assert plan.ok
    bad = remesh_plan(cfg, old, MeshConfig(data=7, model=16), global_batch=256)
    assert not bad.ok


def test_compile_cache_env_wins_else_fixed_checkout_path(monkeypatch,
                                                         tmp_path):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR, when set,
    is left to JAX and nothing is set in code; otherwise the cache goes to
    the fixed .jax_cache at the root of the checkout."""
    from repro.launch.compile_cache import enable_compile_cache
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == old
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = enable_compile_cache()
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
