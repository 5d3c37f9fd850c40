"""Continuous-batching engine: admission/eviction ordering, mid-stream join
exactness, sharded result retrieval, per-slot sampling.  Tier-1."""
import threading

import numpy as np
import pytest

import jax

from repro.config import ServeConfig, TrainConfig, get_config
from repro.core.endpoint import ShardedStore
from repro.serve.engine import (
    ContinuousEngine, QueueFull, Request, Scheduler, SlotTable,
    needs_exact_prefill)
from repro.serve.sampler import SamplingParams
from repro.train.steps import init_train_state


@pytest.fixture(scope="module")
def tiny_engine_parts():
    cfg = get_config("repro-tiny")
    state = init_train_state(jax.random.PRNGKey(0), cfg, TrainConfig())
    return cfg, state["params"]


def _engine(cfg, params, **kw):
    defaults = dict(max_batch=4, max_seq_len=96, prefill_buckets=(8, 16))
    defaults.update(kw)
    return ContinuousEngine(cfg, params, ServeConfig(**defaults))


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab_size, n).astype(np.int32)


# ----------------------------------------------------------------------------
# slot table: deterministic admission / eviction ordering
# ----------------------------------------------------------------------------

def test_slot_table_lowest_free_first():
    tab = SlotTable(3)
    reqs = [Request(i, np.zeros(1, np.int32), 1) for i in range(4)]
    assert [tab.acquire(reqs[i]) for i in range(3)] == [0, 1, 2]
    tab.release(1)
    assert tab.free_count() == 1
    assert tab.acquire(reqs[3]) == 1            # recycled, lowest-first
    with pytest.raises(IndexError):
        tab.acquire(reqs[0])                    # full
    tab.release(0)
    tab.release(2)
    with pytest.raises(AssertionError):
        tab.release(2)                          # double free


def test_admission_order_and_slot_recycling(tiny_engine_parts):
    """FIFO admission into lowest free slots; evicted slots are reused by
    later arrivals mid-stream."""
    cfg, params = tiny_engine_parts
    eng = _engine(cfg, params, max_batch=2)
    rng = np.random.default_rng(0)
    # short / long / short: r2 queues until a slot frees, then takes the
    # slot of whichever of r0/r1 evicted first (r0: fewer tokens).
    r0 = eng.submit(_prompt(rng, cfg, 6), 2)
    r1 = eng.submit(_prompt(rng, cfg, 6), 8)
    r2 = eng.submit(_prompt(rng, cfg, 6), 2)
    eng.step()
    assert eng.request(r0).slot == 0 and eng.request(r1).slot == 1
    assert eng.request(r2).slot == -1           # still queued
    eng.run()
    assert eng.request(r2).slot == 0            # recycled r0's slot
    assert all(eng.request(r).done for r in (r0, r1, r2))
    assert [len(eng.request(r).output) for r in (r0, r1, r2)] == [2, 8, 2]
    eng.close()


def test_submit_validates_budget_before_length_arithmetic(tiny_engine_parts):
    """An invalid token budget must raise the budget error even when the
    budget also breaks the length check (regression: the arithmetic check
    ran first and masked it — or, for large negatives, passed silently)."""
    cfg, params = tiny_engine_parts
    eng = _engine(cfg, params)
    rng = np.random.default_rng(8)
    p = _prompt(rng, cfg, 8)
    with pytest.raises(ValueError, match="max_new_tokens must be >= 1"):
        eng.submit(p, 0)
    with pytest.raises(ValueError, match="max_new_tokens must be >= 1"):
        eng.submit(_prompt(rng, cfg, 95), 0)     # also fails length check
    with pytest.raises(ValueError, match="max_new_tokens must be >= 1"):
        eng.submit(p, -1000)                     # would pass length check
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        eng.submit(p, 96)
    eng.close()


def test_submit_validates_prompt_shape(tiny_engine_parts):
    cfg, params = tiny_engine_parts
    eng = _engine(cfg, params)
    with pytest.raises(ValueError, match="1-D"):
        eng.submit(np.zeros((2, 4), np.int32), 4)
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit(np.zeros(0, np.int32), 4)
    # exact-fit budget is legal
    rid = eng.submit(np.zeros(92, np.int32), 4)
    eng.run()
    assert len(eng.request(rid).output) == 4
    eng.close()


def test_scheduler_bucket_for_clamps_to_capacity():
    """bucket_for owns the capacity clamp, so every caller gets buckets
    that cannot ring-wrap the prefill (regression: the clamp lived at one
    call site in _admit)."""
    scfg = ServeConfig(max_seq_len=96, prefill_buckets=(16, 128))
    sched = Scheduler(scfg)
    assert sched.bucket_for(8) == 16
    assert sched.bucket_for(70) == 96            # bucket 128 > capacity
    assert sched.bucket_for(96) == 96
    exact = Scheduler(scfg, exact_buckets=True)
    assert exact.bucket_for(70) == 70
    assert exact.bucket_for(0) == 1              # floor


def test_bounded_queue_backpressure(tiny_engine_parts):
    cfg, params = tiny_engine_parts
    eng = _engine(cfg, params, max_batch=2, max_queue=2)
    rng = np.random.default_rng(1)
    for _ in range(2):
        eng.submit(_prompt(rng, cfg, 6), 4)
    with pytest.raises(QueueFull):
        for _ in range(3):
            eng.submit(_prompt(rng, cfg, 6), 4)
    eng.run()
    eng.close()


# ----------------------------------------------------------------------------
# mid-stream join: identical tokens to a solo run
# ----------------------------------------------------------------------------

def test_mid_stream_join_matches_solo(tiny_engine_parts):
    """A request admitted into a busy batch mid-decode must produce exactly
    the tokens it produces decoding alone (row independence of the
    fixed-shape fast path)."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(2)
    p_long = _prompt(rng, cfg, 10)
    p_join = _prompt(rng, cfg, 7)       # pads to bucket 8 -> exercises masks

    busy = _engine(cfg, params)
    r_long = busy.submit(p_long, 24)
    for _ in range(5):                  # long request is mid-decode...
        busy.step()
    r_join = busy.submit(p_join, 8)     # ...when the new one joins
    busy.run()

    solo = _engine(cfg, params)
    s_join = solo.submit(p_join, 8)
    solo.run()
    solo_long = _engine(cfg, params)
    s_long = solo_long.submit(p_long, 24)
    solo_long.run()

    assert busy.request(r_join).output == solo.request(s_join).output
    assert busy.request(r_long).output == solo_long.request(s_long).output
    for e in (busy, solo, solo_long):
        e.close()


def test_prefill_bucket_clamped_to_capacity(tiny_engine_parts):
    """A prompt whose bucket exceeds the decode-state capacity must not
    ring-wrap the prefill (regression: head of the prompt's KV silently
    dropped).  Compare against an engine whose bucket is exact."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(7)
    p = _prompt(rng, cfg, 70)           # buckets to 128 > capacity 96
    clamped = _engine(cfg, params, max_seq_len=96,
                      prefill_buckets=(16, 128))
    r1 = clamped.submit(p, 20)
    clamped.run()
    exact = _engine(cfg, params, max_seq_len=96, prefill_buckets=(70,))
    r2 = exact.submit(p, 20)
    exact.run()
    assert clamped.request(r1).output == exact.request(r2).output
    clamped.close()
    exact.close()


def test_recurrent_arch_uses_exact_prefill_and_joins_exactly():
    cfg = get_config("recurrentgemma-9b").reduced()
    assert needs_exact_prefill(cfg)     # rglru + SWA: pads would corrupt
    state = init_train_state(jax.random.PRNGKey(0), cfg, TrainConfig())
    rng = np.random.default_rng(3)
    pa = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    pb = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    busy = _engine(cfg, state["params"], max_batch=2, max_seq_len=64)
    busy.submit(pa, 10)
    for _ in range(3):
        busy.step()
    rb = busy.submit(pb, 6)
    busy.run()
    solo = _engine(cfg, state["params"], max_batch=2, max_seq_len=64)
    sb = solo.submit(pb, 6)
    solo.run()
    assert busy.request(rb).output == solo.request(sb).output
    busy.close()
    solo.close()


# ----------------------------------------------------------------------------
# per-slot sampling + EOS eviction
# ----------------------------------------------------------------------------

def test_heterogeneous_sampling_and_eos(tiny_engine_parts):
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(4)
    p = _prompt(rng, cfg, 8)
    eng = _engine(cfg, params)
    g = eng.submit(p, 6, SamplingParams(temperature=0.0))
    s = eng.submit(p, 6, SamplingParams(temperature=1.0, top_k=40, top_p=0.9))
    eng.run()
    solo = _engine(cfg, params)
    gs = solo.submit(p, 6)
    solo.run()
    # a stochastic neighbor in the batch must not perturb the greedy row
    assert eng.request(g).output == solo.request(gs).output

    # evict on the request's own EOS id: truncates exactly at first hit
    greedy_out = eng.request(g).output
    eos = int(greedy_out[2])
    e2 = _engine(cfg, params)
    r = e2.submit(p, 6, SamplingParams(temperature=0.0, eos_id=eos))
    e2.run()
    first_hit = greedy_out.index(eos)
    assert e2.request(r).output == greedy_out[:first_hit + 1]
    # freed stochastic slots must drop back to temp 0 so all-greedy batches
    # regain the argmax-only sampling path (regression)
    assert float(np.asarray(eng._mirrors["temp"]).max()) == 0.0
    for e in (eng, solo, e2):
        e.close()


# ----------------------------------------------------------------------------
# sharded result store (G3) + sidecar bookkeeping (G2)
# ----------------------------------------------------------------------------

def test_stats_and_results_race_free_with_engine_loop(tiny_engine_parts):
    """stats()/result() may be called from other threads while the engine
    loop runs: counter snapshots and record appends are lock-guarded, so a
    concurrent reader never tears a read or crashes (regression: unsynced
    reads of _steps/_tokens_out/records mutated by the loop thread)."""
    cfg, params = tiny_engine_parts
    eng = _engine(cfg, params)
    rng = np.random.default_rng(9)
    rids = [eng.submit(_prompt(rng, cfg, 6 + i % 4), 12) for i in range(8)]
    errors = []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                s = eng.stats()
                assert s["tokens_out"] >= 0 and s["steps"] >= 0
                for rid in rids:
                    req = eng._requests.get(rid)
                    if req is not None and req.done:
                        out = eng.result(rid)     # drains, then fetches
                        assert out["tokens"] == req.output
        except Exception as e:  # surfaced after join
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    eng.run()
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors
    final = eng.stats()
    assert final["tokens_out"] >= 8              # all requests produced tokens
    eng.close()


def test_results_land_in_sharded_store(tiny_engine_parts):
    cfg, params = tiny_engine_parts
    eng = _engine(cfg, params)
    rng = np.random.default_rng(5)
    rids = [eng.submit(_prompt(rng, cfg, 6 + i), 3 + i) for i in range(6)]
    eng.run()
    for rid in rids:
        out = eng.result(rid)           # drains the sidecar, then fetches
        assert out["tokens"] == eng.request(rid).output
        assert out["ttft_s"] >= 0.0 and out["e2e_s"] >= out["ttft_s"]
    # results hash-shard across the endpoints (every key routed, none lost)
    stored = sum(len(ep) for ep in eng.store.endpoints)
    assert stored == len(rids)
    assert len(eng.records) == len(rids)
    eng.close()


def test_result_retrieval_across_injected_endpoints(tiny_engine_parts):
    """ShardedStore owner routing is stable: reading through a second store
    over the same endpoints finds every result."""
    cfg, params = tiny_engine_parts
    endpoints = [dict() for _ in range(3)]
    eng = ContinuousEngine(
        cfg, params,
        ServeConfig(max_batch=2, max_seq_len=96, prefill_buckets=(8, 16)),
        result_endpoints=endpoints)
    rng = np.random.default_rng(6)
    rids = [eng.submit(_prompt(rng, cfg, 8), 4) for _ in range(4)]
    eng.run()
    eng.executor.drain()
    reader = ShardedStore(endpoints)
    for rid in rids:
        assert reader.get(f"req/{rid}")["tokens"] == eng.request(rid).output
    eng.close()
