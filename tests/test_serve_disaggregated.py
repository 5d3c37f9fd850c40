"""Disaggregated prefill/decode serving: handoff roundtrip, cost-model
routing, close/error hardening, pool-accounting recovery.  Tier-1."""
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.config import ServeConfig, TrainConfig, get_config
from repro.core.characterize import SidecarProfile
from repro.core.costmodel import Placement
from repro.core.endpoint import BlobEndpoint, EndpointRegistry, ShardedStore
from repro.core.planner import PrefillRoutePlanner
from repro.serve.engine import (
    ContinuousEngine, DisaggregatedEngine, PagedEngine, PrefillWorker,
    Request)
from repro.serve.kvpool import (
    ColdTier, KVBlockPool, chain_keys, pack_handoff, unpack_handoff)
from repro.serve.sampler import SamplingParams
from repro.train.steps import init_train_state


@pytest.fixture(scope="module")
def tiny_engine_parts():
    cfg = get_config("repro-tiny")
    state = init_train_state(jax.random.PRNGKey(0), cfg, TrainConfig())
    return cfg, state["params"]


def _scfg(**kw):
    defaults = dict(max_batch=2, max_seq_len=96, prefill_buckets=(8, 16),
                    page_size=8)
    defaults.update(kw)
    return ServeConfig(**defaults)


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab_size, n).astype(np.int32)


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------------------------------
# handoff roundtrip: export -> shard over peer endpoints -> import
# ----------------------------------------------------------------------------

def test_handoff_roundtrip_page_equivalence(tiny_engine_parts, tmp_path):
    """Prefill-endpoint export, serialization through a ShardedStore over
    directory-backed BlobEndpoints, and decode-endpoint import must carry
    every page bit-exactly."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(0)
    prompt = _prompt(rng, cfg, 17)              # 3 pages, last one partial

    worker = PrefillWorker(cfg, params, _scfg())
    h = worker.prefill_to_handoff(7, prompt, 8, SamplingParams())
    assert h is not None and h.rid == 7 and h.prompt_len == 17
    assert len(h.page_blobs) == 3               # ceil(17/8)
    assert h.chains == chain_keys(prompt, 8)    # full pages only (2 keys)

    peers = EndpointRegistry.local_peers(str(tmp_path), 2).peers()
    store = ShardedStore([BlobEndpoint(p) for p in peers])
    store.put("kv/7", pack_handoff(h))
    assert store.contains("kv/7")
    h2 = unpack_handoff(store.pop("kv/7"))
    assert store.pop("kv/7") is None            # consumed (one-shot payload)
    assert (h2.first_token, h2.prompt_len, h2.chains) == \
        (h.first_token, h.prompt_len, h.chains)
    for b1, b2 in zip(h.page_blobs, h2.page_blobs):
        _leaves_equal(b1, b2)

    dec = DisaggregatedEngine(
        cfg, params, _scfg(disagg_route="remote", prefix_cache=False))
    req = Request(7, prompt, 8)
    tok0 = dec.backend.import_handoff(req, h2)
    assert tok0 == h.first_token
    for i, blob in enumerate(h.page_blobs):     # pool pages == shipped pages
        got = jax.device_get(dec.backend._read_page_prog(
            dec.states, jnp.asarray(req.pages[i], jnp.int32)))
        _leaves_equal(got, blob)
    worker.close()
    dec.close()


def test_handoff_roundtrip_quantized_pages(tiny_engine_parts, tmp_path):
    """With kv_quant=int8 the handoff ships int8 page values + f32 scales:
    the packed blob is >=3x smaller than the f32 handoff for the same
    prompt, and the decode-side import lands every page (values and
    scales) bit-exactly."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(5)
    prompt = _prompt(rng, cfg, 17)               # 3 pages, last one partial

    worker = PrefillWorker(cfg, params, _scfg(kv_quant="int8"))
    h = worker.prefill_to_handoff(3, prompt, 8, SamplingParams())
    assert h is not None and len(h.page_blobs) == 3
    dtypes = {np.asarray(leaf).dtype for leaf in jax.tree.leaves(h.page_blobs[0])}
    assert np.dtype(np.int8) in dtypes           # quantized values on the wire
    assert np.dtype(np.float32) in dtypes        # per-page scales ride along

    f32_worker = PrefillWorker(cfg, params, _scfg())
    hf = f32_worker.prefill_to_handoff(3, prompt, 8, SamplingParams())
    shrink = len(pack_handoff(hf)) / len(pack_handoff(h))
    assert shrink >= 3.0, shrink

    peers = EndpointRegistry.local_peers(str(tmp_path), 2).peers()
    store = ShardedStore([BlobEndpoint(p) for p in peers])
    store.put("kv/3", pack_handoff(h))
    h2 = unpack_handoff(store.pop("kv/3"))
    for b1, b2 in zip(h.page_blobs, h2.page_blobs):
        _leaves_equal(b1, b2)

    dec = DisaggregatedEngine(
        cfg, params,
        _scfg(kv_quant="int8", disagg_route="remote", prefix_cache=False))
    req = Request(3, prompt, 8)
    tok0 = dec.backend.import_handoff(req, h2)
    assert tok0 == h.first_token
    for i, blob in enumerate(h.page_blobs):
        got = jax.device_get(dec.backend._read_page_prog(
            dec.states, jnp.asarray(req.pages[i], jnp.int32)))
        _leaves_equal(got, blob)
    worker.close()
    f32_worker.close()
    dec.close()


def test_disaggregated_int8_matches_single_int8_engine(tiny_engine_parts):
    """Quantization must not reintroduce prefill/decode drift: the
    disaggregated int8 path decodes bit-identically to the single-process
    int8 PagedEngine (both quantize pages the same way at write time)."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(6)
    prompts = [_prompt(rng, cfg, n) for n in (5, 12, 17)]
    single = PagedEngine(cfg, params, _scfg(kv_quant="int8"))
    dis = DisaggregatedEngine(
        cfg, params, _scfg(kv_quant="int8", disagg_route="remote"))
    a = single.generate(prompts, 6)
    b = dis.generate(prompts, 6)
    for i in range(len(prompts)):
        assert a[i].output == b[i].output
    assert dis.stats()["handoffs"]["remote_admits"] == len(prompts)
    single.close()
    dis.close()


def test_bf16_handoff_roundtrip(tiny_engine_parts):
    """bf16 models (every published config) hand off bf16 pages: the
    restricted unpickler must accept their element type and carry the
    pages bit-exactly."""
    import dataclasses
    cfg, params = tiny_engine_parts
    cfg16 = dataclasses.replace(cfg, dtype="bfloat16")
    params16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    rng = np.random.default_rng(3)
    worker = PrefillWorker(cfg16, params16, _scfg())
    h = worker.prefill_to_handoff(5, _prompt(rng, cfg, 13), 4,
                                  SamplingParams())
    assert jax.tree.leaves(h.page_blobs)[0].dtype == jnp.bfloat16
    h2 = unpack_handoff(pack_handoff(h))
    for b1, b2 in zip(h.page_blobs, h2.page_blobs):
        _leaves_equal(b1, b2)
    worker.close()


def test_unpack_handoff_rejects_malformed_blobs(tiny_engine_parts):
    """A truncated stream, a non-pickle payload, and a pickle referencing a
    global outside the handoff allowlist must all surface as the same
    stale/malformed ValueError importers route to the request record —
    never an arbitrary unpickle error or constructor call."""
    import pickle

    cfg, params = tiny_engine_parts
    with pytest.raises(ValueError, match="stale/malformed handoff"):
        unpack_handoff(b"not a pickle at all")
    rng = np.random.default_rng(7)
    worker = PrefillWorker(cfg, params, _scfg())
    h = worker.prefill_to_handoff(1, _prompt(rng, cfg, 9), 4,
                                  SamplingParams())
    blob = pack_handoff(h)
    with pytest.raises(ValueError, match="stale/malformed handoff"):
        unpack_handoff(blob[: len(blob) // 2])   # truncated mid-stream
    # a format-drifted / hostile blob referencing a non-allowlisted global
    evil = pickle.dumps(ServeConfig())
    with pytest.raises(ValueError, match="stale/malformed handoff"):
        unpack_handoff(evil)
    assert unpack_handoff(blob).first_token == h.first_token
    worker.close()


def test_disaggregated_matches_single_engine(tiny_engine_parts):
    """Remote-prefilled requests must decode bit-identically to the
    single-engine PagedEngine, including across shared prefixes."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(1)
    prefix = _prompt(rng, cfg, 16)
    prompts = [np.concatenate([prefix, _prompt(rng, cfg, k)])
               for k in (5, 9, 3)] + [_prompt(rng, cfg, 11)]
    single = PagedEngine(cfg, params, _scfg())
    dis = DisaggregatedEngine(cfg, params, _scfg(disagg_route="remote"))
    a = single.generate(prompts, 6)
    b = dis.generate(prompts, 6)
    for i in range(len(prompts)):
        assert a[i].output == b[i].output
    st = dis.stats()
    assert st["handoffs"]["remote_admits"] == len(prompts)
    assert st["handoffs"]["bytes"] > 0
    # both endpoints keep their own prefix caches over the shared prefix
    assert st["prefill_endpoint"]["pool"]["prefix_hit_pages"] > 0
    assert st["prefix_hit_rate"] > 0.0          # decode side deduped imports
    single.close()
    dis.close()


def test_disaggregated_auto_routing_end_to_end(tiny_engine_parts):
    """With a slow modeled accelerator the cost model sends long prompts
    remote; outputs stay exact and the plan table explains each call."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(2)
    profile = SidecarProfile(sidecar_matmul_flops=1e10, sidecar_mem_bw=1e10,
                             link_lat=20e-6, link_bw=16e9,
                             accel_flops=1e9, accel_mem_bw=1e9)
    dis = DisaggregatedEngine(
        cfg, params, _scfg(disagg_route="auto"), profile=profile)
    prompts = [_prompt(rng, cfg, n) for n in (40, 48)]
    out = dis.generate(prompts, 5)
    assert dis.stats()["handoffs"]["remote_admits"] > 0
    ref = PagedEngine(cfg, params, _scfg())
    expect = ref.generate(prompts, 5)
    for i in range(len(prompts)):
        assert out[i].output == expect[i].output
    table = dis.route_plan().to_table()
    assert "prefill/req" in table and "remote prefill" in table
    dis.close()
    ref.close()


# ----------------------------------------------------------------------------
# planner: remote-vs-local routing decisions
# ----------------------------------------------------------------------------

def test_prefill_route_prompt_length_and_pressure():
    """Short prompts lose to the link latency floor (local); prompt length
    or decode batch pressure flips the decision remote."""
    profile = SidecarProfile(sidecar_matmul_flops=1e10, sidecar_mem_bw=1e10,
                             link_lat=20e-6, link_bw=16e9,
                             accel_flops=1e12, accel_mem_bw=1e12)
    pl = PrefillRoutePlanner(flops_per_token=2e6, profile=profile)
    # dev time/token = 2e-6s, link ~ 4.6e-5s -> crossover ~ 23 tokens
    short = pl.route(0, 8, handoff_bytes=1e5, active_slots=0, max_slots=4)
    assert short.placement == Placement.DEVICE
    long = pl.route(1, 512, handoff_bytes=1e5, active_slots=0, max_slots=4)
    assert long.placement == Placement.SIDECAR_ASYNC
    # same short-ish prompt, but a full decode batch amplifies the stall
    idle = pl.route(2, 16, handoff_bytes=1e5, active_slots=0, max_slots=4)
    busy = pl.route(3, 16, handoff_bytes=1e5, active_slots=4, max_slots=4)
    assert idle.placement == Placement.DEVICE
    assert busy.placement == Placement.SIDECAR_ASYNC
    assert pl.remote_count == 2 and pl.local_count == 2
    table = pl.plan().to_table()
    for rid in range(4):
        assert f"prefill/req{rid}" in table
    assert "handoff link" in table


def test_route_planner_table_is_bounded():
    profile = SidecarProfile(1e10, 1e10, 20e-6, 16e9)
    pl = PrefillRoutePlanner(flops_per_token=2e6, profile=profile,
                             keep_last=8)
    for rid in range(32):
        pl.route(rid, 16, 1e5, 0, 4)
    assert len(pl.plan().decisions) == 8        # long-lived server: bounded


# ----------------------------------------------------------------------------
# close / decode-loop-death hardening
# ----------------------------------------------------------------------------

def test_close_with_pending_requests_does_not_hang(tiny_engine_parts):
    """close() must terminate queued and mid-decode requests with error
    records so result(wait=True) returns instead of waiting forever."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(3)
    eng = ContinuousEngine(cfg, params, _scfg())
    r1 = eng.submit(_prompt(rng, cfg, 9), 64)
    eng.step()                                   # r1 admitted, mid-decode
    r2 = eng.submit(_prompt(rng, cfg, 5), 8)     # r2 still queued
    r3 = eng.submit(_prompt(rng, cfg, 7), 8)

    got = {}

    def waiter():
        while True:
            try:
                got["r1"] = eng.result(r1, wait=True)
                return
            except (RuntimeError, KeyError):
                pass

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    eng.close()
    t.join(timeout=30)
    assert not t.is_alive(), "result() waiter still hung after close()"
    assert "error" in got["r1"] and got["r1"]["rid"] == r1
    assert got["r1"]["tokens"]                   # partial output preserved
    for rid in (r2, r3):
        rec = eng.result(rid)
        assert "engine closed" in rec["error"]
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(_prompt(rng, cfg, 4), 4)
    assert eng.step() is False                   # closed engine is inert


def test_decode_loop_death_surfaces_to_result(tiny_engine_parts):
    """An exception out of the decode loop terminates in-flight requests
    with an error record naming the failure instead of leaving them
    'still decoding' forever."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(4)
    eng = ContinuousEngine(cfg, params, _scfg())
    rid = eng.submit(_prompt(rng, cfg, 9), 16)

    def boom():
        raise RuntimeError("injected device fault")
    eng._decode_device = boom
    with pytest.raises(RuntimeError, match="injected device fault"):
        eng.run()
    rec = eng.result(rid)
    assert "decode loop died" in rec["error"]
    assert "injected device fault" in rec["error"]
    eng.close()


# ----------------------------------------------------------------------------
# pool accounting: degrade, never kill the engine thread
# ----------------------------------------------------------------------------

def test_alloc_rolls_back_on_accounting_drift(monkeypatch):
    pool = KVBlockPool(6, page_size=4)           # 5 usable pages
    a = pool.alloc(2)
    pool.register(b"c", a[0])
    pool.unref(a[0])                             # cached: available() counts it
    # drift: available() promises a reclaimable page but eviction (the
    # locked internal alloc actually calls) yields nothing
    monkeypatch.setattr(pool, "_evict_locked", lambda cb=None: None)
    free_before = list(pool._free)
    assert pool.alloc(4) is None                 # needs the broken eviction
    assert pool._free == free_before             # partial take rolled back
    assert pool.stats()["alloc_failures"] == 1
    assert pool.alloc(3) is not None             # free-stack path still fine


def test_unref_underflow_is_recoverable():
    pool = KVBlockPool(4, page_size=4)
    a = pool.alloc(1)
    pool.unref(a[0])
    pool.unref(a[0])                             # upstream double-unref
    assert pool.stats()["unref_underflows"] == 1
    assert pool.free_count() == 3                # accounting undisturbed


def test_cold_tier_zero_capacity_rejects_inserts():
    tier = ColdTier(capacity_pages=0)
    tier.put(b"k", "blob")
    assert len(tier) == 0 and tier.take(b"k") is None
    assert tier.dropped == 0                     # nothing 'lost an LRU race'
    assert tier.rejected == 1


def test_cold_tier_overflow_never_evicts_new_entry():
    tier = ColdTier(capacity_pages=1)
    tier.put(b"k1", "a")
    tier.put(b"k2", "b")                         # overflow drops k1, not k2
    assert tier.dropped == 1 and tier.take(b"k1") is None
    assert tier.take(b"k2") == "b"
