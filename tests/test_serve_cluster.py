"""Multi-replica serve cluster: exactness, prefix-affinity routing, QoS
(preemption, rate limits), replica-death requeue, factory/compat.  Tier-1."""
import numpy as np
import pytest

import jax

from repro.config import EngineMode, ServeConfig, TrainConfig, get_config
from repro.core.characterize import SidecarProfile
from repro.core.costmodel import CostModel, Placement, ReplicaSignals
from repro.core.endpoint import ShardedStore
from repro.core.planner import ReplicaRoutePlanner
from repro.serve import (
    ContinuousEngine, DisaggregatedEngine, FixedBatchEngine, PagedEngine,
    QueueFull, ServeCluster, TenantSpec, TokenBucket, make_engine,
    resolve_engine_mode)
from repro.runtime.locks import order_graph
from repro.train.steps import init_train_state


@pytest.fixture(autouse=True)
def lock_sanitizer(monkeypatch):
    """Run every cluster test with the lock-order sanitizer on, and assert
    the accumulated acquisition graph stayed acyclic afterwards."""
    monkeypatch.setenv("REPRO_LOCK_SANITIZER", "1")
    yield
    order_graph().check()


@pytest.fixture(scope="module")
def tiny_engine_parts():
    cfg = get_config("repro-tiny")
    state = init_train_state(jax.random.PRNGKey(0), cfg, TrainConfig())
    return cfg, state["params"]


def _scfg(**kw):
    defaults = dict(max_batch=2, max_seq_len=96, prefill_buckets=(8, 16),
                    page_size=8, engine_mode="cluster", num_replicas=2,
                    cluster_prefill=False)
    defaults.update(kw)
    return ServeConfig(**defaults)


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab_size, n).astype(np.int32)


def _profile():
    return SidecarProfile(sidecar_matmul_flops=1e10, sidecar_mem_bw=1e10,
                          link_lat=20e-6, link_bw=16e9,
                          accel_flops=1e12, accel_mem_bw=1e12)


# ----------------------------------------------------------------------------
# end-to-end: cluster decode is exact, across replicas and the shared prefill
# ----------------------------------------------------------------------------

def test_cluster_matches_single_engine(tiny_engine_parts):
    """N replicas behind the router (plus the shared prefill endpoint) must
    reproduce a single PagedEngine's tokens bit-identically."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(0)
    prefix = _prompt(rng, cfg, 16)
    prompts = [np.concatenate([prefix, _prompt(rng, cfg, k)])
               for k in (5, 9, 3)] + [_prompt(rng, cfg, 11)]
    ref = PagedEngine(cfg, params, _scfg(engine_mode="paged"))
    clu = ServeCluster(cfg, params, _scfg(cluster_prefill=True),
                       profile=_profile())
    a = ref.generate(prompts, 6)
    b = clu.generate(prompts, 6)
    for i in range(len(prompts)):
        assert a[i].output == b[i]
    st = clu.stats()
    assert st["completed"] == len(prompts)
    assert sum(st["router"]["picks"].values()) >= len(prompts)
    assert st["prefill_endpoint"] is not None
    ref.close()
    clu.close()


PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax, numpy as np
from repro.config import ServeConfig, get_config
from repro.models.transformer import init_params
from repro.serve import PagedEngine, ServeCluster
cfg = get_config("repro-tiny")
params = init_params(jax.random.PRNGKey(0), cfg)
scfg = ServeConfig(max_batch=2, max_seq_len=96, prefill_buckets=(8, 16),
                   page_size=8, engine_mode="cluster", num_replicas=4)
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
           for n in (5, 9, 12, 7, 16, 3, 11, 8)]
ref = PagedEngine(cfg, params, dataclasses.replace(scfg, engine_mode="paged"))
clu = ServeCluster(cfg, params, scfg)
a, b = ref.generate(prompts, 6), clu.generate(prompts, 6)
assert all(a[i].output == b[i] for i in range(len(prompts)))
devs = jax.devices()
for i, rep in enumerate(clu.replicas):
    for tree in (rep.params, rep.states):
        assert {l.devices().pop() for l in jax.tree.leaves(tree)} == \
            {devs[i]}, (i, tree is rep.params)
assert all(clu.alive) and clu.stats()["qos"]["replica_deaths"] == 0
print("PLACED")
"""


def test_cluster_places_each_replica_on_its_own_device():
    """With four devices, replica i keeps its weights and decode state on
    device i, prompts prefilled on device 0 cross as host bytes, and the
    tokens still equal one PagedEngine's.  Subprocess: the virtual device
    count must be set before JAX starts."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PLACED" in out.stdout


def test_prefix_affinity_routes_to_page_owner(tiny_engine_parts):
    """A prompt whose prefix pages live on replica 1 must route there, even
    though the tie-break would otherwise pick replica 0."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(1)
    clu = ServeCluster(cfg, params, _scfg(), profile=_profile())
    prefix = _prompt(rng, cfg, 16)              # 2 full pages (page_size=8)
    # Seed replica 1's prefix index directly (bypassing the router).
    clu.replicas[1].generate([prefix], 4)

    follow = np.concatenate([prefix, _prompt(rng, cfg, 5)])
    idx, decision, sig = clu.router.pick(99, follow, 4,
                                         clu.replicas, clu.alive)
    assert sig[0].hit_pages == 0 and sig[1].hit_pages >= 2
    assert idx == 1
    assert "hit 2p" in decision.rationale
    # And through the full submit path:
    crid = clu.submit(follow, 4)
    clu.run()
    assert clu.result(crid)["replica"] == 1
    clu.close()


def test_replica_death_requeues_without_output_loss(tiny_engine_parts):
    """A replica dying mid-decode strands its requests; they must resume on
    the survivor as continuations and finish with the exact tokens the
    healthy run produces."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(2)
    prompts = [_prompt(rng, cfg, n) for n in (9, 13, 7, 11)]
    budget = 12

    ref = PagedEngine(cfg, params, _scfg(engine_mode="paged"))
    expect = ref.generate(prompts, budget)
    ref.close()

    clu = ServeCluster(cfg, params, _scfg(), profile=_profile())
    crids = [clu.submit(p, budget) for p in prompts]
    for _ in range(4):          # both replicas mid-decode, partial outputs
        clu.step()
    assert any(len(cr.output) > 0 or cr.rid >= 0
               for cr in clu._inflight.values())

    def boom(*a, **kw):
        raise RuntimeError("injected replica fault")
    clu.replicas[0]._decode_device = boom
    clu.run()                   # death absorbed, survivors finish the trace

    st = clu.stats()
    assert st["qos"]["replica_deaths"] == 1
    assert st["qos"]["death_requeues"] >= 1
    assert clu.alive == [False, True]
    for i, crid in enumerate(crids):
        rec = clu.result(crid)
        assert "error" not in rec, rec
        assert rec["tokens"] == expect[i].output
    assert any(clu.result(c)["requeues"] >= 1 for c in crids)
    # Dead replica's pending handoff blobs were dropped.
    assert not any(k.startswith("kv/r0/")
                   for ep in clu.handoff_store.endpoints for k in ep.keys())
    clu.close()


# ----------------------------------------------------------------------------
# QoS: preemption and rate limits
# ----------------------------------------------------------------------------

def test_paid_preempts_best_effort_and_victim_completes(tiny_engine_parts):
    """A paid request that finds no room evicts the youngest best-effort
    request; the victim is re-enqueued as a continuation and still finishes
    with its full budget."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(3)
    tenants = [TenantSpec("paid", priority=2),
               TenantSpec("free", priority=0)]
    clu = ServeCluster(cfg, params, _scfg(num_replicas=1), tenants=tenants,
                       profile=_profile())
    free_budget = 24
    free = [clu.submit(_prompt(rng, cfg, 9), free_budget, tenant="free")
            for _ in range(2)]
    clu.step()                  # both best-effort requests occupy the slots
    assert all(c in clu._inflight for c in free)

    paid = clu.submit(_prompt(rng, cfg, 9), 4, tenant="paid")
    clu.step()                  # paid admits by preempting the youngest
    assert paid in clu._inflight
    clu.run()

    st = clu.stats()
    assert st["qos"]["preemptions"] >= 1
    assert clu.result(paid)["tenant"] == "paid"
    assert len(clu.result(paid)["tokens"]) == 4
    for c in free:              # re-enqueued, not failed: full budget out
        rec = clu.result(c)
        assert "error" not in rec
        assert len(rec["tokens"]) == free_budget
    assert max(clu.result(c)["preemptions"] for c in free) >= 1
    clu.close()


def test_rate_limited_tenant_gets_queuefull_not_a_hang(tiny_engine_parts):
    """Submissions over a tenant's token bucket raise QueueFull immediately;
    the bucket refills with (injected) time."""
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(4)
    now = [1000.0]
    tenants = [TenantSpec("free", priority=0, rate_limit=1.0, burst=2)]
    clu = ServeCluster(cfg, params, _scfg(num_replicas=1), tenants=tenants,
                       clock=lambda: now[0])
    p = _prompt(rng, cfg, 8)
    clu.submit(p, 2, tenant="free")
    clu.submit(p, 2, tenant="free")             # burst of 2 exhausted
    with pytest.raises(QueueFull, match="rate limit"):
        clu.submit(p, 2, tenant="free")
    assert clu.stats()["qos"]["rate_limited"] == 1
    now[0] += 1.0                               # 1s at 1 req/s -> one token
    clu.submit(p, 2, tenant="free")
    clu.run()
    assert clu.stats()["completed"] == 3
    clu.close()


def test_cluster_queue_bound_backpressure(tiny_engine_parts):
    cfg, params = tiny_engine_parts
    rng = np.random.default_rng(5)
    clu = ServeCluster(cfg, params, _scfg(num_replicas=1, max_queue=2))
    for _ in range(2):
        clu.submit(_prompt(rng, cfg, 8), 2)
    with pytest.raises(QueueFull, match="cluster queue full"):
        clu.submit(_prompt(rng, cfg, 8), 2)
    clu.run()
    clu.close()


def test_token_bucket_refill():
    now = [0.0]
    b = TokenBucket(rate=2.0, burst=1, clock=lambda: now[0])
    assert b.try_take() and not b.try_take()
    now[0] += 0.5                               # 2/s * 0.5s = 1 token
    assert b.try_take() and not b.try_take()


# ----------------------------------------------------------------------------
# router / cost model units (no engines)
# ----------------------------------------------------------------------------

def _sig(name, free_slots=2, queue=0, free_pages=64, hits=0, alive=True):
    return ReplicaSignals(name, free_slots, queue, 2, free_pages,
                          hit_pages=hits, alive=alive)


def test_decide_replica_prefers_prefix_hits():
    cm = CostModel(_profile())
    idx, d = cm.decide_replica(32, 5, 2e6, 8,
                               [_sig("r0"), _sig("r1", hits=3)])
    assert idx == 1
    assert d.placement == Placement.REPLICA
    assert "r1" in d.rationale and "beats" in d.rationale


def test_decide_replica_avoids_slot_pressure():
    cm = CostModel(_profile())
    # r0 holds the prefix but has no slot headroom behind a deep queue;
    # the idle replica wins despite paying the full prefill.
    idx, _ = cm.decide_replica(32, 5, 2e6, 8,
                               [_sig("r0", free_slots=0, queue=3, hits=3),
                                _sig("r1")])
    assert idx == 1


def test_decide_replica_all_dead_rejects():
    cm = CostModel(_profile())
    idx, d = cm.decide_replica(32, 5, 2e6, 8,
                               [_sig("r0", alive=False),
                                _sig("r1", alive=False)])
    assert idx == -1
    assert d.placement == Placement.REJECTED


def test_replica_route_planner_log_is_bounded():
    pl = ReplicaRoutePlanner(flops_per_token=2e6, page_size=8,
                             profile=_profile(), keep_last=4)
    for rid in range(16):
        pl.route(rid, 16, 3, [_sig("r0"), _sig("r1")])
    assert len(pl.plan().decisions) == 4
    assert sum(pl.picks.values()) == 16
    assert "route/req15" in pl.plan().to_table()


# ----------------------------------------------------------------------------
# factory / engine-mode resolution / compat shim
# ----------------------------------------------------------------------------

def test_resolve_engine_mode_default_and_invalid():
    assert resolve_engine_mode(ServeConfig()) == EngineMode.CONTINUOUS
    for mode in EngineMode:
        assert resolve_engine_mode(
            ServeConfig(engine_mode=mode.value)) == mode
    with pytest.raises(ValueError):
        resolve_engine_mode(ServeConfig(engine_mode="warp-drive"))
    # The PR-6-deprecated boolean selector is gone, not just ignored.
    with pytest.raises(TypeError):
        ServeConfig(disaggregate=True)


def test_make_engine_dispatch(tiny_engine_parts):
    cfg, params = tiny_engine_parts
    modes = [("fixed", FixedBatchEngine), ("continuous", ContinuousEngine),
             ("paged", PagedEngine), ("disaggregated", DisaggregatedEngine),
             ("cluster", ServeCluster)]
    for mode, cls in modes:
        eng = make_engine(cfg, params, _scfg(engine_mode=mode,
                                             num_replicas=1))
        assert type(eng) is cls
        getattr(eng, "close", lambda: None)()


def test_engine_module_compat_shim():
    """The pre-split import surface must keep resolving to the same
    classes as the package."""
    from repro.serve import engine as shim
    from repro.serve import engines, scheduler
    assert shim.ContinuousEngine is engines.ContinuousEngine
    assert shim.ServeEngine is engines.ContinuousEngine
    assert shim.PagedEngine is engines.PagedEngine
    assert shim.Request is scheduler.Request
    assert shim.QueueFull is scheduler.QueueFull


def test_sharded_store_drop_prefix():
    store = ShardedStore([dict(), dict()])
    for k in ("kv/r0/1", "kv/r0/2", "kv/r1/1"):
        store.put(k, b"x")
    assert store.drop_prefix("kv/r0/") == 2
    assert not store.contains("kv/r0/1")
    assert store.contains("kv/r1/1")
