"""Checkpoint roundtrip / replication / elastic restore; executor; health."""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is a dev-only extra: guard the import so a bare environment
# still collects (and runs) everything except the property-based test.
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:          # pragma: no cover - exercised in CI
    HAVE_HYPOTHESIS = False

from repro.ckpt import checkpoint as ck
from repro.ckpt.manager import CheckpointManager
from repro.core.endpoint import EndpointRegistry, HostMemoryPool
from repro.core.executor import BackgroundExecutor
from repro.runtime.health import FailureInjector, StepTimeMonitor


# ----------------------------------------------------------------------------
# checkpoint
# ----------------------------------------------------------------------------

def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32)),
        "nested": {"b": jnp.asarray(rng.integers(0, 100, (4,)).astype(np.int32)),
                   "c": jnp.asarray(rng.standard_normal((3, 5, 2))
                                    .astype(np.float32))},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_roundtrip_bitwise(tmp_path):
    tree = _tree()
    ck.save_checkpoint(str(tmp_path), 7, tree)
    out = ck.restore_checkpoint(str(tmp_path), 7, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_roundtrip_property(tmp_path_factory, seed):
        tmp = tmp_path_factory.mktemp(f"ck{seed % 100}")
        tree = _tree(seed)
        ck.save_checkpoint(str(tmp), 1, tree)
        out = ck.restore_checkpoint(str(tmp), 1, tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_uncommitted_checkpoint_invisible(tmp_path):
    tree = _tree()
    path = ck.save_checkpoint(str(tmp_path), 5, tree)
    os.remove(os.path.join(path, ck.MANIFEST))   # simulate crash mid-commit
    assert ck.list_steps(str(tmp_path)) == []


def test_manager_async_replication_and_gc(tmp_path):
    ex = BackgroundExecutor(num_threads=2, max_inflight=8)
    reg = EndpointRegistry.local_peers(str(tmp_path / "peers"), 3)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2, executor=ex,
                            replicas=reg)
    tree = _tree()
    for s in (1, 2, 3):
        mgr.save(s, tree)
    assert mgr.wait()
    assert ck.list_steps(str(tmp_path / "ckpt")) == [2, 3]   # GC keep=2
    for peer in reg.peers():
        assert ck.list_steps(peer.root) != []                # replicated
    # disaster: local loss, restore from peer
    restored = mgr.restore_from_peer("peer0", tree)
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    ex.shutdown()


def test_elastic_restore_different_sharding(tmp_path):
    """Save from one 'mesh', restore onto another (single-device here:
    sharding degenerates, but the global-index path is exercised)."""
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    hs = jax.tree.map(ck.HostSharded.from_jax, tree)
    ck.save_checkpoint(str(tmp_path), 1, hs)
    out = ck.restore_checkpoint(str(tmp_path), 1, tree)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))


# ----------------------------------------------------------------------------
# executor (G2): bounded, fault-isolated
# ----------------------------------------------------------------------------

def test_executor_failure_isolation():
    ex = BackgroundExecutor(num_threads=1, max_inflight=4, max_retries=1)

    def boom():
        raise ValueError("injected")

    def ok():
        return 42

    t1 = ex.submit("boom", boom)
    t2 = ex.submit("ok", ok)
    t1.done.wait(5)
    t2.done.wait(5)
    assert t1.record.error is not None
    assert t2.result == 42                      # failure didn't poison queue
    stats = ex.stats()
    assert stats["failed"] == 1 and stats["completed"] == 1
    ex.shutdown(drain=False)


def test_executor_stats_are_totals_of_every_task_record():
    """stats() keeps its keys and meanings as running totals: completed and
    failed (dropped tasks included) counts, mean wait and run of the
    completed ones, the first 8 errors in the order they were recorded."""
    ex = BackgroundExecutor(num_threads=1, max_inflight=1,
                            backpressure="reject", max_retries=0)
    gate, running = threading.Event(), threading.Event()

    def blocker():
        running.set()
        gate.wait(5.0)

    def boom():
        raise ValueError("boom")

    tasks = [ex.submit("blocker", blocker)]
    assert running.wait(5.0)
    tasks.append(ex.submit("boom", boom))        # takes the one queue place
    tasks += [ex.submit(f"r{i}", lambda: None) for i in range(9)]  # rejected
    gate.set()
    assert ex.drain(timeout=5.0)
    for i in range(3):                           # one at a time: accepted
        tasks.append(ex.submit(f"ok{i}", lambda i=i: i))
        assert tasks[-1].done.wait(5.0)
    assert ex.drain(timeout=5.0)                 # totals counted
    recs = [t.record for t in tasks]
    ok = [r for r in recs if r.error is None]
    stats = ex.stats()
    assert {k: stats[k] for k in ("completed", "failed", "dropped",
                                  "errors")} == {
        "completed": 4, "failed": 10, "dropped": 9,
        "errors": ["rejected: queue full"] * 8}
    assert stats["mean_wait_s"] == pytest.approx(
        sum(r.wait_s for r in ok) / len(ok))
    assert stats["mean_run_s"] == pytest.approx(
        sum(r.run_s for r in ok) / len(ok))
    assert tasks[1].record.error == "ValueError: boom"
    assert stats["submitted"] == len(tasks) and stats["blocked_submits"] == 0
    ex.shutdown(drain=False)


def test_executor_backpressure_drop_oldest():
    ex = BackgroundExecutor(num_threads=1, max_inflight=2,
                            backpressure="drop_oldest")
    import threading
    gate = threading.Event()
    ex.submit("blocker", gate.wait, )
    tasks = [ex.submit(f"t{i}", lambda i=i: i) for i in range(6)]
    gate.set()
    ex.drain(10)
    stats = ex.stats()
    assert stats["dropped"] > 0                 # bounded queue enforced
    ex.shutdown(drain=False)


def test_executor_stages_device_arrays():
    ex = BackgroundExecutor(num_threads=1, max_inflight=4)
    arr = jnp.arange(10)
    out = {}

    def consume(host):
        out["type"] = type(host).__name__
        out["sum"] = int(host.sum())

    t = ex.submit("stage", consume, arr)
    t.done.wait(5)
    assert out["sum"] == 45                     # staged d2h on the sidecar
    ex.shutdown(drain=False)


def test_executor_drain_waits_for_inflight():
    """drain() must block on accepted-but-unfinished work and honor its
    timeout (regression: the old implementation busy-waited on the
    undocumented queue.Queue.unfinished_tasks attribute)."""
    ex = BackgroundExecutor(num_threads=1, max_inflight=4)
    gate = threading.Event()
    t = ex.submit("slow", gate.wait)
    assert ex.drain(timeout=0.2) is False       # in flight: timeout, no hang
    gate.set()
    assert ex.drain(timeout=5.0) is True        # finished: drains promptly
    assert t.record.finished_at > 0.0
    assert ex.stats()["completed"] == 1         # drain implies record visible
    ex.shutdown(drain=False)


def test_executor_drain_counts_dropped_tasks():
    """Dropped/rejected tasks must not wedge drain()'s in-flight count."""
    ex = BackgroundExecutor(num_threads=1, max_inflight=1,
                            backpressure="reject")
    gate = threading.Event()
    ex.submit("blocker", gate.wait)
    time.sleep(0.05)                            # let the worker pick it up
    for i in range(3):
        ex.submit(f"r{i}", lambda: None)        # queue full -> some rejected
    gate.set()
    assert ex.drain(timeout=5.0) is True
    ex.shutdown(drain=False)


def test_executor_close_is_idempotent_and_rejects_late_submits():
    """shutdown() twice is a no-op; drain() after close returns promptly;
    a submit() after close fails the task out instead of queueing work no
    worker will ever run (regression: callers waiting on task.done hung)."""
    ex = BackgroundExecutor(num_threads=1, max_inflight=4)
    ok = ex.submit("noop", lambda: 1)
    assert ex.drain(timeout=5.0) is True
    ex.shutdown()
    ex.shutdown()                               # second close: no-op, no hang
    assert ex.drain(timeout=1.0) is True        # nothing left in flight
    late = ex.submit("late", lambda: 2)
    assert late.done.is_set()
    assert "rejected" in late.record.error
    assert ok.record.error is None
    assert ex.stats()["dropped"] >= 1


def test_executor_shutdown_without_drain_cancels_queued_tasks():
    """shutdown(drain=False) must fail out queued-but-unstarted tasks so a
    later drain() (or task.done.wait()) cannot hang on orphaned work."""
    ex = BackgroundExecutor(num_threads=1, max_inflight=4)
    gate = threading.Event()
    running = threading.Event()

    def blocker():
        running.set()
        gate.wait(5.0)
        return "done"

    first = ex.submit("blocker", blocker)
    assert running.wait(5.0)
    queued = [ex.submit(f"q{i}", lambda: None) for i in range(2)]
    releaser = threading.Thread(target=lambda: (time.sleep(0.2), gate.set()))
    releaser.start()
    ex.shutdown(drain=False)
    releaser.join()
    for task in queued:
        assert task.done.wait(5.0)
        assert "cancelled" in task.record.error
    assert first.done.wait(5.0)
    assert ex.drain(timeout=5.0) is True


# ----------------------------------------------------------------------------
# host memory pool (G3)
# ----------------------------------------------------------------------------

def test_host_pool_capacity_and_prefetch():
    pool = HostMemoryPool(capacity_bytes=1000)
    pool.put("x", jnp.zeros(100, jnp.float32))          # 400B
    with pytest.raises(MemoryError):
        pool.put("y", jnp.zeros(200, jnp.float32))      # 800B > remaining
    back = pool.to_device("x")
    assert isinstance(back, jax.Array)
    pool.delete("x")
    assert pool.used == 0


# ----------------------------------------------------------------------------
# straggler monitor
# ----------------------------------------------------------------------------

def test_straggler_detection():
    mon = StepTimeMonitor(window=30, z_threshold=4.0, min_samples=10)
    for _ in range(20):
        mon.record(0.100)
    rep = mon.record(0.500)                     # 5x median
    assert rep is not None and "straggler" in rep.advisory
    assert mon.record(0.101) is None            # normal step: quiet


def test_failure_injector():
    inj = FailureInjector(fail_steps=(3,))
    inj.tick(); inj.tick()
    with pytest.raises(RuntimeError):
        inj.tick()
