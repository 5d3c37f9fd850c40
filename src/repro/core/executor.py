"""Background sidecar executor — paper G2 as infrastructure.

Runs latency-insensitive work (checkpoint serialization, peer replication,
metrics, log processing) on host threads so the device step loop never
blocks.  Properties the paper's doctrine requires:

  * **Non-blocking submit** with device->host staging inside the worker
    (``jax.device_get`` happens on the sidecar thread, after an async
    host-copy enqueue on the main thread when possible).
  * **Bounded queue + backpressure policy** — an overloaded sidecar must not
    grow unbounded (the cost model's G2-overload case); policies: "block"
    (checkpoints — correctness), "drop_oldest" (metrics — lossy ok).
    Under "block" a full queue does stall the caller: each such submit is
    counted in ``stats()`` and spanned as ``sidecar.blocked`` in a
    profiler trace.
  * **Failure isolation** — a sidecar task failure (e.g. a flaky replication
    peer) is recorded and retried; it never propagates into the step loop.
    This is the fault-tolerance contract: background-plane failures are
    soft-degradations, not training failures.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

from repro.runtime.locks import make_condition, make_lock


@dataclasses.dataclass
class TaskRecord:
    name: str
    submitted_at: float
    started_at: float = 0.0
    finished_at: float = 0.0
    error: Optional[str] = None
    retries: int = 0

    @property
    def wait_s(self) -> float:
        return (self.started_at or time.time()) - self.submitted_at

    @property
    def run_s(self) -> float:
        return max(0.0, self.finished_at - self.started_at)


class _Task:
    __slots__ = ("name", "fn", "args", "record", "done", "result", "max_retries")

    def __init__(self, name, fn, args, max_retries):
        self.name = name
        self.fn = fn
        self.args = args
        self.record = TaskRecord(name, time.time())
        self.done = threading.Event()
        self.result = None
        self.max_retries = max_retries


class BackgroundExecutor:
    """Thread-pool sidecar with bounded queue and failure isolation."""

    def __init__(self, num_threads: int = 2, max_inflight: int = 4,
                 backpressure: str = "block", max_retries: int = 2):
        assert backpressure in ("block", "drop_oldest", "reject")
        self.backpressure = backpressure
        self.max_retries = max_retries
        self._q: "queue.Queue[_Task]" = queue.Queue(maxsize=max_inflight)
        # _lock guards the outcome and back-pressure totals; _cv guards
        # in-flight counts.  They are never nested — keep it that way, or the
        # lock-order sanitizer will record an edge between them.
        self._lock = make_lock("BackgroundExecutor._lock")
        self._stop = threading.Event()
        # Running totals, not a record per task: a serving process submits a
        # task per spilled page and per finished request, for its lifetime.
        self._completed = 0                     # guarded-by: _lock
        self._failed = 0                        # guarded-by: _lock
        self._dropped = 0                       # guarded-by: _lock
        self._wait_s = 0.0                      # guarded-by: _lock
        self._run_s = 0.0                       # guarded-by: _lock
        self._errors: List[str] = []            # guarded-by: _lock
        # Back-pressure: submits that found the queue full under "block",
        # and the perf_counter seconds their callers spent blocked.
        self._blocked_submits = 0               # guarded-by: _lock
        self._blocked_s = 0.0                   # guarded-by: _lock
        # In-flight accounting for drain(): counts accepted-but-unfinished
        # tasks under a condition variable (queue.Queue.unfinished_tasks is
        # undocumented, and join() has no timeout).
        self._cv = make_condition("BackgroundExecutor._cv")
        self._inflight = 0                      # guarded-by: _cv
        self._submitted = 0                     # guarded-by: _cv
        self._threads = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"sidecar-{i}")
            for i in range(num_threads)]
        for t in self._threads:
            t.start()

    # -- submission -----------------------------------------------------------
    def submit(self, name: str, fn: Callable, *arrays: Any) -> _Task:
        """Non-blocking (subject to backpressure policy).  ``arrays`` may be
        jax Arrays — host staging happens on the worker thread."""
        for a in arrays:
            if isinstance(a, jax.Array):
                try:
                    a.copy_to_host_async()
                except Exception:
                    pass
        task = _Task(name, fn, arrays, self.max_retries)
        with self._cv:
            self._submitted += 1
            rejected = self._stop.is_set()
            if not rejected:
                self._inflight += 1   # count before enqueue: no drain races
        if rejected:
            # After shutdown no worker will ever run this; fail it out
            # immediately so callers waiting on task.done cannot hang.
            task.record.error = "rejected: executor shut down"
            task.record.finished_at = time.time()
            task.done.set()
            with self._lock:
                self._dropped += 1
                self._account(task.record)
            return task
        while True:
            try:
                self._q.put_nowait(task)
                return task
            except queue.Full:
                if self.backpressure == "block":
                    t0 = time.perf_counter()
                    with TraceAnnotation("sidecar.blocked"):
                        self._q.put(task)
                    blocked = time.perf_counter() - t0
                    with self._lock:
                        self._blocked_submits += 1
                        self._blocked_s += blocked
                    return task
                if self.backpressure == "reject":
                    task.record.error = "rejected: queue full"
                    task.done.set()
                    with self._lock:
                        self._dropped += 1
                        self._account(task.record)
                    self._finish_one()
                    return task
                # drop_oldest
                try:
                    old = self._q.get_nowait()
                    old.record.error = "dropped: backpressure"
                    old.done.set()
                    with self._lock:
                        self._dropped += 1
                        self._account(old.record)
                    self._finish_one()
                except queue.Empty:
                    pass

    def _account(self, rec: TaskRecord) -> None:  # requires: _lock
        """Fold one finished (or dropped) task into the running totals."""
        if rec.error is None:
            self._completed += 1
            self._wait_s += rec.wait_s
            self._run_s += rec.run_s
        else:
            self._failed += 1
            if len(self._errors) < 8:
                self._errors.append(rec.error)

    def _finish_one(self) -> None:
        with self._cv:
            self._inflight -= 1
            self._cv.notify_all()

    def _worker(self):
        while not self._stop.is_set():
            try:
                task = self._q.get(timeout=0.1)
            except queue.Empty:
                continue
            task.record.started_at = time.time()
            host_args = []
            try:
                for a in task.args:
                    host_args.append(jax.device_get(a)
                                     if isinstance(a, jax.Array) else a)
            except Exception as e:  # staging failure
                task.record.error = f"staging: {e}"
            if task.record.error is None:
                for attempt in range(task.max_retries + 1):
                    try:
                        task.result = task.fn(*host_args)
                        task.record.error = None
                        break
                    except Exception as e:
                        task.record.error = \
                            f"{type(e).__name__}: {e}"
                        task.record.retries = attempt
            task.record.finished_at = time.time()
            task.done.set()
            with self._lock:
                self._account(task.record)
            self._finish_one()        # after the totals: drain()==True
            self._q.task_done()       # implies the outcome is counted

    # -- introspection / lifecycle ----------------------------------------------
    def drain(self, timeout: float = 30.0) -> bool:
        """Wait (with timeout) until every accepted task has finished —
        the checkpoint barrier at shutdown.  ``queue.join()`` semantics, but
        interruptible: returns False if work is still in flight at timeout."""
        with self._cv:
            return self._cv.wait_for(lambda: self._inflight == 0, timeout)

    def stats(self) -> Dict[str, Any]:
        """Outcome totals (``failed`` counts dropped tasks too, as each
        carries an error), mean queue wait and run time of the completed
        tasks, the first 8 errors, and back-pressure: ``submitted`` calls,
        ``blocked_submits`` that waited for room and the ``blocked_s``
        seconds their callers spent waiting."""
        with self._cv:
            submitted = self._submitted
        with self._lock:
            ok = self._completed
            s = {
                "completed": ok,
                "failed": self._failed,
                "dropped": self._dropped,
                "mean_wait_s": self._wait_s / ok if ok else 0.0,
                "mean_run_s": self._run_s / ok if ok else 0.0,
                "errors": list(self._errors),
                "blocked_submits": self._blocked_submits,
                "blocked_s": self._blocked_s,
            }
        s["submitted"] = submitted
        return s

    def shutdown(self, drain: bool = True):
        """Stop the workers.  Idempotent: a second call is a no-op sweep.

        With ``drain=False`` any queued-but-unstarted task is failed out
        (error recorded, ``done`` set, counted in ``_inflight``'s release)
        so a later ``drain()`` or ``task.done.wait()`` cannot hang on work
        no worker will ever run."""
        if drain:
            self.drain()
        self._stop.set()
        for t in self._threads:
            t.join(timeout=2.0)
        # Workers have exited (or timed out mid-task); cancel what never
        # started so every accepted task still reaches a terminal state.
        while True:
            try:
                task = self._q.get_nowait()
            except queue.Empty:
                break
            task.record.error = "cancelled: executor shut down"
            task.record.finished_at = time.time()
            task.done.set()
            with self._lock:
                self._dropped += 1
                self._account(task.record)
            self._finish_one()
