"""Serve engines: continuous batching, paged KV-cache, fixed-batch baseline.

The split follows the paper's doctrine directly:

  * **Fast path (device)** — the fixed-shape jitted programs in
    ``serve.programs``: bucket prefill (batch 1, one trace per bucket
    length), batched decode (always ``max_batch`` wide), and slot insertion.
    The device never sees a dynamic shape, so heterogeneous traffic costs no
    recompiles.
  * **Admission plane (host, G2)** — ``serve.scheduler``: between decode
    steps, finished requests are evicted (per-request EOS / max-token),
    freed slots are recycled, and queued requests are prefilled solo and
    spliced into the running batch — new arrivals join mid-decode instead of
    waiting for a full batch to drain.
  * **Bookkeeping (sidecar, G2)** — latency records and spilled-page
    staging go through ``BackgroundExecutor``; the step loop blocks on them
    only when the sidecar's queue is full (counted in ``stats()["sidecar"]``).
  * **Results (G3)** — completed generations land in a ``ShardedStore``
    hash-sharded over peer endpoints, the paper's Redis-slot scheme.

``FixedBatchEngine`` keeps the old drain-the-whole-batch behavior as the
benchmark baseline (``benchmarks/serve_continuous.py``).

Spans: the loop marks its phases with ``jax.profiler.TraceAnnotation``, which
records only while a profiler trace runs, on the device trace's clock.
``serve.step`` holds ``serve.admit`` (one per admission attempt, with its
``rid``) and ``serve.decode``; each holds a ``*.wait`` span around its
blocking device->host readback.  ``serve.submit`` marks an enqueue, and the
paged backend adds ``kv.match`` and ``kv.alloc`` inside an admission.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.config.model import ModelConfig
from repro.config.run import ServeConfig
from repro.core.endpoint import ShardedStore
from repro.core.executor import BackgroundExecutor
from repro.models.transformer import (
    ExecPolicy, init_decode_state, supports_paging)
from repro.runtime.locks import make_lock, make_rlock
from repro.serve import programs
from repro.serve.backends import make_backend
from repro.serve.kvpool import unpack_handoff
from repro.serve.sampler import SamplingParams, sample
from repro.serve.scheduler import (
    hit_stop, hit_stop_at, needs_exact_prefill, normalize_stop, QueueFull,
    Request, Scheduler, SlotTable)
from repro.serve.speculative import build_draft_plane
from repro.train.steps import make_decode_step, make_prefill_step


class ContinuousEngine:
    """Continuous-batching engine; see module docstring for the G2/G3 split."""

    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 policy: ExecPolicy = ExecPolicy(),
                 executor: Optional[BackgroundExecutor] = None,
                 result_endpoints: Optional[Sequence[Any]] = None,
                 drafter: Optional[Tuple[ModelConfig, Any]] = None):
        self.cfg, self.scfg = cfg, scfg
        self.params = params
        self.policy = policy
        self._key = jax.random.PRNGKey(scfg.seed)

        B = scfg.max_batch
        self.slots = SlotTable(B)
        self.scheduler = Scheduler(scfg, exact_buckets=needs_exact_prefill(cfg))
        # Per-slot mirrors live on device (see programs.decode_program); the
        # host only keeps what its eviction logic reads.
        self._mirrors = {
            "tok": jnp.zeros(B, jnp.int32),
            "pos": jnp.zeros(B, jnp.int32),
            "temp": jnp.zeros(B, jnp.float32),
            "top_k": jnp.zeros(B, jnp.int32),
            "top_p": jnp.ones(B, jnp.float32),
        }
        self._eos = np.full(B, -1, np.int32)
        self._host_temps = np.zeros(B, np.float32)
        # Speculative plane (ServeConfig.speculative): the drafter's own
        # device states + programs, and per-slot write ceilings (the last
        # position a row may legitimately occupy; 0 for free slots) that the
        # verify/propose programs clamp chunk positions to, so overshooting
        # a budget scatters into the row's own never-read tail.
        self._caps = np.zeros(B, np.int32)
        if scfg.speculative:
            self._check_speculative_target()
        self._draft = (build_draft_plane(cfg, params, scfg, policy, drafter)
                       if scfg.speculative else None)
        self._build_device_plane()

        # Sidecar plane (G2) + sharded result store (G3).
        self._own_executor = executor is None
        self.executor = executor or BackgroundExecutor(
            num_threads=2, max_inflight=8, backpressure="block")
        endpoints = (list(result_endpoints) if result_endpoints is not None
                     else [dict() for _ in range(max(1, scfg.result_shards))])
        self.store = ShardedStore(endpoints)
        # slot->endpoint ownership is static; compute the balance once so
        # stats() stays O(1) on the decode loop
        self._shard_balance = self.store.balance()
        # One lock covers everything mutated by the engine loop and read from
        # other threads (records, step/token counters): stats() and result()
        # may legally race the loop thread.
        self._lock = make_lock("ContinuousEngine._lock")
        self.records: List[Dict[str, Any]] = []        # guarded-by: _lock

        self._rid = itertools.count()
        self._requests: Dict[int, Request] = {}        # guarded-by: _admission
        self._steps = 0                                # guarded-by: _lock
        self._tokens_out = 0                           # guarded-by: _lock
        self._spec_steps = 0                           # guarded-by: _lock
        self._spec_proposed = 0                        # guarded-by: _lock
        self._spec_accepted = 0                        # guarded-by: _lock
        self._cb_errors = 0                            # guarded-by: _lock
        # Set-once close latch: checked lock-free on the hot step path, set
        # under _admission so no submit() can slip past a closing engine.
        self._closed = threading.Event()
        self._loop_error: Optional[BaseException] = None  # guarded-by: _lock
        # Serializes the step loop against close()/failure teardown: a
        # close() racing a mid-flight step must not release slots the loop
        # is still decoding (RLock: the step exception path re-enters via
        # _fail_pending).  submit() deliberately does NOT take it — a
        # producer must never stall behind a device step — so queue
        # admission vs. teardown atomicity gets its own small lock.
        self._lifecycle = make_rlock("ContinuousEngine._lifecycle")
        self._admission = make_lock("ContinuousEngine._admission")

    def _check_speculative_target(self) -> None:
        """Dense-engine gate, checked before drafter resolution so the
        caller hears about the unsupported *target* first.  The dense verify
        relies on stale rejected entries being causally masked — only true
        for global-attention rows.  Other archs speculate through the paged
        engine's SnapshotBackend (all-or-nothing verify with an explicit
        fallback state); ``PagedEngine`` overrides this as a no-op."""
        if not supports_paging(self.cfg):
            raise ValueError(
                f"{self.cfg.arch_id}: dense speculative decode needs a "
                "global-attention decoder-only arch; serve this config "
                "with engine_mode='paged' (snapshot backend) instead")

    def _build_device_plane(self) -> None:
        """Fast path: two fixed-shape fused programs (admit retraces once per
        bucket length; decode is a single trace), shared process-wide through
        ``serve.programs``'s compiled-program cache.  Donations keep the
        batch state and per-slot mirrors updated in place.  ``PagedEngine``
        overrides this with block-table programs over a shared page pool."""
        cfg, scfg = self.cfg, self.scfg
        self._admit_prog = programs.admit_program(
            cfg, self.policy, scfg.max_seq_len)
        self._decode_prog = programs.decode_program(cfg, self.policy)
        if self._draft is not None:
            self._verify_prog = programs.verify_program(
                cfg, self.policy, scfg.draft_k)
        self.states = init_decode_state(cfg, scfg.max_batch,
                                        capacity=scfg.max_seq_len)

    # -- request lifecycle ----------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               sampling: Optional[SamplingParams] = None,
               frontend_embeds: Optional[np.ndarray] = None,
               stop=None,
               on_token: Optional[Callable[[int], None]] = None) -> int:
        """Enqueue a request; returns its rid.  ``on_token``, if given, is
        called with each token id as it is committed (engine loop thread,
        after stop/EOS/budget truncation — under speculative decoding an
        accepted draft chunk streams in acceptance order).  A raising
        callback is disabled after its first exception, never fatal."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token array")
        # Validate the budget *before* using it in the length arithmetic:
        # an invalid budget must get the budget error, not a misleading
        # max_seq_len complaint (or none at all, for large negatives).
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > self.scfg.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len ({self.scfg.max_seq_len})")
        req = Request(next(self._rid), prompt, max_new_tokens,
                      sampling or SamplingParams.from_config(self.scfg),
                      frontend_embeds=frontend_embeds,
                      stop=normalize_stop(stop), on_token=on_token)
        # Atomic against _fail_pending's teardown so a request can never
        # slip into the queue after close() already failed everything.
        with TraceAnnotation("serve.submit", rid=req.rid), self._admission:
            if self._closed.is_set():
                raise RuntimeError("engine is closed; no new submissions")
            self.scheduler.push(req)      # raises QueueFull at capacity
            self._requests[req.rid] = req
        return req.rid

    def can_admit(self, prompt_len: int, max_new_tokens: int,
                  hit_pages: int = 0) -> bool:
        """Whether an admission now would find a slot (and, for paged
        engines, pages) without deferring — the cluster router's dispatch
        gate.  Queued requests are counted against the free slots: they will
        consume them first."""
        del prompt_len, max_new_tokens, hit_pages
        return self.slots.free_count() > self.scheduler.depth()

    def preempt(self, rid: int) -> Optional[Request]:
        """Withdraw an unfinished request, releasing its slot (and pages)
        immediately.  Returns the request — partial output preserved — so the
        caller can re-enqueue a continuation, or None if the request is
        unknown or already finished.  The cluster's QoS plane uses this to
        evict best-effort work under paid-class pressure."""
        with self._lifecycle:
            with self._admission:
                req = self._requests.get(rid)
                if req is None or req.done:
                    return None
                del self._requests[rid]
            if req.slot >= 0 and self.slots.get(req.slot) is req:
                self._release_slot(req.slot)
                req.slot = -1
            else:
                self.scheduler.remove(req)
            return req

    def _admit(self) -> int:
        """Fill free slots from the queue: solo bucket prefill, sample the
        first token, splice the state into the running batch."""
        admitted = 0
        while self.slots.free_count() and not self.scheduler.empty():
            req = self.scheduler.pop()
            began = time.time()
            with TraceAnnotation("serve.admit", rid=req.rid):
                tok0 = self._admit_one(req)
            if tok0 is None:            # resource shortage (paged engine):
                self.scheduler.push_front(req)   # retry after evictions free
                break                            # pages on later steps
            req.admitted_at = began
            sp = req.sampling
            slot = req.slot
            req.first_token_at = time.time()
            req.output.append(tok0)
            admitted += 1
            self._eos[slot] = sp.eos_id
            self._host_temps[slot] = sp.temperature
            self._deliver(req, len(req.output) - 1)
            if (sp.eos_id >= 0 and tok0 == sp.eos_id) \
                    or req.max_new_tokens <= 1 \
                    or hit_stop(req.output, req.stop):
                self._release_slot(slot)  # finished during admission
                self._finish(req)
            elif self._draft is not None:
                self._caps[slot] = len(req.prompt) + req.max_new_tokens - 1
                self._draft_admit(req, slot)
        return admitted

    def _admit_one(self, req: Request) -> Optional[int]:
        """Acquire a slot and run the fused admit program for one request.
        Returns the first sampled token, or None if admission must wait."""
        L = len(req.prompt)
        # bucket_for clamps to capacity: an over-capacity bucket would
        # ring-wrap the prefill and drop the head of the prompt's cache.
        S = self.scheduler.bucket_for(L)
        toks = np.zeros((1, S), np.int32)
        toks[0, :L] = req.prompt
        positions = np.arange(S, dtype=np.int32)[None, :]
        sp = req.sampling
        batch = {"tokens": jnp.asarray(toks),
                 "positions": jnp.asarray(positions),
                 "length": jnp.asarray(L, jnp.int32),
                 "temp": jnp.asarray(sp.temperature, jnp.float32),
                 "top_k": jnp.asarray(sp.top_k, jnp.int32),
                 "top_p": jnp.asarray(sp.top_p, jnp.float32)}
        if req.frontend_embeds is not None:
            batch["frontend_embeds"] = jnp.asarray(req.frontend_embeds)
        slot = self.slots.acquire(req)
        self.states, tok, self._key, self._mirrors = self._admit_prog(
            self.params, self.states, batch,
            jnp.asarray(slot, jnp.int32), self._key, self._mirrors)
        with TraceAnnotation("serve.admit.wait"):
            return int(tok[0])

    def _deliver(self, req: Request, start: int) -> None:
        """Stream ``req.output[start:]`` to the request's ``on_token``
        callback.  Runs after truncation, so only committed tokens are ever
        delivered; a raising callback is disabled, not fatal."""
        cb = req.on_token
        if cb is None:
            return
        try:
            for t in req.output[start:]:
                cb(int(t))
        except Exception:
            req.on_token = None
            with self._lock:
                self._cb_errors += 1

    def _draft_admit(self, req: Request, slot: int) -> None:
        """Prefill the admitted prompt into the drafter's state.  Subclasses
        hosting the draft plane on another endpoint account its time there."""
        self._draft.admit(slot, req.prompt,
                          self.scheduler.bucket_for(len(req.prompt)))

    def _draft_propose(self, caps: jax.Array) -> jax.Array:
        """k greedy draft tokens per row, continuing the target's committed
        mirrors (the drafter keeps no mirrors of its own)."""
        return self._draft.propose(self._mirrors["tok"],
                                   self._mirrors["pos"], caps)

    def _release_slot(self, slot: int) -> None:
        self.slots.release(slot)
        self._caps[slot] = 0
        # Zero the freed slot's device temperature so an all-greedy batch
        # regains the cheap argmax sampling path (a stale temp > 0 would
        # force the stochastic branch on every later step).
        if self._host_temps[slot] > 0.0:
            self._host_temps[slot] = 0.0
            self._mirrors = dict(self._mirrors,
                                 temp=jnp.asarray(self._host_temps))

    def _decode_device(self) -> np.ndarray:
        """Run the fused decode program; returns the (B,) sampled tokens."""
        self.states, toks_dev, self._key, self._mirrors = self._decode_prog(
            self.params, self.states, self._key, self._mirrors)
        with TraceAnnotation("serve.decode.wait"):
            return np.asarray(toks_dev)

    def _decode_once(self) -> bool:
        """One batched decode step over all slots + per-slot evictions."""
        active = self.slots.active()
        if not active:
            return False
        with TraceAnnotation("serve.decode"):
            if self._draft is not None:
                self._decode_spec_once(active)
            else:
                self._decode_plain(active)
            with self._lock:
                self._steps += 1
        return True

    def _decode_plain(self, active: List[Request]) -> None:
        """One token per active slot; finished slots are released."""
        toks = self._decode_device()
        for req in active:
            slot = req.slot
            tok = int(toks[slot])
            req.output.append(tok)
            with self._lock:
                self._tokens_out += 1
            self._deliver(req, len(req.output) - 1)
            if (self._eos[slot] >= 0 and tok == self._eos[slot]) \
                    or len(req.output) >= req.max_new_tokens \
                    or hit_stop(req.output, req.stop):
                # Stop sequences finish inclusively: the matched tokens stay
                # in the output (callers strip them if they want clean text).
                self._release_slot(slot)
                self._finish(req)

    def _verify_device(self, drafts: jax.Array, caps: jax.Array):
        """Run the fused verify program; returns the host (B, k+1) emitted
        chunk and (B,) accept lengths."""
        self.states, out, acc, self._key, self._mirrors = self._verify_prog(
            self.params, self.states, self._key, self._mirrors, drafts, caps)
        with TraceAnnotation("serve.decode.wait"):
            return np.asarray(out), np.asarray(acc)

    def _decode_spec_once(self, active: List[Request]) -> None:
        """One speculative macro step: the drafter proposes k tokens per
        slot, the target verifies all k+1 positions in one batched forward,
        and each slot commits its accepted prefix — with the same per-token
        termination semantics as sequential decode (EOS, token budget, or a
        stop sequence completing *inside* the chunk truncate mid-chunk, at
        the earliest trigger)."""
        k = self._draft.k
        caps = jnp.asarray(self._caps)
        drafts = self._draft_propose(caps)
        out, acc = self._verify_device(drafts, caps)
        committed = proposed = accepted = 0
        for req in active:
            slot = req.slot
            m = int(acc[slot])
            if self._host_temps[slot] <= 0.0:   # only greedy rows speculate
                proposed += k
                accepted += m
            start = len(req.output)
            req.output.extend(int(out[slot, j]) for j in range(m + 1))
            cut = None                    # terminal output length, if any
            eos = int(self._eos[slot])
            if eos >= 0:
                for j in range(m + 1):
                    if int(out[slot, j]) == eos:
                        cut = start + j + 1
                        break
            if len(req.output) >= req.max_new_tokens:
                cut = (req.max_new_tokens if cut is None
                       else min(cut, req.max_new_tokens))
            scut = hit_stop_at(req.output, req.stop, start + 1)
            if scut is not None and (cut is None or scut < cut):
                cut = scut
            if cut is not None:
                del req.output[cut:]
            committed += len(req.output) - start
            self._deliver(req, start)
            if cut is not None:
                self._release_slot(slot)
                self._finish(req)
        with self._lock:
            self._tokens_out += committed
            self._spec_steps += 1
            self._spec_proposed += proposed
            self._spec_accepted += accepted

    def step(self) -> bool:
        """Admit + one decode step.  Returns False once fully idle.

        An exception out of the decode loop is terminal for every in-flight
        request: it is recorded (so ``result()`` surfaces it instead of
        reporting the request as forever "still decoding") and every
        pending request gets a terminal error record before re-raising."""
        with TraceAnnotation("serve.step"), self._lifecycle:
            if self._closed.is_set():
                return False
            try:
                admitted = self._admit()
                return self._decode_once() or admitted > 0
            except Exception as e:
                with self._lock:
                    self._loop_error = e
                self._fail_pending(
                    f"decode loop died: {type(e).__name__}: {e}")
                raise

    def run(self) -> None:
        """Drive until queue and slots are empty (the serve loop)."""
        while self.step():
            pass

    def _finish(self, req: Request) -> None:
        done_at = time.time()
        payload = {
            "rid": req.rid,
            "tokens": list(req.output),
            "prompt_len": int(len(req.prompt)),
            "queue_s": req.admitted_at - req.submitted_at,
            "ttft_s": req.first_token_at - req.submitted_at,
            "e2e_s": done_at - req.submitted_at,
        }
        # Latency-insensitive bookkeeping rides the sidecar (G2): the store
        # write + latency record never block the decode loop.  Submit BEFORE
        # marking the request done: a concurrent result(rid, wait=True) that
        # observes req.done must find the record covered by its drain()
        # (submitting after would open a done-but-not-yet-recorded window).
        self.executor.submit(f"serve.record/{req.rid}", self._record, payload)
        req.finished_at = done_at

    def _record(self, payload: Dict[str, Any]) -> None:
        self.store.put(f"req/{payload['rid']}", payload)
        with self._lock:
            self.records.append(payload)

    def _fail_pending(self, reason: str) -> None:
        """Terminate every unfinished request with an error record.

        Runs on close() and on decode-loop death so a ``result(wait=True)``
        waiter always finds a terminal record instead of waiting on a
        request that can no longer finish.  Records are written
        synchronously — this path is not latency-sensitive and must not
        depend on the sidecar still being alive.  Holds the admission lock
        so no submit() can enqueue between the sweep and the queue drain."""
        with self._admission:
            pending = [r for r in self._requests.values() if not r.done]
            for req in pending:
                if req.slot >= 0 and self.slots.get(req.slot) is req:
                    self._release_slot(req.slot)
                done_at = time.time()
                self._record({
                    "rid": req.rid,
                    "tokens": list(req.output),
                    "prompt_len": int(len(req.prompt)),
                    "ttft_s": (req.first_token_at - req.submitted_at
                               if req.first_token_at else 0.0),
                    "e2e_s": done_at - req.submitted_at,
                    "error": reason,
                })
                req.finished_at = done_at
            while not self.scheduler.empty():
                self.scheduler.pop()

    # -- results / introspection ----------------------------------------------
    def result(self, rid: int, wait: bool = True) -> Dict[str, Any]:
        """Fetch a completed generation from the sharded result store.

        A request the engine can no longer finish is still terminal:
        ``close()`` and decode-loop death write error records for every
        pending request, so this returns a payload with an ``"error"`` key
        instead of hanging the waiter; a decode-loop exception re-raises
        here with the original as cause.

        Callers that passed ``on_token`` to :meth:`submit` have already
        streamed these tokens; the payload's ``"tokens"`` list is the
        authoritative record (same ids, same order, post-truncation)."""
        if wait and not self.executor.drain():
            raise TimeoutError(
                f"sidecar drain timed out before req/{rid} was recorded")
        with self._admission:
            req = self._requests.get(rid)
        if req is not None and not req.done:
            with self._lock:
                loop_error = self._loop_error
            if loop_error is not None:
                raise RuntimeError(
                    f"request {rid} cannot complete: the decode loop died"
                ) from loop_error
            raise RuntimeError(
                f"request {rid} is still queued/decoding; drive step()/run() "
                "to completion before fetching its result")
        return self.store.get(f"req/{rid}")

    def request(self, rid: int) -> Request:
        with self._admission:
            return self._requests[rid]

    def stats(self) -> Dict[str, Any]:
        # Counters are mutated by the engine loop thread; snapshot them under
        # the lock so a concurrent reader never sees a torn update.
        with self._lock:
            steps, tokens = self._steps, self._tokens_out
            cb_errors = self._cb_errors
            spec = (self._spec_steps, self._spec_proposed,
                    self._spec_accepted)
        s = {
            "steps": steps,
            "tokens_out": tokens,
            "active": len(self.slots.active()),
            "queued": self.scheduler.depth(),
            "free_slots": self.slots.free_count(),
            "result_shards": self._shard_balance,
        }
        if cb_errors:
            s["callback_errors"] = cb_errors
        s["sidecar"] = self.executor.stats()
        if self._draft is not None:
            msteps, prop, acc = spec
            s["speculative"] = {
                "draft_k": self._draft.k,
                "macro_steps": msteps,
                "proposed": prop,
                "accepted": acc,
                "acceptance_rate": (acc / prop) if prop else 0.0,
            }
        return s

    def spec_boost(self) -> float:
        """Expected committed tokens per device macro step relative to
        sequential decode — 1 + k * acceptance_rate for greedy traffic, 1.0
        until enough chunks have been measured.  The cluster cost model
        scales a replica's queue-drain estimate by this."""
        if self._draft is None:
            return 1.0
        with self._lock:
            prop, acc = self._spec_proposed, self._spec_accepted
        if prop < self._draft.k * 8:       # too few chunks to trust yet
            return 1.0
        return 1.0 + self._draft.k * (acc / prop)

    def cache_bytes(self) -> int:
        """Resident KV-cache bytes (dense per-slot buffers or paged pools) —
        the benchmark's fixed-memory axis."""
        total = 0

        def visit(path, leaf):
            nonlocal total
            last = path[-1]
            if (isinstance(last, jax.tree_util.DictKey)
                    and last.key in ("k", "v", "kp", "vp", "ksc", "vsc")):
                total += leaf.nbytes
            return leaf
        jax.tree_util.tree_map_with_path(visit, self.states)
        return total

    def close(self) -> None:
        """Shut down: fail whatever is still pending (queued or mid-decode)
        with terminal records so concurrent ``result(wait=True)`` callers
        wake with an error payload instead of hanging, then drain the
        sidecar."""
        with self._lifecycle:       # wait out any in-flight step first
            if not self._closed.is_set():
                # Latch under _admission: a submit() that got past the latch
                # check is in the queue before _fail_pending sweeps it; one
                # that didn't will raise.  Then fail everything pending.
                with self._admission:
                    self._closed.set()
                self._fail_pending("engine closed before completion")
        self.executor.drain()
        if self._own_executor:
            self.executor.shutdown(drain=False)

    # -- batch convenience (old ServeEngine.generate API) ----------------------
    def generate(self, prompts: List[np.ndarray], max_new_tokens: int,
                 frontend_embeds: Optional[np.ndarray] = None
                 ) -> Dict[int, Request]:
        """Submit a list of prompts and drive to completion.  Returns
        {index -> Request}, matching the old fixed-batch engine's API."""
        out: Dict[int, Request] = {}
        for i, p in enumerate(prompts):
            fe = (np.asarray(frontend_embeds[i:i + 1])
                  if frontend_embeds is not None else None)
            while True:
                try:
                    rid = self.submit(p, max_new_tokens, frontend_embeds=fe)
                    break
                except QueueFull:
                    self.step()           # make room: drain one decode step
            out[i] = self.request(rid)
        self.run()
        self.executor.drain()
        return out


# The continuous engine is the default serving entry point.
ServeEngine = ContinuousEngine


class PagedEngine(ContinuousEngine):
    """Continuous batching over a pluggable decode-state backend.

    The dense engine allocates ``max_batch x max_seq_len`` cache rows up
    front — worst-case memory per slot, no sharing, nothing ever cools.
    This engine keeps the same admission plane but delegates all cache
    management to a ``serve.backends.CacheBackend``, picked per arch by
    ``make_backend``:

      * **PagedKVBackend** (all-global-attention decoder-only archs) — the
        paper's endpoint-expansion plane: a physical page pool per attention
        layer with a host-side block table (resident memory follows the live
        token count), rolling-hash CoW prefix reuse, and LRU spill of
        reusable prefix pages to a host-endpoint ``ColdTier`` via the
        sidecar (advice #2/#3).
      * **SnapshotBackend** (recurrent / SWA / enc-dec archs) — per-slot
        state is a fixed-size tree, so the reuse unit is a whole batch-1
        state snapshot at a prompt boundary: an LRU snapshot pool with
        cold-tier spill, and suffix-only resume prefill on a prefix hit.

    Both backends implement the handoff-import half of disaggregated
    serving: when a ``handoff_store`` is attached, admission first checks it
    for a blob published under this request's key (by a ``PrefillWorker`` on
    another endpoint) and splices that state in instead of prefilling.
    This is what lets a ``DisaggregatedEngine`` — or each decode replica of
    a ``ServeCluster`` — consume remotely-prefilled prompts, for every arch
    in ``configs/``.
    """

    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 policy: ExecPolicy = ExecPolicy(),
                 executor: Optional[BackgroundExecutor] = None,
                 result_endpoints: Optional[Sequence[Any]] = None,
                 handoff_endpoints: Optional[Sequence[Any]] = None,
                 handoff_ns: str = "",
                 drafter: Optional[Tuple[ModelConfig, Any]] = None):
        self.backend = make_backend(cfg, scfg)  # validates page geometry
        self.page_size = scfg.page_size
        # Handoff-import plane (disaggregated / cluster serving).  The
        # namespace keeps per-replica keys disjoint when several engines
        # share one blob store.
        self.handoff_ns = handoff_ns
        self.handoff_store = (ShardedStore(list(handoff_endpoints))
                              if handoff_endpoints is not None else None)
        # Mutated by the loop thread during admission, read by stats()
        # callers (cluster driver, benchmarks) — _lock is created by the
        # super().__init__ call below, before any sharing can start.
        self._remote_admits = 0               # guarded-by: _lock
        self._local_admits = 0                # guarded-by: _lock
        self._deferred_imports = 0            # guarded-by: _lock
        self._handoff_bytes = 0               # guarded-by: _lock
        super().__init__(cfg, params, scfg, policy, executor,
                         result_endpoints, drafter=drafter)

    def _check_speculative_target(self) -> None:
        # Every arch speculates here: the backend layer supplies rollback
        # (write-position bookkeeping for paged KV, all-or-nothing state
        # select for snapshots).
        return None

    def _build_device_plane(self) -> None:
        # The backend owns the fused programs and the decode-state layout;
        # binding happens here because the backend's programs need
        # ``self.policy`` and its state allocation sets ``self.states``.
        self.backend.bind(self)
        self.backend.build_device_plane()

    # -- backend pass-throughs (compat with pre-backend callers/tests) ---------
    @property
    def pool(self):
        """The backend's cache substrate (``KVBlockPool`` / ``SnapshotPool``)."""
        return self.backend.pool

    @property
    def cold(self):
        """The backend's cold tier (or None)."""
        return self.backend.cold

    def prefix_hits(self, chains) -> int:
        """Affinity units already resident here, without LRU side effects
        (pages for the paged backend, matched snapshots otherwise)."""
        return self.backend.probe(chains)[0]

    def can_admit(self, prompt_len: int, max_new_tokens: int,
                  hit_pages: int = 0) -> bool:
        if self.slots.free_count() <= self.scheduler.depth():
            return False
        return self.backend.can_admit_resources(prompt_len, max_new_tokens,
                                                hit_pages)

    def _handoff_key(self, rid: int) -> str:
        return f"kv/{self.handoff_ns}{rid}"

    def _admit_one(self, req: Request) -> Optional[int]:
        if self.handoff_store is not None:
            key = self._handoff_key(req.rid)
            data = self.handoff_store.pop(key)
            if data is not None:
                tok0 = self.backend.import_handoff(req, unpack_handoff(data))
                if tok0 is None:
                    # Pool exhausted: keep the blob so the deferred-admission
                    # retry imports it instead of re-running the remote
                    # prefill.
                    self.handoff_store.put(key, data)
                    with self._lock:
                        self._deferred_imports += 1
                    return None
                with self._lock:                # counted once, on success
                    self._remote_admits += 1
                    self._handoff_bytes += len(data)
                return tok0
        tok0 = self.backend.admit(req)
        if tok0 is not None:
            with self._lock:
                self._local_admits += 1
        return tok0

    # -- decode / release ------------------------------------------------------
    def _decode_device(self) -> np.ndarray:
        return self.backend.decode_step()

    def _verify_device(self, drafts: jax.Array, caps: jax.Array):
        # The backend owns the verify program: block-table scatter for the
        # paged pool, all-or-nothing state select for snapshot archs.
        return self.backend.verify_step(drafts, caps)

    def _release_slot(self, slot: int) -> None:
        self.backend.release(self.slots.get(slot), slot)
        super()._release_slot(slot)

    def stats(self) -> Dict[str, Any]:
        s = super().stats()
        s.update(self.backend.stats())
        s["resident_cache_bytes"] = self.cache_bytes()
        if self.handoff_store is not None:
            with self._lock:
                s["handoffs"] = {
                    "remote_admits": self._remote_admits,
                    "local_admits": self._local_admits,
                    "deferred_imports": self._deferred_imports,
                    "bytes": self._handoff_bytes,
                }
        return s


class FixedBatchEngine:
    """Old drain-the-whole-batch engine: pads the active set to ``max_batch``
    and runs every request to the same horizon.  Kept as the benchmark
    baseline for ``benchmarks/serve_continuous.py``."""

    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 policy: ExecPolicy = ExecPolicy()):
        self.cfg, self.scfg = cfg, scfg
        self.params = params
        self.policy = policy
        self._prefill = jax.jit(make_prefill_step(cfg, policy))
        self._decode = jax.jit(make_decode_step(cfg, policy), donate_argnums=1)
        self._key = jax.random.PRNGKey(scfg.seed)

    def generate(self, prompts: List[np.ndarray], max_new_tokens: int,
                 frontend_embeds: Optional[np.ndarray] = None
                 ) -> Dict[int, Request]:
        """Batched generation.  Prompts must be equal length (the engine runs
        fixed-shape programs; host-side length bucketing is the caller's
        job — the limitation the continuous engine removes)."""
        B = len(prompts)
        lens = {len(p) for p in prompts}
        if len(lens) != 1:
            raise ValueError("FixedBatchEngine batches must be "
                             f"length-bucketed; got lengths {sorted(lens)}")
        S = max(lens.pop(), 1)
        reqs = {i: Request(i, np.asarray(p, np.int32), max_new_tokens)
                for i, p in enumerate(prompts)}
        toks = np.stack([np.asarray(p, np.int32) for p in prompts])
        positions = np.broadcast_to(
            np.arange(S, dtype=np.int32)[None, :], (B, S)).copy()

        # Fixed capacity keeps prefill/decode shapes stable across calls
        # (capacity=S+max_new would retrace per horizon).
        states = init_decode_state(
            self.cfg, B, capacity=max(self.scfg.max_seq_len,
                                      S + max_new_tokens))
        batch = {"tokens": jnp.asarray(toks),
                 "positions": jnp.asarray(positions)}
        if frontend_embeds is not None:
            batch["frontend_embeds"] = jnp.asarray(frontend_embeds)
        states, logits = self._prefill(self.params, states, batch)
        t_first = time.time()

        cur_pos = np.array([len(p) for p in prompts], np.int32)
        for r in reqs.values():
            r.first_token_at = t_first
        for step in range(max_new_tokens):
            self._key, sk = jax.random.split(self._key)
            next_tok = sample(logits, sk, self.scfg)        # (B,)
            host_tok = np.asarray(next_tok)
            for i, r in reqs.items():
                if len(r.output) < r.max_new_tokens:
                    r.output.append(int(host_tok[i]))
            if step == max_new_tokens - 1:
                break
            batch = {"tokens": next_tok[:, None],
                     "positions": jnp.asarray(cur_pos)[:, None]}
            states, logits = self._decode(self.params, states, batch)
            cur_pos = cur_pos + 1
        done = time.time()
        for r in reqs.values():
            r.finished_at = done
        return reqs
