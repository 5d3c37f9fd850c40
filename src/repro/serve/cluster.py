"""Multi-replica serve cluster: one admission plane, N decode replicas, QoS.

``DisaggregatedEngine`` (PR 3) realized the paper's advice #3 — the off-path
device as a new *network endpoint* — for one prefill + one decode pair.
This module generalizes it to the ROADMAP's "millions of users" shape:

  * **N decode replicas per model group**, each a full ``PagedEngine``
    (own slot table, own cache backend — page pool + prefix index for
    paged archs, snapshot pool for recurrent/SWA archs), all in one process,
    replica i on device i (round-robin when replicas outnumber devices);
    the compiled-program cache (``serve.programs``) means N
    replicas cost one set of traces.  ``extra_models`` registers additional
    (config, params) groups, so one cluster serves transformer and
    recurrent traffic concurrently; requests name their group via
    ``submit(..., model=...)``.
  * **A cost-model router** (``serve.router`` over
    ``CostModel.decide_replica``) picks a replica per request from live
    signals — free cache units, batch pressure, queue depth — with
    **prefix affinity**: the prompt's probe handle (chain keys / snapshot
    keys) is probed against every replica of its model group, so
    shared-prefix sessions land where their decode state already lives.
  * **A shared prefill endpoint per model group** (optional): one
    ``PrefillWorker`` feeding that group's replicas through per-replica
    handoff namespaces (``kv/r{i}/{rid}``) over one hash-sharded blob
    store.
  * **Per-tenant QoS** on admission: token-bucket rate limits (violators get
    ``QueueFull``, never a silent hang), priority classes (paid admits
    before best-effort), and **preemption** — when a paid request finds no
    room, the youngest best-effort request on the routed replica is evicted
    and *re-enqueued as a continuation* (prompt + output-so-far; exact under
    greedy decoding), not failed.
  * **Replica-death requeue**: a replica whose step loop dies is marked
    dead, its pending handoff blobs are dropped (``ShardedStore
    .drop_prefix``), and its in-flight requests — partial outputs preserved —
    are re-enqueued as continuations on the survivors.

The cluster driver is single-threaded (``step()``/``run()``), like the
engines it wraps: determinism is what makes the exactness tests possible.
Per-replica busy time is accounted so benchmarks can report the
parallel-world wall clock (replicas are independent endpoints; their step
times overlap): ``wall_parallel ~= wall_serial - sum(busy_i) + max(busy_i)``.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.config.model import ModelConfig
from repro.config.run import ServeConfig
from repro.core.endpoint import ShardedStore
from repro.core.executor import BackgroundExecutor
from repro.models.transformer import ExecPolicy
from repro.runtime.locks import make_lock
from repro.serve.disagg import PrefillWorker
from repro.serve.engines import PagedEngine
from repro.serve.kvpool import pack_handoff
from repro.serve.router import ClusterRouter
from repro.serve.sampler import SamplingParams
from repro.serve.scheduler import normalize_stop, QueueFull, Request


BEST_EFFORT = 0         # priority of the preemptible class


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's QoS contract.

    ``priority`` orders admission (higher first); requests at
    ``BEST_EFFORT`` (0) are preemptible under paid-class pressure.
    ``rate_limit`` caps sustained submissions per second through a token
    bucket of ``burst`` capacity; 0 disables the limit."""
    name: str
    priority: int = BEST_EFFORT
    rate_limit: float = 0.0          # requests/s sustained; 0 = unlimited
    burst: int = 8                   # bucket capacity (requests)

    @property
    def preemptible(self) -> bool:
        return self.priority <= BEST_EFFORT


class TokenBucket:
    """Classic token bucket; the clock is injectable so rate-limit tests
    don't sleep."""

    def __init__(self, rate: float, burst: int,
                 clock: Callable[[], float] = time.monotonic):
        self.rate = rate
        self.burst = max(1, burst)
        self.clock = clock
        self._tokens = float(self.burst)
        self._last = clock()

    def try_take(self) -> bool:
        now = self.clock()
        self._tokens = min(self.burst,
                           self._tokens + (now - self._last) * self.rate)
        self._last = now
        if self._tokens >= 1.0:
            self._tokens -= 1.0
            return True
        return False


@dataclasses.dataclass
class ClusterRequest:
    """One request's cluster-level lifetime, across preemptions and replica
    deaths.  ``output`` accumulates tokens from every admission round; the
    per-round engine ``Request`` only ever holds its own round's tokens."""
    crid: int
    tenant: TenantSpec
    prompt: np.ndarray
    max_new_tokens: int
    sampling: SamplingParams
    submitted_at: float
    model: str = "default"           # model group this request routes within
    stop: Tuple[Tuple[int, ...], ...] = ()
    # Streaming: tokens land from whichever replica currently decodes the
    # request; continuation rounds re-prefill output-so-far, so each token
    # is delivered exactly once.  Cleared on the first exception it raises.
    on_token: Optional[Callable[[int], None]] = None
    output: List[int] = dataclasses.field(default_factory=list)
    replica: int = -1                # current replica index (-1 = queued)
    rid: int = -1                    # rid on that replica
    first_token_at: float = 0.0
    finished_at: float = 0.0
    preemptions: int = 0
    requeues: int = 0                # replica-death reassignments
    error: str = ""

    @property
    def done(self) -> bool:
        return self.finished_at > 0.0

    @property
    def remaining(self) -> int:
        return self.max_new_tokens - len(self.output)

    def continuation(self) -> "tuple[np.ndarray, int]":
        """(prompt, max_new) for the next admission round: the original
        prompt extended by everything generated so far.  Exact under greedy
        decoding — re-prefilling the extended prompt reproduces the decode
        state the preempted slot held."""
        if not self.output:
            return self.prompt, self.max_new_tokens
        return (np.concatenate([self.prompt,
                                np.asarray(self.output, np.int32)]),
                self.remaining)


class ServeCluster:
    """One admission plane in front of N ``PagedEngine`` decode replicas.

    Public surface mirrors the engines: ``submit`` / ``step`` / ``run`` /
    ``result`` / ``stats`` / ``generate`` / ``close``, plus
    ``route_plan()`` for the router's decision log."""

    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 policy: ExecPolicy = ExecPolicy(),
                 tenants: Optional[Sequence[TenantSpec]] = None,
                 profile: Optional[Any] = None,
                 clock: Callable[[], float] = time.time,
                 extra_models: Optional[
                     Dict[str, Tuple[ModelConfig, Any]]] = None,
                 drafter: Optional[Tuple[ModelConfig, Any]] = None):
        # time.time, not monotonic: TTFT subtracts this clock's submit stamp
        # from the engines' time.time first-token stamp — same epoch or bust.
        if scfg.num_replicas < 1:
            raise ValueError("num_replicas must be >= 1")
        self.cfg, self.scfg = cfg, scfg
        self.clock = clock
        self.executor = BackgroundExecutor(
            num_threads=2, max_inflight=8, backpressure="block")
        rep_scfg = dataclasses.replace(scfg, engine_mode="paged")
        handoff_eps = [dict() for _ in range(max(1, scfg.handoff_shards))]
        self.handoff_store = ShardedStore(handoff_eps)

        # Model groups: "default" plus any extras.  Each group gets
        # scfg.num_replicas replicas; replica indices are global (the
        # handoff namespace r{i}/ stays unique cluster-wide) and
        # ``_model_of`` maps a global index back to its group.
        self.models: Dict[str, Tuple[ModelConfig, Any]] = {
            "default": (cfg, params)}
        for name, (mcfg, mparams) in (extra_models or {}).items():
            if name == "default":
                raise ValueError(
                    "extra_models may not rebind the 'default' group")
            self.models[name] = (mcfg, mparams)
        self.replicas: List[PagedEngine] = []
        self._model_of: List[str] = []
        # Replica i lives on device i (round-robin past the device count):
        # its weights are placed there and its engine allocates its decode
        # state beside them.  Handoffs cross as host bytes, so no array op
        # ever spans two devices.
        devices = jax.devices()
        for name, (mcfg, mparams) in self.models.items():
            for _ in range(scfg.num_replicas):
                i = len(self.replicas)
                dev = devices[i % len(devices)]
                # An explicit drafter override is built against the default
                # group's weights; extra groups resolve their own from
                # scfg.draft_model (e.g. a layer-skip of their own params).
                rep_drafter = None
                if drafter is not None and name == "default":
                    rep_drafter = (drafter[0], jax.device_put(drafter[1], dev))
                self.replicas.append(PagedEngine(
                    mcfg, jax.device_put(mparams, dev), rep_scfg, policy,
                    executor=self.executor,
                    handoff_endpoints=handoff_eps, handoff_ns=f"r{i}/",
                    drafter=rep_drafter))
                self._model_of.append(name)
        n_total = len(self.replicas)
        self.alive = [True] * n_total

        self._prefills: Dict[str, PrefillWorker] = {}
        self.prefill: Optional[PrefillWorker] = None
        if scfg.cluster_prefill:
            # Workers never decode, so they never speculate themselves.
            pre_scfg = dataclasses.replace(
                scfg, max_batch=max(1, scfg.prefill_slots),
                num_pages=scfg.prefill_pages, engine_mode="paged",
                speculative=False)
            for name, (mcfg, mparams) in self.models.items():
                self._prefills[name] = PrefillWorker(
                    mcfg, mparams, pre_scfg, policy, executor=self.executor)
            self.prefill = self._prefills["default"]

        n_params = sum(int(x.size) for x in jax.tree.leaves(params))
        self.router = ClusterRouter(flops_per_token=2.0 * n_params,
                                    page_size=scfg.page_size,
                                    profile=profile)

        self.tenants: Dict[str, TenantSpec] = {
            t.name: t for t in (tenants or [])}
        self._buckets: Dict[str, TokenBucket] = {
            t.name: TokenBucket(t.rate_limit, t.burst, clock)
            for t in self.tenants.values() if t.rate_limit > 0}
        self._default_tenant = TenantSpec("default", priority=1)

        self._crid = itertools.count()
        # The driver (submit/step/run) is single-threaded by contract — the
        # queue and dispatch maps below stay unguarded on that thread.
        # Results, busy accounting and QoS counters ARE read concurrently
        # (result()/stats()/busy_seconds() from bench and test threads), so
        # they get the cluster lock.
        self._pending: List[ClusterRequest] = []      # cluster-level queue
        self._inflight: Dict[int, ClusterRequest] = {}  # crid -> dispatched
        self._by_replica: List[Dict[int, ClusterRequest]] = [
            {} for _ in range(n_total)]               # rid -> cr, per replica
        self._lock = make_lock("ServeCluster._lock")
        self._results: Dict[int, Dict[str, Any]] = {}  # guarded-by: _lock
        self.max_pending = scfg.max_queue * n_total

        # Endpoint busy accounting for the parallel-world wall clock.
        self.busy_s = [0.0] * n_total       # guarded-by: _lock
        self.prefill_busy_s = 0.0           # guarded-by: _lock
        # QoS / lifecycle counters.
        self.preemptions = 0                # guarded-by: _lock
        self.death_requeues = 0             # guarded-by: _lock
        self.rate_limited = 0               # guarded-by: _lock
        self.deaths = 0                     # guarded-by: _lock
        self._closed = threading.Event()

    # -- admission -------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, tenant: str = "default",
               sampling: Optional[SamplingParams] = None,
               model: str = "default", stop=None,
               on_token: Optional[Callable[[int], None]] = None) -> int:
        """Enqueue one request under a tenant's QoS contract.  ``model``
        names the group it routes within; ``stop`` is a token-id stop
        sequence (or list of them) checked host-side after every decode
        step.  Raises ``QueueFull`` when the tenant is over its rate limit
        or the cluster queue is at capacity — callers get backpressure,
        never a hang.

        ``on_token`` streams each committed token id (replica loop thread,
        exactly once across preemptions/requeues — continuation rounds
        re-prefill output already delivered).  One caveat: a stop sequence
        that only completes *across* an admission-round boundary is caught
        by the cluster-level rescan at finish, after its tokens already
        streamed — the result payload is the truncated truth."""
        if self._closed.is_set():
            raise RuntimeError("cluster is closed; no new submissions")
        if model not in self.models:
            raise ValueError(
                f"unknown model group {model!r}; have {sorted(self.models)}")
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token array")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > self.scfg.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len ({self.scfg.max_seq_len})")
        spec = self.tenants.get(tenant, self._default_tenant)
        bucket = self._buckets.get(tenant)
        if bucket is not None and not bucket.try_take():
            with self._lock:
                self.rate_limited += 1
            raise QueueFull(
                f"tenant {tenant!r} over rate limit "
                f"({spec.rate_limit:.3g} req/s, burst {spec.burst})")
        if len(self._pending) >= self.max_pending:
            raise QueueFull(
                f"cluster queue full ({self.max_pending}); retry after step()")
        cr = ClusterRequest(next(self._crid), spec, prompt, max_new_tokens,
                            sampling or SamplingParams.from_config(self.scfg),
                            submitted_at=self.clock(), model=model,
                            stop=normalize_stop(stop), on_token=on_token)
        self._pending.append(cr)
        return cr.crid

    def _requeue(self, cr: ClusterRequest, *, death: bool) -> None:
        """Put a withdrawn request back on the cluster queue as a
        continuation (never fails it).  Exempt from the queue bound — it was
        admitted once already."""
        cr.replica, cr.rid = -1, -1
        if death:
            cr.requeues += 1
        else:
            cr.preemptions += 1
        self._pending.append(cr)

    # -- dispatch --------------------------------------------------------------
    def _dispatch(self) -> int:
        """Route every dispatchable queued request to a replica: paid
        classes first (stable FIFO within a class), prefix affinity + load
        scoring per request, preemption when a paid request finds no room."""
        if not self._pending:
            return 0
        self._pending.sort(key=lambda c: (-c.tenant.priority, c.crid))
        dispatched = 0
        remaining: List[ClusterRequest] = []
        for cr in self._pending:
            # Routing is not free (chain hashing + N affinity probes per
            # request): when no live replica has slot headroom, only paid
            # requests — which can make room by preemption — are worth
            # scoring; best-effort waits for a decode completion.
            if cr.tenant.preemptible and not self._any_room(cr.model):
                remaining.append(cr)
                continue
            if self._dispatch_one(cr):
                dispatched += 1
            else:
                remaining.append(cr)
        self._pending = remaining
        return dispatched

    def _any_room(self, model: str) -> bool:
        return any(self.alive[i] and self._model_of[i] == model
                   and rep.slots.free_count() > rep.scheduler.depth()
                   for i, rep in enumerate(self.replicas))

    def _dispatch_one(self, cr: ClusterRequest) -> bool:
        if _stop_hit_index(cr.output, cr.stop) is not None:
            # A stop sequence completed across admission rounds (it can
            # straddle a preemption boundary); _finish truncates.
            self._finish(cr)
            return True
        prompt, max_new = cr.continuation()
        if max_new <= 0:            # budget already spent pre-withdrawal
            self._finish(cr)
            return True
        # Route only within the request's model group: a replica holding
        # different weights is as unusable as a dead one.
        mask = [self.alive[i] and self._model_of[i] == cr.model
                for i in range(len(self.replicas))]
        idx, decision, _ = self.router.pick(
            cr.crid, prompt, max_new, self.replicas, mask)
        if idx < 0:
            cr.error = decision.rationale       # no live replica: terminal
            self._finish(cr)
            return True
        rep = self.replicas[idx]
        if not rep.can_admit(len(prompt), max_new):
            # A paid request that finds no room evicts the youngest
            # best-effort request on the routed replica (re-enqueued, not
            # failed); best-effort requests just wait for capacity.
            if cr.tenant.preemptible or not self._preempt_on(idx, cr):
                return False
            if not rep.can_admit(len(prompt), max_new):
                return False
        rid = self._submit_to(idx, cr, prompt, max_new)
        if rid is None:
            return False
        cr.replica, cr.rid = idx, rid
        self._inflight[cr.crid] = cr
        self._by_replica[idx][rid] = cr
        return True

    def _token_relay(self, cr: ClusterRequest
                     ) -> Optional[Callable[[int], None]]:
        """Per-dispatch-round relay to the cluster request's callback: a
        raising callback is cleared cluster-wide (later rounds attach
        nothing) and the exception propagates so the replica's own
        disable-and-count path still runs."""
        if cr.on_token is None:
            return None

        def relay(tok: int) -> None:
            cb = cr.on_token
            if cb is None:
                return
            try:
                cb(tok)
            except Exception:
                cr.on_token = None
                raise
        return relay

    def _submit_to(self, idx: int, cr: ClusterRequest, prompt: np.ndarray,
                   max_new: int) -> Optional[int]:
        rep = self.replicas[idx]
        try:
            rid = rep.submit(prompt, max_new, sampling=cr.sampling,
                             stop=cr.stop, on_token=self._token_relay(cr))
        except QueueFull:
            return None
        prefill = self._prefills.get(cr.model)
        if prefill is not None:
            t0 = time.perf_counter()
            h = prefill.prefill_to_handoff(rid, prompt, max_new, cr.sampling)
            with self._lock:
                self.prefill_busy_s += time.perf_counter() - t0
            if h is not None:       # worker out of capacity -> local prefill
                self.handoff_store.put(f"kv/r{idx}/{rid}", pack_handoff(h))
        return rid

    def _preempt_on(self, idx: int, paid: ClusterRequest) -> bool:
        """Evict the youngest best-effort request on replica ``idx`` to make
        room for a paid request; the victim is re-enqueued as a
        continuation.  Returns True if a victim was withdrawn."""
        victims = [cr for cr in self._by_replica[idx].values()
                   if cr.tenant.preemptible and not cr.done]
        if not victims:
            return False
        victim = max(victims, key=lambda c: c.rid)      # youngest admission
        rep = self.replicas[idx]
        req = rep.preempt(victim.rid)
        if req is None:
            return False
        self._withdraw(idx, victim, req)
        self._requeue(victim, death=False)
        with self._lock:
            self.preemptions += 1
        return True

    def _withdraw(self, idx: int, cr: ClusterRequest, req: Request) -> None:
        """Absorb a withdrawn engine request's partial output into the
        cluster request and drop the replica-side bookkeeping."""
        cr.output.extend(req.output)
        if cr.first_token_at == 0.0 and req.first_token_at > 0.0:
            cr.first_token_at = req.first_token_at
        self._by_replica[idx].pop(cr.rid, None)
        self._inflight.pop(cr.crid, None)
        self.handoff_store.pop(f"kv/r{idx}/{cr.rid}", None)

    # -- the drive loop --------------------------------------------------------
    def step(self) -> bool:
        """Dispatch + one decode step on every live replica.  Returns False
        once fully idle.  A replica whose step raises is marked dead and its
        requests are requeued on the survivors — the cluster keeps serving."""
        if self._closed.is_set():
            return False
        progressed = self._dispatch() > 0
        for i, rep in enumerate(self.replicas):
            if not self.alive[i]:
                continue
            t0 = time.perf_counter()
            try:
                worked = rep.step()
            except Exception as e:
                self._on_replica_death(i, e)
                progressed = True
                continue
            with self._lock:
                self.busy_s[i] += time.perf_counter() - t0
            progressed = worked or progressed
            self._harvest(i)
        return progressed or bool(self._pending) or bool(self._inflight)

    def run(self) -> None:
        while self.step():
            pass

    def _harvest(self, idx: int) -> None:
        """Collect finished engine requests on one replica into cluster
        results."""
        done = [(rid, cr) for rid, cr in self._by_replica[idx].items()
                if rep_req_done(self.replicas[idx], rid)]
        for rid, cr in done:
            req = self.replicas[idx].request(rid)
            cr.output.extend(req.output)
            if cr.first_token_at == 0.0 and req.first_token_at > 0.0:
                cr.first_token_at = req.first_token_at
            self._by_replica[idx].pop(rid, None)
            self._inflight.pop(cr.crid, None)
            self._finish(cr)

    def _on_replica_death(self, idx: int, exc: BaseException) -> None:
        """Mark a replica dead, drop its pending handoffs, requeue its
        in-flight requests (partial outputs preserved) on the survivors."""
        self.alive[idx] = False
        with self._lock:
            self.deaths += 1
        stranded = list(self._by_replica[idx].values())
        rep = self.replicas[idx]
        for cr in stranded:
            # The engine's failure path (_fail_pending) released the slot
            # and recorded partial output on the Request; absorb it.
            try:
                req = rep.request(cr.rid)
                output = req.output
                first = req.first_token_at
            except KeyError:
                output, first = [], 0.0
            cr.output.extend(output)
            if cr.first_token_at == 0.0 and first > 0.0:
                cr.first_token_at = first
            self._inflight.pop(cr.crid, None)
            if cr.remaining > 0:
                cr.replica, cr.rid = -1, -1
                cr.requeues += 1
                self._pending.append(cr)
                with self._lock:
                    self.death_requeues += 1
            else:
                self._finish(cr)
        self._by_replica[idx].clear()
        # One-shot payloads nobody will ever pop.
        self.handoff_store.drop_prefix(f"kv/r{idx}/")

    def _finish(self, cr: ClusterRequest) -> None:
        cut = _stop_hit_index(cr.output, cr.stop)
        if cut is not None:
            del cr.output[cut:]     # inclusive of the stop sequence itself
        cr.finished_at = self.clock()
        payload = {
            "crid": cr.crid,
            "tenant": cr.tenant.name,
            "tokens": list(cr.output),
            "prompt_len": int(len(cr.prompt)),
            "ttft_s": (cr.first_token_at - cr.submitted_at
                       if cr.first_token_at else 0.0),
            "e2e_s": cr.finished_at - cr.submitted_at,
            "replica": cr.replica,
            "preemptions": cr.preemptions,
            "requeues": cr.requeues,
        }
        if cr.error:
            payload["error"] = cr.error
        with self._lock:
            self._results[cr.crid] = payload

    # -- results / introspection ----------------------------------------------
    def result(self, crid: int) -> Dict[str, Any]:
        with self._lock:
            payload = self._results.get(crid)
        if payload is None:
            raise RuntimeError(
                f"request {crid} is still queued/decoding; drive "
                "step()/run() to completion before fetching its result")
        return payload

    def request(self, crid: int) -> ClusterRequest:
        for cr in self._pending:
            if cr.crid == crid:
                return cr
        if crid in self._inflight:
            return self._inflight[crid]
        raise KeyError(crid)

    def route_plan(self):
        """The router's per-request decision log as an ``OffloadPlan``."""
        return self.router.plan()

    def busy_seconds(self) -> Dict[str, float]:
        """Per-endpoint busy time this process spent *simulating* parallel
        endpoints serially.  ``wall_parallel ~= wall_serial - sum(values)
        + max(values)`` is the benchmark's scaling estimator."""
        with self._lock:
            out = {f"r{i}": s for i, s in enumerate(self.busy_s)}
            if self.prefill is not None:
                out["prefill"] = self.prefill_busy_s
        return out

    def stats(self) -> Dict[str, Any]:
        # Snapshot guarded counters first; rep.stats() takes per-engine
        # locks, so it runs outside ours (ServeCluster._lock stays a leaf).
        with self._lock:
            busy = list(self.busy_s)
            prefill_busy = self.prefill_busy_s
            completed = len(self._results)
            qos = {
                "preemptions": self.preemptions,
                "death_requeues": self.death_requeues,
                "rate_limited": self.rate_limited,
                "replica_deaths": self.deaths,
            }
        reps = [dict(rep.stats(), alive=self.alive[i],
                     busy_s=round(busy[i], 4),
                     model=self._model_of[i])
                for i, rep in enumerate(self.replicas)]
        # Cluster-level speculative aggregate: sum the speculating
        # replicas' proposal/acceptance counters so operators read one
        # acceptance rate, not N.
        specs = [r["speculative"] for r in reps if "speculative" in r]
        spec = None
        if specs:
            prop = sum(s["proposed"] for s in specs)
            acc = sum(s["accepted"] for s in specs)
            spec = {"replicas": len(specs), "proposed": prop,
                    "accepted": acc,
                    "acceptance_rate": round(acc / prop, 4) if prop else 0.0}
        return {
            "replicas": reps,
            "speculative": spec,
            "pending": len(self._pending),
            "inflight": len(self._inflight),
            "completed": completed,
            "qos": qos,
            "router": {
                "picks": dict(self.router.planner.picks),
                "rejections": self.router.planner.rejections,
            },
            "prefill_endpoint": (
                {"pool": self.prefill.pool.stats(),
                 "busy_s": round(prefill_busy, 4)}
                if self.prefill is not None else None),
        }

    def close(self) -> None:
        if self._closed.is_set():
            return
        self._closed.set()
        for cr in list(self._inflight.values()) + self._pending:
            if not cr.done:
                cr.error = "cluster closed before completion"
                self._finish(cr)
        self._pending.clear()
        self._inflight.clear()
        for rep in self.replicas:
            rep.close()
        for worker in self._prefills.values():
            worker.close()
        self.executor.drain()
        self.executor.shutdown(drain=False)

    # -- batch convenience ----------------------------------------------------
    def generate(self, prompts: List[np.ndarray], max_new_tokens: int,
                 tenant: str = "default",
                 model: str = "default") -> Dict[int, List[int]]:
        """Submit a list of prompts and drive to completion.  Returns
        {index -> tokens}."""
        crids = []
        for p in prompts:
            while True:
                try:
                    crids.append(self.submit(p, max_new_tokens, tenant,
                                             model=model))
                    break
                except QueueFull:
                    self.step()
        self.run()
        return {i: self.result(crid)["tokens"]
                for i, crid in enumerate(crids)}


def rep_req_done(rep: PagedEngine, rid: int) -> bool:
    try:
        return rep.request(rid).done
    except KeyError:
        return False


def _stop_hit_index(tokens: Sequence[int], stop) -> Optional[int]:
    """Index one past the end of the *earliest* completed stop sequence in
    ``tokens``, or None.  Cluster-level rescan: a stop sequence can straddle
    a preemption/requeue boundary, where neither admission round's engine
    sees the whole thing."""
    best = None
    for seq in stop:
        n = len(seq)
        for i in range(n, len(tokens) + 1):
            if tuple(tokens[i - n:i]) == seq:
                best = i if best is None else min(best, i)
                break
    return best
