"""Decode-state backends: one contract, two cache disciplines.

``PagedEngine``/``DisaggregatedEngine``/``ServeCluster`` used to hardcode the
block-table KV discipline — alloc/release pages, chain-key prefix lookup,
spill/fault against the ``ColdTier``, ``KVHandoff`` export/import, affinity
probes — which silently restricted every distributed serving feature to
all-global-attention decoder-only archs.  This module extracts that contract
into an explicit ``CacheBackend`` interface and adds a second implementation,
so the same engines cover every arch in ``configs/``:

  * ``PagedKVBackend`` — today's paged, tiered KV-cache unchanged:
    ``KVBlockPool`` pages + block tables, chain-key CoW prefix sharing,
    LRU spill to the ``ColdTier``, per-page ``KVHandoff`` blobs.
  * ``SnapshotBackend`` — recurrent/SWA/enc-dec archs, whose decode state is
    a *fixed-size* tree per slot (rwkv6 ``S``/``x_prev``, rglru
    ``h``/``conv``, sliding-window ring caches) with no page structure to
    share.  The reuse unit is a **snapshot**: the whole batch-1 solo state
    captured at a prompt boundary (``read_decode_slot``), kept in a small
    LRU ``SnapshotPool``, spilled whole to the ``ColdTier`` under pressure,
    and restored as the donor of a suffix-only resume prefill
    (``make_resume_prefill_step``).  Handoffs ship the same O(1) blob
    (``SnapshotHandoff``) instead of per-page K/V.

The backend owns the cache substrate and the fused device programs; the
engine keeps the admission plane (slots, queue, mirrors' host shadow,
handoff-store plumbing, results).  The two halves talk through the engine
back-reference set by ``bind`` — backends read/write ``engine.states``,
``engine._key``, ``engine._mirrors`` exactly where the engine methods they
replaced did.

Why snapshots are exact: the cold admission path runs the *same* fused dense
admit program as ``ContinuousEngine``, and the warm path restores a donor
state byte-identical to the one the original prefill produced at that
boundary, then prefills only the suffix at offset positions — for recurrent
mixers the carried-state prefill is the same recurrence split at the
boundary, for ring caches ``cache_write`` scatters at ``positions % C`` so a
resumed prefill lands exactly where a cold prefill would have.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.config.model import ModelConfig
from repro.config.run import ServeConfig
from repro.runtime.locks import make_lock
from repro.models.transformer import (
    decode_state_nbytes, init_decode_state, init_paged_decode_state,
    supports_paging)
from repro.serve import programs
from repro.serve.kvpool import (
    SCRATCH_PAGE, ColdTier, KVBlockPool, KVHandoff, chain_keys)
from repro.serve.scheduler import Request


def place_beside(tree: Any, params: Any) -> Any:
    """``tree`` on the device(s) that hold ``params``: a cluster replica
    keeps its decode state on its own device, next to its weights."""
    leaf = jax.tree.leaves(params)[0]
    return jax.device_put(tree, getattr(leaf, "sharding", None))


def make_backend(cfg: ModelConfig, scfg: ServeConfig) -> "CacheBackend":
    """Pick the decode-state discipline for an arch: block-table KV paging
    when the arch supports it, the snapshot pool otherwise.  This is the
    selector that lets ``EngineMode.paged``/``disaggregated``/``cluster``
    serve recurrent/SWA archs instead of rejecting them."""
    if supports_paging(cfg):
        return PagedKVBackend(cfg, scfg)
    return SnapshotBackend(cfg, scfg)


class CacheBackend:
    """The decode-state management contract behind the serve engines.

    One instance per engine; ``bind(engine)`` wires the back-reference
    before ``build_device_plane`` compiles the fused programs and allocates
    ``engine.states``.  All device-touching methods run on the engine loop
    thread; the shared hit counters are guarded by ``engine._lock`` because
    ``stats()`` may race the loop."""

    kind: str = ""

    def __init__(self, cfg: ModelConfig, scfg: ServeConfig):
        self.cfg, self.scfg = cfg, scfg
        self.engine: Any = None
        self._prompt_tokens = 0       # guarded-by: engine._lock
        self._hit_tokens = 0          # guarded-by: engine._lock
        self._rolled_back = 0         # guarded-by: engine._lock

    def bind(self, engine) -> None:
        self.engine = engine

    # -- device plane ----------------------------------------------------------
    def build_device_plane(self) -> None:
        """Compile/fetch the fused programs and set ``engine.states``."""
        raise NotImplementedError

    def decode_step(self) -> np.ndarray:
        """One batched decode dispatch; returns the (B,) sampled tokens."""
        raise NotImplementedError

    def verify_step(self, drafts: jax.Array,
                    caps: jax.Array) -> Tuple[np.ndarray, np.ndarray]:
        """Speculative verify dispatch (the engine's ``_verify_device``):
        score a (B, k) draft chunk in one batched forward and return the
        host (B, k+1) emitted chunk plus (B,) accept lengths, rewinding
        this backend's write bookkeeping past rejected entries.  Only built
        when the owning engine speculates."""
        raise NotImplementedError

    def _count_rollback(self, acc: np.ndarray, k: int) -> None:
        """Accumulate rejected-suffix tokens across live rows (stochastic
        rows reject all k by construction)."""
        rolled = sum(k - int(acc[r.slot])
                     for r in self.engine.slots.active())
        with self.engine._lock:
            self._rolled_back += rolled

    # -- admission -------------------------------------------------------------
    def admit(self, req: Request) -> Optional[int]:
        """Local admission: reuse what the cache holds, prefill the rest,
        splice into the batch.  Returns the first sampled token, or None
        when admission must defer for resources."""
        raise NotImplementedError

    def release(self, req: Optional[Request], slot: int) -> None:
        """Give back whatever the backend reserved for a slot."""
        raise NotImplementedError

    def can_admit_resources(self, prompt_len: int, max_new_tokens: int,
                            hit_units: int = 0) -> bool:
        """Whether cache resources (not slots) allow an admission now."""
        raise NotImplementedError

    # -- handoff (disaggregated / cluster) -------------------------------------
    def export_handoff(self, req: Request, rid: int, max_new_tokens: int,
                       first_token: int):
        """Package a freshly-admitted request's decode state for transport
        (the prefill endpoint's half)."""
        raise NotImplementedError

    def import_handoff(self, req: Request, h) -> Optional[int]:
        """Splice a transported decode state into the batch (the decode
        endpoint's half).  Returns the first token, or None to defer;
        raises ValueError on a stale/malformed blob."""
        raise NotImplementedError

    def handoff_bytes_for(self, prompt_len: int) -> float:
        """Estimated handoff blob size — the router's link-cost input.
        Paged: pages x page_bytes (scales with the prompt); snapshot: one
        O(1) state blob regardless of length."""
        raise NotImplementedError

    # -- affinity probes (cluster router) --------------------------------------
    def prepare_probe(self, prompt: np.ndarray):
        """Per-request probe handle, computed once and probed against every
        replica of a model group (chain keys for paged, the prompt itself
        for snapshots)."""
        raise NotImplementedError

    def probe(self, handle) -> Tuple[int, int]:
        """Read-only affinity: ``(hit_units, hit_tokens)`` this backend
        already holds for the handle, without touching LRU order."""
        raise NotImplementedError

    def available_units(self) -> int:
        """Allocation units obtainable now (pages / snapshot slots)."""
        raise NotImplementedError

    def units_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        """Units a full admission would consume (0 when admission never
        contends for cache units, as with the snapshot pool)."""
        raise NotImplementedError

    # -- introspection ---------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        raise NotImplementedError

    def _count_hit(self, prompt_len: int, hit_tokens: int) -> None:
        with self.engine._lock:
            self._prompt_tokens += prompt_len
            self._hit_tokens += hit_tokens

    def _hit_rate(self) -> float:
        with self.engine._lock:
            hit, prompt = self._hit_tokens, self._prompt_tokens
        return hit / prompt if prompt else 0.0


# ----------------------------------------------------------------------------
# Paged KV backend (the extracted PagedEngine substrate, unchanged behavior)
# ----------------------------------------------------------------------------

class PagedKVBackend(CacheBackend):
    """Block-table KV paging: refcounted pages, chain-key CoW prefix reuse,
    tiered spill/fault, per-page handoffs.  See ``serve.kvpool`` for the
    host-side allocator; this class is the engine-facing half that used to
    live on ``PagedEngine`` itself."""

    kind = "paged"

    def __init__(self, cfg: ModelConfig, scfg: ServeConfig):
        super().__init__(cfg, scfg)
        if scfg.max_seq_len % scfg.page_size:
            raise ValueError(f"max_seq_len ({scfg.max_seq_len}) must be a "
                             f"multiple of page_size ({scfg.page_size})")
        from repro.models.attention import KV_QUANT_MODES, POOL_PAGE_MULTIPLE
        if scfg.kv_quant not in KV_QUANT_MODES:
            raise ValueError(f"kv_quant={scfg.kv_quant!r}: expected one of "
                             f"{KV_QUANT_MODES}")
        self.page_size = scfg.page_size
        self.pages_per_seq = scfg.max_seq_len // scfg.page_size
        # Default: full residency for every slot plus the scratch page,
        # rounded up to the pool's tile-aligned size.
        m = POOL_PAGE_MULTIPLE
        num_pages = scfg.num_pages or \
            -(-(scfg.max_batch * self.pages_per_seq + 1) // m) * m
        if num_pages < self.pages_per_seq + 1:
            raise ValueError(
                f"num_pages ({num_pages}) must cover one full sequence "
                f"({self.pages_per_seq}) plus the scratch page")
        self.pool = KVBlockPool(num_pages, scfg.page_size,
                                prefix_cache=scfg.prefix_cache)
        self.cold = ColdTier(scfg.cold_pages) if scfg.cold_pages > 0 else None
        self._table = np.full((scfg.max_batch, self.pages_per_seq),
                              SCRATCH_PAGE, np.int32)
        self._page_bytes: Optional[float] = None

    def build_device_plane(self) -> None:
        eng = self.engine
        self._admit_prog = programs.paged_admit_program(
            self.cfg, eng.policy, self.scfg.max_seq_len)
        self._decode_prog = programs.paged_decode_program(self.cfg, eng.policy)
        # Page movers for the tiered plane: slice a page out for spilling
        # (fresh buffers, safe to stage on the sidecar) / write a faulted
        # page back in place.
        self._read_page_prog = programs.read_page_program()
        self._read_pages_prog = programs.read_pages_program()
        self._write_page_prog = programs.write_page_program()
        if eng._draft is not None:
            self._verify_prog = programs.paged_verify_program(
                self.cfg, eng.policy, self.scfg.draft_k)
        eng.states = place_beside(
            init_paged_decode_state(self.cfg, self.pool.num_pages,
                                    self.page_size,
                                    kv_quant=self.scfg.kv_quant),
            eng.params)

    # -- tiered-memory plane ---------------------------------------------------
    def _spill(self, page: int, chain: bytes) -> None:
        """Evict a cached prefix page: slice its K/V out of every pool into
        the cold tier, then let the sidecar stage the slices to host memory
        (``ColdTier.replace``).  The slice is enqueued on the device stream
        *before* any later program can reuse the page, so the handoff is
        race-free; the decode loop never blocks on the device->host copy
        (advice #2), and a failed/dropped staging task just leaves the
        device slices in place — never a dangling entry."""
        if self.cold is None:
            return
        eng = self.engine
        blob = self._read_page_prog(eng.states, jnp.asarray(page, jnp.int32))
        self.cold.put(chain, blob)
        leaves, treedef = jax.tree.flatten(blob)
        eng.executor.submit(
            f"kv.spill/{chain.hex()[:8]}",
            functools.partial(self._cold_stage, chain, treedef), *leaves)

    def _cold_stage(self, chain: bytes, treedef, *host_leaves) -> None:
        # Runs on the sidecar after jax.device_get of every leaf: the cold
        # entry becomes true host-endpoint memory.
        self.cold.replace(chain,
                          jax.tree.unflatten(treedef, list(host_leaves)))

    def _fault_in(self, chain: bytes) -> Optional[int]:
        """Bring a cold prefix page back into the pool.  Returns the hot
        page (ref'd for the caller) or None on a miss / full pool."""
        if self.cold is None or not self.cold.contains(chain):
            return None
        blob = self.cold.take(chain)
        if blob is None:
            return None
        with TraceAnnotation("kv.alloc"):
            got = self.pool.alloc(1, evict_cb=self._spill)
        if got is None:
            self.cold.put(chain, blob)          # no room: stay cold
            return None
        page = got[0]
        eng = self.engine
        eng.states = self._write_page_prog(
            eng.states, jnp.asarray(page, jnp.int32), blob)
        self.pool.register(chain, page)
        self.pool.note_fault()
        return page

    # -- admission -------------------------------------------------------------
    def _match_prefix(self, req: Request, chains: List[bytes]) -> List[int]:
        """Longest chain of *full* prompt pages already resident (hot hit)
        or spilled (cold fault-in).  Always leaves >= 1 token to prefill so
        the admit program has a real last-token logit to sample from."""
        limit = (len(req.prompt) - 1) // self.page_size
        pages: List[int] = []
        with TraceAnnotation("kv.match"):
            for chain in chains[:limit]:
                # Atomic hit + pin: a separate lookup()/ref() pair would let
                # a concurrent alloc() evict the page in between and hand it
                # to another slot (the late ref would pin foreign KV).
                page = self.pool.lookup_and_ref(chain)
                if page is not None:
                    pages.append(page)
                    continue
                page = self._fault_in(chain)    # alloc() already ref'd it
                if page is None:
                    break
                pages.append(page)
        return pages

    def prepare_probe(self, prompt: np.ndarray):
        return chain_keys(np.asarray(prompt, np.int32), self.page_size)

    def probe(self, handle) -> Tuple[int, int]:
        """Leading chain keys resident here (hot index or cold tier),
        *without* mutating LRU order or hit counters — the cluster router's
        affinity probe."""
        n = 0
        for chain in (handle or []):
            if self.pool.probe(chain) or \
                    (self.cold is not None and self.cold.contains(chain)):
                n += 1
            else:
                break
        return n, n * self.page_size

    def available_units(self) -> int:
        return self.pool.available()

    def units_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        return -(-(prompt_len + max_new_tokens) // self.page_size)

    def can_admit_resources(self, prompt_len: int, max_new_tokens: int,
                            hit_units: int = 0) -> bool:
        need = self.units_needed(prompt_len, max_new_tokens)
        return self.pool.available() >= max(0, need - hit_units)

    def _register_prefix(self, req: Request, chains: List[bytes],
                         pages: List[int], n_hit: int) -> None:
        """Index the freshly-prefilled full prompt pages for future sharing."""
        for i in range(n_hit, len(req.prompt) // self.page_size):
            self.pool.register(chains[i], pages[i])

    def _reserve_pages(self, req: Request, chains: List[bytes],
                       need: int) -> Optional[Tuple[List[int], int]]:
        """Shared admission half: prefix-match (hot hit or cold fault-in),
        allocate the remainder, update hit accounting.  Returns
        ``(pages, n_hit)``, or None when admission must defer — hit refs are
        rolled back so decode can free pages in the meantime."""
        hit_pages = self._match_prefix(req, chains)
        n_hit = len(hit_pages)
        with TraceAnnotation("kv.alloc"):
            new_pages = self.pool.alloc(need - n_hit, evict_cb=self._spill)
        if new_pages is None:                   # pool exhausted by live slots:
            for p in hit_pages:                 # defer; decode will free pages
                self.pool.unref(p)
            return None
        pages = hit_pages + new_pages
        req.pages = pages
        req.prefix_hit_tokens = n_hit * self.page_size
        self._count_hit(len(req.prompt), n_hit * self.page_size)
        return pages, n_hit

    def _install_slot(self, req: Request, pages: List[int]) -> int:
        """Acquire a decode slot and point its block-table row at pages."""
        slot = self.engine.slots.acquire(req)
        row = np.full(self.pages_per_seq, SCRATCH_PAGE, np.int32)
        row[:len(pages)] = pages
        self._table[slot] = row
        return slot

    def admit(self, req: Request) -> Optional[int]:
        """Local paged admission: prefix-match, allocate, bucket-prefill the
        suffix through the fused paged admit program."""
        eng = self.engine
        pg, M = self.page_size, self.pages_per_seq
        L = len(req.prompt)
        need = -(-(L + req.max_new_tokens) // pg)
        chains = (chain_keys(req.prompt, pg) if self.scfg.prefix_cache
                  else [])
        got = self._reserve_pages(req, chains, need)
        if got is None:
            return None
        pages, n_hit = got
        hit_len = n_hit * pg

        slot = self._install_slot(req, pages)
        row = self._table[slot]
        # Hit pages scatter to the scratch page (never rewrite shared pages).
        assign = np.full(M, SCRATCH_PAGE, np.int32)
        assign[n_hit:len(pages)] = pages[n_hit:]

        suffix = req.prompt[hit_len:]
        # Clamp the suffix bucket so hit_len + S never wraps the solo cache.
        S = max(min(eng.scheduler.bucket_for(len(suffix)),
                    self.scfg.max_seq_len - hit_len), len(suffix), 1)
        toks = np.zeros((1, S), np.int32)
        toks[0, :len(suffix)] = suffix
        positions = (hit_len + np.arange(S, dtype=np.int32))[None, :]
        sp = req.sampling
        batch = {"tokens": jnp.asarray(toks),
                 "positions": jnp.asarray(positions),
                 "length": jnp.asarray(L, jnp.int32),
                 "hit_len": jnp.asarray(hit_len, jnp.int32),
                 "table": jnp.asarray(row),
                 "assign": jnp.asarray(assign),
                 "slot": jnp.asarray(slot, jnp.int32),
                 "temp": jnp.asarray(sp.temperature, jnp.float32),
                 "top_k": jnp.asarray(sp.top_k, jnp.int32),
                 "top_p": jnp.asarray(sp.top_p, jnp.float32)}
        eng.states, tok, eng._key, eng._mirrors = self._admit_prog(
            eng.params, eng.states, batch, eng._key, eng._mirrors)
        if self.scfg.prefix_cache:
            self._register_prefix(req, chains, pages, n_hit)
        with TraceAnnotation("serve.admit.wait"):
            return int(tok[0])

    # -- handoff ---------------------------------------------------------------
    def export_handoff(self, req: Request, rid: int, max_new_tokens: int,
                       first_token: int) -> KVHandoff:
        """Slice the prompt's pages out of the pool as transportable blobs
        (the ``PrefillWorker`` export half)."""
        eng = self.engine
        pg = self.page_size
        n_prompt = -(-len(req.prompt) // pg)
        # One stacked gather + one device->host transfer for every prompt
        # page (a per-page device_get loop here is a host sync per page on
        # the prefill hot path — the HOST_SYNC_LOOP analysis rule pins this).
        idx = jnp.asarray(req.pages[:n_prompt], jnp.int32)
        stacked = jax.device_get(self._read_pages_prog(eng.states, idx))
        blobs = [jax.tree.map(lambda a, i=i: a[i], stacked)
                 for i in range(n_prompt)]
        return KVHandoff(
            rid=rid, prompt_len=len(req.prompt),
            max_new_tokens=max_new_tokens, first_token=first_token,
            page_blobs=blobs, chains=chain_keys(req.prompt, pg),
            sampling=dataclasses.asdict(req.sampling))

    def import_handoff(self, req: Request, h) -> Optional[int]:
        """Fault a handoff's pages into this engine's pool and splice the
        request into the decode batch — the decode half of the narrow
        interface.  Pages the local prefix index already holds (hot or
        cold) are reused instead of imported; imported full prompt pages are
        registered for future sharing, so both endpoints keep their own
        working prefix caches."""
        eng = self.engine
        pg = self.page_size
        # A blob popped at this request's key must actually be *this*
        # request's: a colliding rid against a persistent handoff store
        # (relaunch over the same BlobEndpoint directories) would otherwise
        # splice another prompt's KV pages into the batch silently.
        if not isinstance(h, KVHandoff):
            raise ValueError(
                f"stale/malformed handoff at kv/{req.rid}: expected a "
                f"KVHandoff blob, got {type(h).__name__}")
        L = h.prompt_len
        n_prompt = h.num_prompt_pages(pg)
        if (h.rid != req.rid or L != len(req.prompt)
                or h.max_new_tokens != req.max_new_tokens
                or n_prompt != len(h.page_blobs)):
            raise ValueError(
                f"stale/malformed handoff at kv/{req.rid}: blob carries "
                f"rid={h.rid} prompt_len={L} max_new={h.max_new_tokens} "
                f"({len(h.page_blobs)} page blobs, expected {n_prompt})")
        need = -(-(L + req.max_new_tokens) // pg)
        chains = [bytes(c) for c in h.chains] if self.scfg.prefix_cache \
            else []
        got = self._reserve_pages(req, chains, need)
        if got is None:                     # pool exhausted: defer
            return None
        pages, n_hit = got

        for i in range(n_hit, n_prompt):            # fault transferred pages
            eng.states = self._write_page_prog(
                eng.states, jnp.asarray(pages[i], jnp.int32),
                h.page_blobs[i])
        slot = self._install_slot(req, pages)
        # The blob's sampling state is the wire-format truth (a cross-host
        # decode endpoint has no Request object to fall back on).
        sp = h.sampling
        m = eng._mirrors
        eng._mirrors = {
            "tok": m["tok"].at[slot].set(h.first_token),
            "pos": m["pos"].at[slot].set(L),
            "temp": m["temp"].at[slot].set(float(sp["temperature"])),
            "top_k": m["top_k"].at[slot].set(int(sp["top_k"])),
            "top_p": m["top_p"].at[slot].set(float(sp["top_p"])),
        }
        if self.scfg.prefix_cache:
            self._register_prefix(req, chains, pages, n_hit)
        return int(h.first_token)

    def handoff_bytes_for(self, prompt_len: int) -> float:
        if self._page_bytes is None:
            self._page_bytes = (self.engine.cache_bytes()
                                / max(1, self.pool.num_pages))
        return -(-prompt_len // self.page_size) * self._page_bytes

    # -- decode / release ------------------------------------------------------
    def decode_step(self) -> np.ndarray:
        eng = self.engine
        eng.states, toks_dev, eng._key, eng._mirrors = self._decode_prog(
            eng.params, eng.states, eng._key, eng._mirrors,
            jnp.asarray(self._table))
        with TraceAnnotation("serve.decode.wait"):
            return np.asarray(toks_dev)

    def verify_step(self, drafts: jax.Array,
                    caps: jax.Array) -> Tuple[np.ndarray, np.ndarray]:
        """Block-table verify: the chunk scatters through each row's own
        pages (int8 pools re-cut per-entry scales on every write, so a
        rejected entry overwritten by the next chunk gets a fresh scale —
        no stale quantization survives a rollback).  Pages are reserved for
        the full decode horizon at admission, so the write-position rewind
        is pure bookkeeping: rewound entries stay inside the row's own
        reservation, are causally masked until rewritten, and free with the
        request at release — never handed to another slot mid-flight."""
        eng = self.engine
        eng.states, out, acc, eng._key, eng._mirrors = self._verify_prog(
            eng.params, eng.states, eng._key, eng._mirrors,
            jnp.asarray(self._table), drafts, caps)
        with TraceAnnotation("serve.decode.wait"):
            out, acc = np.asarray(out), np.asarray(acc)
        self._count_rollback(acc, int(drafts.shape[1]))
        return out, acc

    def release(self, req: Optional[Request], slot: int) -> None:
        if req is not None:
            for p in req.pages:
                self.pool.unref(p)      # shared pages stay; private ones free
            req.pages = []
        # Point the retired row at the scratch page: its mirrors keep
        # advancing through the fixed-shape decode, and those garbage writes
        # must never land in a page that gets reallocated.
        self._table[slot] = SCRATCH_PAGE

    def stats(self) -> Dict[str, Any]:
        s = {
            "kv_pool": self.pool.stats(),
            "cold_pages": len(self.cold) if self.cold is not None else 0,
            "prefix_hit_rate": self._hit_rate(),
        }
        if self.engine is not None and self.engine._draft is not None:
            with self.engine._lock:
                s["spec_rolled_back_tokens"] = self._rolled_back
        return s


# ----------------------------------------------------------------------------
# Snapshot backend (recurrent / SWA / enc-dec archs)
# ----------------------------------------------------------------------------

def snap_key(tokens: np.ndarray) -> bytes:
    """Content key of a whole token prefix (the snapshot analogue of
    ``kvpool.chain_keys``: one key per registered boundary, committing to
    every token up to it)."""
    tokens = np.ascontiguousarray(np.asarray(tokens, np.int32))
    return hashlib.blake2b(tokens.tobytes(), digest_size=16).digest()


@dataclasses.dataclass
class SnapshotHandoff:
    """Wire format between a prefill and a decode endpoint for snapshot
    archs: one O(1) state blob (host-numpy tree of the batch-1 solo decode
    state at position ``prompt_len``) instead of ``KVHandoff``'s per-page
    K/V list.  Same envelope fields so both blob kinds travel the same
    ``ShardedStore`` keys and validation path."""
    rid: int
    prompt_len: int
    max_new_tokens: int
    first_token: int
    state: Any                       # host-numpy solo decode-state tree
    sampling: Dict[str, Any]         # temperature / top_k / top_p / eos_id


class SnapshotPool:
    """Fixed-capacity LRU pool of decode-state snapshots, keyed by
    ``snap_key`` of the token prefix they were captured at.

    Entries are ``key -> (boundary_length, device state tree)``.  Snapshots
    are shared read-only — restore copies the donor into the batch (the
    resume program never donates it) — so eviction never invalidates a live
    slot; the evict callback spills the whole tree to the cold tier."""

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("snapshot pool needs capacity >= 1")
        self.capacity = capacity
        # The engine loop registers/restores snapshots while router threads
        # probe (contains/lengths) and stats() readers race the loop.  The
        # evict callback runs under this lock and must not re-enter the pool.
        self._lock = make_lock("SnapshotPool._lock")
        self._store: "OrderedDict[bytes, Tuple[int, Any]]" = OrderedDict()  # guarded-by: _lock
        self.hits = 0        # guarded-by: _lock
        self.lookups = 0     # guarded-by: _lock
        self.evictions = 0   # guarded-by: _lock

    def __len__(self) -> int:
        with self._lock:
            return len(self._store)

    def lengths(self) -> List[int]:
        """Distinct boundary lengths currently resident."""
        with self._lock:
            return sorted({ln for ln, _ in self._store.values()},
                          reverse=True)

    def get(self, key: bytes) -> Optional[Any]:
        """Hot hit (LRU touch) or None."""
        with self._lock:
            self.lookups += 1
            ent = self._store.get(key)
            if ent is None:
                return None
            self.hits += 1
            self._store.move_to_end(key)
            return ent[1]

    def contains(self, key: bytes) -> bool:
        """Read-only probe: no LRU touch, no counters (router affinity)."""
        with self._lock:
            return key in self._store

    def put(self, key: bytes, length: int, state: Any,
            evict_cb=None) -> None:
        """Register a snapshot (newest wins on duplicate keys), evicting the
        LRU entry over capacity through ``evict_cb(key, length, state)``."""
        with self._lock:
            self._store[key] = (length, state)
            self._store.move_to_end(key)
            while len(self._store) > self.capacity:
                k, (ln, st) = self._store.popitem(last=False)
                if evict_cb is not None:
                    evict_cb(k, ln, st)
                self.evictions += 1

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "slots": self.capacity,
                "resident": len(self._store),
                "hits": self.hits,
                "lookups": self.lookups,
                "evictions": self.evictions,
            }


class SnapshotBackend(CacheBackend):
    """Decode-state management for archs without pageable KV: recurrent
    mixers (rwkv6, rglru), sliding-window ring caches, enc-dec / frontend
    archs.  Per-slot state is a fixed-size tree, so the reuse/spill/handoff
    unit is the whole batch-1 snapshot:

      * **Cold admission** runs the *same* fused dense admit program as
        ``ContinuousEngine`` (bit-identical outputs), then captures the
        spliced slot as a full-prompt snapshot (``read_decode_slot``).
      * **Warm admission** finds the longest registered prefix boundary of
        the prompt (hot pool first, cold-tier fault-in second), restores
        that snapshot as the donor and prefills only the suffix at offset
        positions (``resume_admit_program``) — the recurrent analogue of the
        paged prefix hit, and exact because the donor *is* the state the
        original prefill held at that boundary.
      * **Spill/fault** move whole snapshots between the hot pool and the
        ``ColdTier`` (sidecar-staged to host numpy, like KV pages).
      * **Handoff** ships one ``SnapshotHandoff`` blob; import splices it
        with ``insert_decode_slot`` and never defers (no page contention).

    Prefix reuse is disabled for enc-dec / frontend archs (their state
    depends on non-token inputs the content key cannot commit to) — they
    still get continuous batching, handoffs and clustering through the cold
    path."""

    kind = "snapshot"

    def __init__(self, cfg: ModelConfig, scfg: ServeConfig):
        super().__init__(cfg, scfg)
        if scfg.kv_quant != "none":
            raise ValueError(
                f"kv_quant={scfg.kv_quant!r}: snapshot-backend archs "
                f"({cfg.arch_id}) have no paged KV to quantize — their "
                "decode state stays f32; serve them with kv_quant='none'")
        self.pool = SnapshotPool(max(1, scfg.snapshot_slots))
        self.cold = ColdTier(scfg.cold_pages) if scfg.cold_pages > 0 else None
        # Cold-boundary bookkeeping and tier counters are mutated on the
        # engine loop (spill/fault) and read from router threads
        # (_candidate_lengths via probe) and stats() — the engine's lock
        # guards them, like the hit counters in CacheBackend.
        self._cold_lens: Dict[bytes, int] = {}   # guarded-by: engine._lock
        self._reuse = (scfg.prefix_cache and cfg.frontend == "none"
                       and not cfg.is_encoder_decoder)
        self._state_bytes: Optional[int] = None
        self.faults = 0      # guarded-by: engine._lock
        self.spills = 0      # guarded-by: engine._lock

    def build_device_plane(self) -> None:
        eng = self.engine
        self._admit_prog = programs.admit_program(
            self.cfg, eng.policy, self.scfg.max_seq_len)
        self._resume_prog = programs.resume_admit_program(self.cfg, eng.policy)
        self._decode_prog = programs.decode_program(self.cfg, eng.policy)
        self._read_slot_prog = programs.read_slot_program()
        self._insert_slot_prog = programs.insert_slot_program()
        if eng._draft is not None:
            self._verify_prog = programs.snapshot_verify_program(
                self.cfg, eng.policy, self.scfg.draft_k)
        eng.states = place_beside(
            init_decode_state(self.cfg, self.scfg.max_batch,
                              capacity=self.scfg.max_seq_len),
            eng.params)

    # -- tiered-memory plane ---------------------------------------------------
    def _spill(self, key: bytes, length: int, state: Any) -> None:
        """Evicted snapshot -> cold tier, sidecar-staged to host memory
        (same insert-then-replace pattern as the paged spill, so a fault
        racing the staging always finds the blob)."""
        if self.cold is None:
            return
        self.cold.put(key, state)
        with self.engine._lock:
            self._cold_lens[key] = length
            self.spills += 1
        leaves, treedef = jax.tree.flatten(state)
        self.engine.executor.submit(
            f"snap.spill/{key.hex()[:8]}",
            functools.partial(self._cold_stage, key, treedef), *leaves)

    def _cold_stage(self, key: bytes, treedef, *host_leaves) -> None:
        self.cold.replace(key,
                          jax.tree.unflatten(treedef, list(host_leaves)))

    def _fault_in(self, key: bytes, length: int) -> Optional[Any]:
        """Bring a cold snapshot back into the hot pool; None on a miss."""
        if self.cold is None:
            return None
        blob = self.cold.take(key)
        if blob is None:
            return None
        with self.engine._lock:
            self._cold_lens.pop(key, None)
            self.faults += 1
        state = jax.tree.map(jnp.asarray, blob)
        # Outside engine._lock: put() may evict -> _spill -> engine._lock
        # (re-entering here would self-deadlock the non-reentrant lock).
        self.pool.put(key, length, state, evict_cb=self._spill)
        return state

    # -- prefix matching -------------------------------------------------------
    def _candidate_lengths(self) -> List[int]:
        """Distinct registered boundary lengths, longest first (hot pool +
        cold tier; cold bookkeeping pruned lazily as the tier drops LRU
        entries)."""
        lens = set(self.pool.lengths())
        if self.cold is not None:
            # Snapshot the bookkeeping, probe the cold tier *outside* the
            # engine lock (ColdTier has its own), then prune under it.
            with self.engine._lock:
                items = list(self._cold_lens.items())
            stale = [k for k, _ln in items if not self.cold.contains(k)]
            with self.engine._lock:
                for k in stale:
                    self._cold_lens.pop(k, None)
                lens.update(self._cold_lens.values())
        return sorted(lens, reverse=True)

    def _match(self, prompt: np.ndarray) -> Tuple[int, Optional[Any]]:
        """Longest registered boundary that is a proper prefix of the
        prompt (>= 1 token always left to prefill, so the resume program
        has a real last-token logit to sample from).  Returns
        ``(hit_len, donor_state)`` or ``(0, None)``."""
        L = len(prompt)
        for ln in self._candidate_lengths():
            if ln > L - 1:
                continue
            key = snap_key(prompt[:ln])
            state = self.pool.get(key)
            if state is None:
                state = self._fault_in(key, ln)
            if state is not None:
                return ln, state
        return 0, None

    def prepare_probe(self, prompt: np.ndarray):
        return np.asarray(prompt, np.int32)

    def probe(self, handle) -> Tuple[int, int]:
        if not self._reuse or handle is None:
            return 0, 0
        L = len(handle)
        for ln in self._candidate_lengths():
            if ln > L - 1:
                continue
            key = snap_key(handle[:ln])
            if self.pool.contains(key) or \
                    (self.cold is not None and self.cold.contains(key)):
                return 1, ln
        return 0, 0

    def available_units(self) -> int:
        # Every resident snapshot is evictable (restore copies, never
        # references), so the whole pool is always obtainable.
        return self.pool.capacity

    def units_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        return 0            # slot state is pre-allocated; nothing to reserve

    def can_admit_resources(self, prompt_len: int, max_new_tokens: int,
                            hit_units: int = 0) -> bool:
        return True         # the slot table is the only contended resource

    # -- admission -------------------------------------------------------------
    def admit(self, req: Request) -> Optional[int]:
        eng = self.engine
        L = len(req.prompt)
        reusable = self._reuse and req.frontend_embeds is None
        hit_len, donor = self._match(req.prompt) if reusable else (0, None)
        if donor is None:
            tok0, solo = self._admit_cold(req, register=reusable)
        else:
            tok0, solo = self._admit_resume(req, donor, hit_len)
        req.prefix_hit_tokens = hit_len
        self._count_hit(L, hit_len)
        if reusable and solo is not None:
            self.pool.put(snap_key(req.prompt), L, solo,
                          evict_cb=self._spill)
        return tok0

    def _admit_cold(self, req: Request,
                    register: bool) -> Tuple[int, Optional[Any]]:
        """Full prefill through the fused dense admit program — literally
        the ``ContinuousEngine`` admission, which is what makes snapshot
        serving bit-identical to the dense baseline."""
        eng = self.engine
        L = len(req.prompt)
        S = eng.scheduler.bucket_for(L)
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
        slot = eng.slots.acquire(req)
        eng.states, tok, eng._key, eng._mirrors = self._admit_prog(
            eng.params, eng.states, batch,
            jnp.asarray(slot, jnp.int32), eng._key, eng._mirrors)
        solo = None
        if register:        # capture the spliced slot as a fresh snapshot
            solo = self._read_slot_prog(eng.states,
                                        jnp.asarray(slot, jnp.int32))
        with TraceAnnotation("serve.admit.wait"):
            return int(tok[0]), solo

    def _admit_resume(self, req: Request, donor: Any,
                      hit_len: int) -> Tuple[int, Any]:
        """Suffix-only prefill on top of a restored snapshot.  The resume
        program also returns the post-prefill solo state, so the full new
        prompt registers as a snapshot without a second dispatch."""
        eng = self.engine
        L = len(req.prompt)
        suffix = req.prompt[hit_len:]
        # Clamp the suffix bucket so hit_len + S never wraps the solo cache
        # (exact-prefill archs bucket to the exact suffix length anyway).
        S = max(min(eng.scheduler.bucket_for(len(suffix)),
                    self.scfg.max_seq_len - hit_len), len(suffix), 1)
        toks = np.zeros((1, S), np.int32)
        toks[0, :len(suffix)] = suffix
        positions = (hit_len + np.arange(S, dtype=np.int32))[None, :]
        sp = req.sampling
        batch = {"tokens": jnp.asarray(toks),
                 "positions": jnp.asarray(positions),
                 "length": jnp.asarray(L, jnp.int32),
                 "hit_len": jnp.asarray(hit_len, jnp.int32),
                 "temp": jnp.asarray(sp.temperature, jnp.float32),
                 "top_k": jnp.asarray(sp.top_k, jnp.int32),
                 "top_p": jnp.asarray(sp.top_p, jnp.float32)}
        slot = eng.slots.acquire(req)
        eng.states, solo, tok, eng._key, eng._mirrors = self._resume_prog(
            eng.params, eng.states, donor, batch,
            jnp.asarray(slot, jnp.int32), eng._key, eng._mirrors)
        with TraceAnnotation("serve.admit.wait"):
            return int(tok[0]), solo

    # -- handoff ---------------------------------------------------------------
    def export_handoff(self, req: Request, rid: int, max_new_tokens: int,
                       first_token: int) -> SnapshotHandoff:
        eng = self.engine
        solo = self._read_slot_prog(eng.states,
                                    jnp.asarray(req.slot, jnp.int32))
        return SnapshotHandoff(
            rid=rid, prompt_len=len(req.prompt),
            max_new_tokens=max_new_tokens, first_token=first_token,
            state=jax.device_get(solo),
            sampling=dataclasses.asdict(req.sampling))

    def import_handoff(self, req: Request, h) -> Optional[int]:
        """Splice a transported snapshot into the batch.  Never defers —
        slot state is pre-allocated, there is no page pool to contend
        for."""
        eng = self.engine
        if not isinstance(h, SnapshotHandoff):
            raise ValueError(
                f"stale/malformed handoff at kv/{req.rid}: expected a "
                f"SnapshotHandoff blob, got {type(h).__name__}")
        L = h.prompt_len
        if (h.rid != req.rid or L != len(req.prompt)
                or h.max_new_tokens != req.max_new_tokens):
            raise ValueError(
                f"stale/malformed handoff at kv/{req.rid}: blob carries "
                f"rid={h.rid} prompt_len={L} max_new={h.max_new_tokens}")
        solo = jax.tree.map(jnp.asarray, h.state)
        slot = eng.slots.acquire(req)
        eng.states = self._insert_slot_prog(
            eng.states, solo, jnp.asarray(slot, jnp.int32))
        # The blob's sampling state is the wire-format truth (a cross-host
        # decode endpoint has no Request object to fall back on).
        sp = h.sampling
        m = eng._mirrors
        eng._mirrors = {
            "tok": m["tok"].at[slot].set(h.first_token),
            "pos": m["pos"].at[slot].set(L),
            "temp": m["temp"].at[slot].set(float(sp["temperature"])),
            "top_k": m["top_k"].at[slot].set(int(sp["top_k"])),
            "top_p": m["top_p"].at[slot].set(float(sp["top_p"])),
        }
        self._count_hit(L, 0)
        if self._reuse:     # the import doubles as a local registration
            self.pool.put(snap_key(req.prompt), L, solo,
                          evict_cb=self._spill)
        return int(h.first_token)

    def handoff_bytes_for(self, prompt_len: int) -> float:
        # O(1) per request: one solo decode-state blob, independent of the
        # prompt length — the router's link-cost term for snapshot archs.
        if self._state_bytes is None:
            self._state_bytes = decode_state_nbytes(self.cfg,
                                                    self.scfg.max_seq_len)
        return float(self._state_bytes)

    # -- decode / release ------------------------------------------------------
    def decode_step(self) -> np.ndarray:
        eng = self.engine
        eng.states, toks_dev, eng._key, eng._mirrors = self._decode_prog(
            eng.params, eng.states, eng._key, eng._mirrors)
        with TraceAnnotation("serve.decode.wait"):
            return np.asarray(toks_dev)

    def verify_step(self, drafts: jax.Array,
                    caps: jax.Array) -> Tuple[np.ndarray, np.ndarray]:
        """All-or-nothing verify for irreversible per-slot state: the fused
        program keeps the pre-verify state alive (it is NOT donated) until
        the per-row select commits — fully-matching rows take the chunk
        state, any rejection takes the single-step fallback computed from
        the same pre-verify snapshot, bit-identical to a non-speculative
        step.  Accept lengths come back as 0 or k only."""
        eng = self.engine
        eng.states, out, acc, eng._key, eng._mirrors = self._verify_prog(
            eng.params, eng.states, eng._key, eng._mirrors, drafts, caps)
        with TraceAnnotation("serve.decode.wait"):
            out, acc = np.asarray(out), np.asarray(acc)
        self._count_rollback(acc, int(drafts.shape[1]))
        return out, acc

    def release(self, req: Optional[Request], slot: int) -> None:
        pass                # per-slot state is part of the batched tree

    def stats(self) -> Dict[str, Any]:
        with self.engine._lock:
            faults, spills = self.faults, self.spills
        s = {
            "snapshot_pool": dict(self.pool.stats(), faults=faults,
                                  spills=spills),
            "cold_snapshots": (len(self.cold) if self.cold is not None
                               else 0),
            "prefix_hit_rate": self._hit_rate(),
        }
        if self.engine is not None and self.engine._draft is not None:
            with self.engine._lock:
                s["spec_rolled_back_tokens"] = self._rolled_back
        return s
