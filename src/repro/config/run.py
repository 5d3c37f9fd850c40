"""Run-level configs: mesh, training, serving, offload (paper guidelines)."""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Tuple


class EngineMode(str, enum.Enum):
    """Which serve engine ``repro.serve.make_engine`` builds.

    One request path, five implementations of increasing distribution —
    fixed-batch baseline, continuous batching, backend-managed cache
    (paged KV or snapshot pool, per arch), disaggregated prefill/decode,
    and the multi-replica cluster."""
    FIXED = "fixed"
    CONTINUOUS = "continuous"
    PAGED = "paged"
    DISAGGREGATED = "disaggregated"
    CLUSTER = "cluster"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh. Production: (16,16) per pod, 2 pods multi-pod."""
    data: int = 1
    model: int = 1
    pod: int = 1

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.pod, self.data, self.model) if self.pod > 1 else (self.data, self.model)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.pod > 1 else ("data", "model")

    @property
    def num_devices(self) -> int:
        return self.pod * self.data * self.model


@dataclasses.dataclass(frozen=True)
class OffloadConfig:
    """The paper's four guidelines as framework switches.

    G1: ``use_accelerators`` — route hot-spot ops to Pallas kernels when the
        shape is supported (general-purpose jnp fallback otherwise).
    G2: ``background_offload`` — checkpoint/metrics/log work runs on the
        sidecar (host threads), never blocking the step.
    G3: ``endpoint_expansion`` — host DRAM as an extra memory endpoint
        (host-resident optimizer master state with prefetch) and host-side
        data sharding; ``replica_endpoints`` = peer hosts for ckpt replication.
    G4: ``enforce_cost_model`` — placement planner rejects critical-path
        offloads whose link cost exceeds the predicted saving.
    """
    use_accelerators: bool = True
    background_offload: bool = True
    endpoint_expansion: bool = False
    replica_endpoints: int = 0
    enforce_cost_model: bool = True
    # Sidecar executor sizing
    max_inflight_tasks: int = 4
    sidecar_threads: int = 2


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    global_batch: int = 8
    seq_len: int = 128
    steps: int = 100
    microbatches: int = 1            # grad accumulation
    learning_rate: float = 3e-4
    warmup_steps: int = 10
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    optimizer: str = "adamw"         # "adamw" | "lion" | "sgdm"
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    z_loss: float = 1e-4
    moe_aux_loss: float = 1e-2
    seed: int = 0
    remat: str = "none"              # "none" | "block" | "full"
    grad_compression: str = "none"   # "none" | "int8_ef"
    zero1: bool = True               # shard optimizer state over data axis
    log_every: int = 10
    ckpt_every: int = 0              # 0 -> disabled
    ckpt_dir: str = ""
    ckpt_keep: int = 3


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving knobs.  ``max_batch`` is the fixed decode width (slot count);
    the admission plane fills/evicts slots between decode steps."""
    max_batch: int = 8
    max_seq_len: int = 1024          # decode-state capacity per slot
    prefill_chunk: int = 512
    temperature: float = 0.0         # 0 -> greedy
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    # Continuous-batching admission plane
    max_queue: int = 64              # bounded request queue (backpressure)
    eos_id: int = -1                 # -1 -> no EOS eviction
    prefill_buckets: Tuple[int, ...] = (16, 32, 64, 128, 256, 512)
    result_shards: int = 4           # ShardedStore endpoints for results
    # Paged KV-cache (PagedEngine): fixed-size pages + block tables instead
    # of a dense per-slot cache; memory scales with live tokens.
    page_size: int = 16              # tokens per physical KV page
    num_pages: int = 0               # pool size; 0 -> full residency for
    #                                  every slot (max_batch * pages_per_seq
    #                                  + scratch, rounded up to 64 pages)
    prefix_cache: bool = True        # hash-keyed prefix page sharing (CoW)
    kv_quant: str = "none"           # "none" | "int8": quantized KV pages
    #                                  (int8 values + per-entry f32 scales;
    #                                  ~3.5x pages per byte, ~3.5x smaller
    #                                  handoff blobs).  Paged backend only —
    #                                  snapshot archs keep f32 state.
    cold_pages: int = 256            # host-tier spill capacity (pages for
    #                                  the paged backend, snapshots for the
    #                                  snapshot backend); 0 disables the
    #                                  tiered-memory plane
    # Snapshot pool (SnapshotBackend, recurrent/SWA archs): hot LRU capacity
    # for whole decode-state snapshots reused as prefix donors.
    snapshot_slots: int = 8
    # Disaggregated prefill/decode serving (DisaggregatedEngine): prefill
    # runs on a second engine endpoint; decode state comes back as a handoff
    # blob hash-sharded over peer endpoints.
    disagg_route: str = "auto"       # "auto" (cost model per request) |
    #                                  "remote" | "local" (forced)
    prefill_slots: int = 2           # prefill-endpoint slot count
    prefill_pages: int = 0           # prefill-endpoint pool pages (0 -> full
    #                                  residency, like num_pages)
    handoff_shards: int = 2          # ShardedStore endpoints for handoffs
    # Speculative decoding: a small greedy drafter proposes ``draft_k``
    # tokens per slot; the target scores all k+1 positions in one batched
    # verify step and accepts the longest matching greedy prefix.  Exact for
    # greedy requests (accepted chunks are bit-identical to sequential
    # decode); stochastic slots fall back to one token per step.
    speculative: bool = False
    draft_k: int = 4                 # drafted tokens per macro step (>= 1)
    draft_model: str = "self:1"      # "self:<n>" -> first-n-layer truncation
    #                                  of the target (shared embed/unembed);
    #                                  "self-int8" -> int8-quantized copy of
    #                                  the target; any other value -> an arch
    #                                  name from configs/ (independent
    #                                  random-init drafter, same vocab)
    # Engine selection (EngineMode): "" -> "continuous".
    engine_mode: str = ""
    # Multi-replica serve cluster (ServeCluster, engine_mode="cluster"):
    # N decode replicas (each a PagedEngine) behind a cost-model router.
    num_replicas: int = 2
    cluster_prefill: bool = True     # shared PrefillWorker endpoint feeding
    #                                  replicas via KV handoffs
