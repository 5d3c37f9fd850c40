"""Top-level models: decoder-only LM, encoder-decoder, VLM cross-attention.

Layers follow ``cfg.pattern`` repeated; parameters are stored STACKED per
pattern slot (leading dim = repetitions) in both execution modes:

  * ``scan_layers=True``  — ``lax.scan`` over repetitions (fast compiles;
    used for smoke tests and the multi-pod compile proof),
  * ``scan_layers=False`` — python loop indexing the same stacked params
    (accurate ``cost_analysis`` accounting for the roofline, since XLA counts
    a while-loop body only once).

Remainder layers (L % len(pattern), e.g. recurrentgemma's 38 = 12x3 + 2) get
their own unstacked "tail" params.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.model import MIX_ATTN, MIX_ATTN_CROSS, ModelConfig
from repro.models import attention as attn_mod
from repro.models import blocks as blk
from repro.models.attention import put_pages, take_pages
from repro.models.common import dtype_of, normal_init, rms_norm, init_rmsnorm, split_keys


@dataclasses.dataclass(frozen=True)
class ExecPolicy:
    """Execution knobs — the §Perf hillclimbing levers."""
    scan_layers: bool = True
    q_chunk: int = 0            # 0 -> auto
    kv_chunk: int = 0
    use_kernel: bool = False    # Pallas path (TPU); False -> XLA oracle path
    remat: str = "none"         # "none" | "block"
    # §Perf: pin recurrent-mixer operands to batch-only sharding (kills the
    # per-chunk resharding collectives in the rwkv6 scan; see models/rwkv6.py)
    constrain_recurrence: bool = False

    def chunks_for(self, seq_len: int) -> Tuple[int, int]:
        if self.q_chunk and self.kv_chunk:
            return self.q_chunk, self.kv_chunk
        if seq_len > 2048:
            c = 512
            while seq_len % c:
                c //= 2
            return c, c
        return 0, 0


def _reps_rem(cfg: ModelConfig) -> Tuple[int, int]:
    p = len(cfg.pattern)
    return cfg.num_layers // p, cfg.num_layers % p


# ----------------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = dtype_of(cfg.dtype)
    keys = split_keys(key, 8)
    reps, rem = _reps_rem(cfg)
    params: Dict[str, Any] = {
        "embed": normal_init(keys[0], (cfg.vocab_size, cfg.d_model), dtype),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = normal_init(
            keys[1], (cfg.d_model, cfg.vocab_size), dtype, fan_in=cfg.d_model)
    if cfg.frontend != "none":
        fd = cfg.frontend_dim or cfg.d_model
        params["frontend_proj"] = normal_init(
            keys[2], (fd, cfg.d_model), dtype, fan_in=fd)

    def stacked(key, kind):
        ks = jax.random.split(key, reps)
        return jax.vmap(lambda k: blk.init_block(k, kind, cfg, dtype))(ks)

    lk = split_keys(keys[3], len(cfg.pattern) + max(rem, 1))
    params["layers"] = {
        str(i): stacked(lk[i], kind) for i, kind in enumerate(cfg.pattern)
    } if reps else {}
    params["tail"] = {
        str(i): blk.init_block(lk[len(cfg.pattern) + i], cfg.pattern[i], cfg, dtype)
        for i in range(rem)
    }

    if cfg.is_encoder_decoder:
        ek = split_keys(keys[4], 2)
        enc_reps = cfg.num_encoder_layers
        eks = jax.random.split(ek[0], enc_reps)
        params["encoder"] = {
            "layers": {"0": jax.vmap(
                lambda k: blk.init_block(k, MIX_ATTN, cfg, dtype))(eks)},
            "final_norm": init_rmsnorm(cfg.d_model, dtype),
        }
    return params


def init_decode_state(cfg: ModelConfig, batch: int, capacity: int) -> Dict[str, Any]:
    """Stacked per-slot block states + tail states (+ enc-dec memory)."""
    dtype = dtype_of(cfg.dtype)
    reps, rem = _reps_rem(cfg)

    def stack_state(kind):
        one = blk.init_block_state(kind, cfg, batch, capacity, dtype)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (reps,) + a.shape), one)

    state: Dict[str, Any] = {
        "slots": {str(i): stack_state(kind)
                  for i, kind in enumerate(cfg.pattern)} if reps else {},
        "tail": {str(i): blk.init_block_state(cfg.pattern[i], cfg, batch,
                                              capacity, dtype)
                 for i in range(rem)},
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.is_encoder_decoder:
        m = cfg.frontend_seq_len or 256
        state["enc_out"] = jnp.zeros((batch, m, cfg.d_model), dtype)
    return state


def insert_decode_slot(state: Dict[str, Any], solo: Dict[str, Any],
                       slot) -> Dict[str, Any]:
    """Write a batch-1 decode state into row ``slot`` of a batched state.

    This is the device half of continuous batching: the admission plane
    prefills a request solo, then splices its caches/recurrent state into the
    running batch between decode steps.  Stacked ("slots") leaves carry the
    batch on axis 1 (axis 0 is the scan repetition), unstacked ("tail") and
    encoder-memory leaves on axis 0.  Both states must share capacity.
    ``slot`` may be a traced int32 scalar (jit with the batch state donated).
    """
    def write_at(axis):
        def f(dst, src):
            start = [0] * dst.ndim
            start[axis] = slot
            return jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype), tuple(start))
        return f

    out: Dict[str, Any] = {
        "slots": (jax.tree.map(write_at(1), state["slots"], solo["slots"])
                  if state["slots"] else {}),
        "tail": jax.tree.map(write_at(0), state["tail"], solo["tail"]),
        "pos": state["pos"],
    }
    if "enc_out" in state:
        out["enc_out"] = write_at(0)(state["enc_out"], solo["enc_out"])
    return out


def read_decode_slot(state: Dict[str, Any], slot) -> Dict[str, Any]:
    """Inverse of :func:`insert_decode_slot`: slice row ``slot`` of a batched
    decode state back out as a batch-1 solo state (same tree, batch dim kept).

    This is the snapshot half of the recurrent-state pool: a slot's fixed-size
    state (rwkv6 ``S``/``x_prev``, rglru ``h``/``conv``, SWA ring caches) is
    captured between decode steps for prefix reuse, spill, or handoff, then
    spliced back with ``insert_decode_slot``.  ``slot`` may be a traced int32
    scalar.
    """
    def take(axis):
        def f(a):
            start = [0] * a.ndim
            start[axis] = slot
            size = list(a.shape)
            size[axis] = 1
            return jax.lax.dynamic_slice(a, tuple(start), tuple(size))
        return f

    out: Dict[str, Any] = {
        "slots": (jax.tree.map(take(1), state["slots"])
                  if state["slots"] else {}),
        "tail": jax.tree.map(take(0), state["tail"]),
        "pos": state["pos"],
    }
    if "enc_out" in state:
        out["enc_out"] = take(0)(state["enc_out"])
    return out


def select_decode_rows(mask: jax.Array, a: Dict[str, Any],
                       b: Dict[str, Any]) -> Dict[str, Any]:
    """Per-row merge of two same-shape batched decode states: row ``i`` of
    the result comes from ``a`` where ``mask[i]`` is true, else from ``b``.

    The device half of speculative all-or-nothing commit for snapshot archs:
    rows whose whole draft chunk verified keep the multi-token post-verify
    state, rejected rows fall back to the single-step state.  Stacked
    ("slots") leaves carry the batch on axis 1, unstacked ("tail") and
    encoder-memory leaves on axis 0 — same convention as
    :func:`insert_decode_slot`."""
    def sel(axis):
        def f(x, y):
            shape = [1] * x.ndim
            shape[axis] = mask.shape[0]
            return jnp.where(mask.reshape(shape), x, y)
        return f

    out: Dict[str, Any] = {
        "slots": (jax.tree.map(sel(1), a["slots"], b["slots"])
                  if a["slots"] else {}),
        "tail": jax.tree.map(sel(0), a["tail"], b["tail"]),
        "pos": a["pos"],
    }
    if "enc_out" in a:
        out["enc_out"] = sel(0)(a["enc_out"], b["enc_out"])
    return out


def decode_state_nbytes(cfg: ModelConfig, capacity: int) -> int:
    """Bytes of one slot's decode state (the snapshot/handoff transfer unit
    for non-paged archs) — computed via ``eval_shape``, no allocation."""
    tree = jax.eval_shape(lambda: init_decode_state(cfg, 1, capacity))
    return sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))


# ----------------------------------------------------------------------------
# Paged decode state (block-table KV paging; see serve.kvpool for the
# host-side allocator and serve.engine.PagedEngine for the admission plane)
# ----------------------------------------------------------------------------

def supports_paging(cfg: ModelConfig) -> bool:
    """Block-table KV paging covers global-attention decoder-only archs.
    Recurrent mixers and SWA ring caches have O(1)/ring state with no page
    structure to share, and enc-dec / VLM frontends carry non-pageable
    per-slot memory — those archs serve through the snapshot-pool backend
    (``serve.backends.SnapshotBackend``) instead."""
    return (all(k == MIX_ATTN for k in cfg.pattern)
            and not cfg.is_encoder_decoder
            and cfg.mlp_kind != "rwkv_cmix"
            and cfg.frontend == "none")


def init_paged_decode_state(cfg: ModelConfig, num_pages: int,
                            page_size: int,
                            kv_quant: str = "none") -> Dict[str, Any]:
    """Like ``init_decode_state`` but attention caches are shared physical
    page pools (no batch axis): slot residency is whatever the block tables
    map, so memory scales with live tokens instead of slots x max_seq_len.
    ``kv_quant="int8"`` stores pages quantized (int8 values + per-entry f32
    scale leaves ``ksc``/``vsc`` riding the same tree, so spill/fault/handoff
    move them for free)."""
    if not supports_paging(cfg):
        raise ValueError(f"{cfg.arch_id}: paging needs all-global-attention "
                         "decoder-only (recurrent/SWA archs keep the dense "
                         "exact-prefill path)")
    dtype = dtype_of(cfg.dtype)
    reps, rem = _reps_rem(cfg)

    def pool(lead=()):
        one = {"cache": attn_mod.init_paged_cache(cfg, num_pages, page_size,
                                                  dtype, kv_quant=kv_quant)}
        if not lead:
            return one
        return jax.tree.map(
            lambda a: jnp.zeros(lead + a.shape, a.dtype), one)

    return {
        "slots": {str(i): pool((reps,)) for i in range(len(cfg.pattern))}
                 if reps else {},
        "tail": {str(i): pool() for i in range(rem)},
        "pos": jnp.zeros((), jnp.int32),
    }


def _map_pools(fn, pstate: Dict[str, Any], *rest) -> Dict[str, Any]:
    """``fn(key, leaf, *rest_leaves)`` over every pool leaf."""
    pools = {"slots": pstate["slots"], "tail": pstate["tail"]}
    return jax.tree_util.tree_map_with_path(
        lambda path, a, *r: fn(path[-1].key, a, *r), pools, *rest)


def read_page(pstate: Dict[str, Any], page) -> Dict[str, Any]:
    """Slice physical page ``page`` out of every layer's pool (the spill
    payload: fresh small buffers, safe to hand to the sidecar while the pool
    itself keeps being donated through decode steps)."""
    return _map_pools(lambda k, a: take_pages(k, a, page), pstate)


def read_pages(pstate: Dict[str, Any], pages) -> Dict[str, Any]:
    """Batched :func:`read_page`: gather ``pages`` (an int32 vector) from
    every pool in one op, with the page axis moved to the front of every
    leaf — element ``i`` of the result tree equals ``read_page(pstate,
    pages[i])``.  Lets the handoff exporter move all of a request's prompt
    pages to the host in a single transfer instead of one sync per page."""
    return _map_pools(
        lambda k, a: jnp.moveaxis(take_pages(k, a, pages), -2, 0), pstate)


def write_page(pstate: Dict[str, Any], page, blob: Dict[str, Any]
               ) -> Dict[str, Any]:
    """Fault a spilled page's content back into every layer's pool."""
    return dict(_map_pools(lambda k, a, b: put_pages(k, a, page, b),
                           pstate, blob), pos=pstate["pos"])


def load_prefix_pages(solo: Dict[str, Any], pstate: Dict[str, Any],
                      table_row, hit_len) -> Dict[str, Any]:
    """Seed a fresh batch-1 dense decode state with a reused prefix: gather
    the row's pages from every pool into the solo cache's first ``capacity``
    entries and mark ``[0, hit_len)`` valid.  Unassigned logical pages point
    at the scratch page, so the gathered garbage is masked off by ``pos``.
    Quantized pools dequantize on the way out (the dense solo cache is the
    model dtype; requantization on scatter-back is the only lossy step)."""
    def seed(dense_leaf, pool_cache, key, skey):
        # dense (..., 1, C, J, N) <- the row's pages of every layer
        gathered = take_pages(key, pool_cache[key], table_row).reshape(
            dense_leaf.shape)
        if skey in pool_cache:
            scales = take_pages(skey, pool_cache[skey], table_row).reshape(
                dense_leaf.shape[:-1])
            gathered = attn_mod.kv_dequantize(gathered, scales)
        return gathered.astype(dense_leaf.dtype)

    def fill(solo_state, pool_state):
        cache = solo_state["cache"]
        C = cache["pos"].shape[-1]
        t = jnp.arange(C, dtype=jnp.int32)
        pos = jnp.where(t < hit_len, t, -1)
        return {"cache": {
            "k": seed(cache["k"], pool_state["cache"], "kp", "ksc"),
            "v": seed(cache["v"], pool_state["cache"], "vp", "vsc"),
            "pos": jnp.broadcast_to(pos, cache["pos"].shape)}}

    out = dict(solo)
    for group in ("slots", "tail"):
        out[group] = {i: fill(solo[group][i], pstate[group][i])
                      for i in solo[group]}
    out["pos"] = jnp.asarray(hit_len, jnp.int32)
    return out


def scatter_solo_pages(pstate: Dict[str, Any], solo: Dict[str, Any],
                       assign) -> Dict[str, Any]:
    """Admission's device half: scatter a prefilled solo dense cache into the
    pools at the pages ``assign`` maps (logical -> physical; scratch page 0
    for logical pages that were prefix hits or past the allocation, so shared
    pages are never rewritten).  One scatter of page windows per leaf into
    the (donated) pool, which it updates in place.  Quantized pools
    quantize on the way in, scattering values and the matching scales
    under the same indices."""
    def scat(pool_cache, dense_leaf, key, skey):
        # dense (..., 1, M*page, J, N) -> pages (..., M, page*J*N)
        lead = dense_leaf.shape[:-4]                     # (reps,) or ()
        M = assign.shape[0]

        def put(k, new):
            return put_pages(k, pool_cache[k], assign,
                             new.reshape(lead + (M, -1)))
        if skey not in pool_cache:
            return {key: put(key, dense_leaf)}
        values, scales = attn_mod.kv_quantize(dense_leaf)
        return {key: put(key, values), skey: put(skey, scales)}

    out = {"slots": {}, "tail": {}, "pos": pstate["pos"]}
    for group in ("slots", "tail"):
        for i in pstate[group]:
            pool = pstate[group][i]["cache"]
            dense = solo[group][i]["cache"]
            out[group][i] = {"cache": {
                **scat(pool, dense["k"], "kp", "ksc"),
                **scat(pool, dense["v"], "vp", "vsc")}}
    return out


def invalidate_positions_from(states: Dict[str, Any], length) -> Dict[str, Any]:
    """Mark attention-cache entries holding positions >= ``length`` empty.

    Bucket prefill right-pads the prompt; causal masking keeps the pads from
    corrupting real-token outputs, and this drops the pads' own cache entries
    (``pos`` -1 == empty) so later decode steps never attend to them.  Works
    on position *values*, so ring-wrapped SWA caches are handled too.
    """
    def f(path, leaf):
        last = path[-1]
        if (isinstance(last, jax.tree_util.DictKey) and last.key == "pos"
                and getattr(leaf, "ndim", 0) >= 2):
            return jnp.where(leaf < length, leaf, -1)
        return leaf
    return jax.tree_util.tree_map_with_path(f, states)


# ----------------------------------------------------------------------------
# Layer stack execution
# ----------------------------------------------------------------------------

def _run_stack(
    layer_params: dict,
    tail_params: dict,
    pattern: Tuple[str, ...],
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    policy: ExecPolicy,
    *,
    memory: Optional[jax.Array] = None,
    states: Optional[dict] = None,     # {"slots": ..., "tail": ...}
    causal: bool = True,
    page_table: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[dict], jax.Array]:
    reps = 0
    if layer_params:
        reps = jax.tree.leaves(layer_params)[0].shape[0]
    qc, kc = policy.chunks_for(x.shape[1])
    aux0 = jnp.zeros((), jnp.float32)

    def apply_one(p, kind, x, st, layer=None):
        return blk.apply_block(
            p, kind, x, positions, cfg, memory=memory, state=st,
            causal=causal, page_table=page_table, layer=layer, q_chunk=qc,
            kv_chunk=kc, use_kernel=policy.use_kernel,
            constrain_recurrence=policy.constrain_recurrence)

    new_states: Optional[dict] = {"slots": {}, "tail": {}} if states is not None else None

    if reps and page_table is not None:
        # Paged decode/verify: the stacked page pools ride in the carry and
        # each layer writes its new entries into them in place, by layer
        # index.  Scanned as xs/ys instead, every layer's whole pool would be
        # sliced out, copied and restacked in every step.
        def paged_body(carry, xs):
            x, aux, pools = carry
            p_slice, r = xs
            pools = dict(pools)
            for i, kind in enumerate(pattern):
                x, pools[str(i)], a = apply_one(
                    p_slice[str(i)], kind, x, pools[str(i)], layer=r)
                aux = aux + a
            return (x, aux, pools), None

        carry = (x, aux0, states["slots"])
        if policy.scan_layers:
            carry, _ = jax.lax.scan(
                paged_body, carry,
                (layer_params, jnp.arange(reps, dtype=jnp.int32)))
        else:
            for r in range(reps):
                carry, _ = paged_body(
                    carry, (jax.tree.map(lambda a, r=r: a[r], layer_params),
                            r))
        x, aux, new_states["slots"] = carry
    elif reps:
        slot_states = states["slots"] if states is not None else None

        def body(carry, xs):
            x, aux = carry
            p_slice = xs[0]
            s_slice = xs[1] if states is not None else None
            out_states = {}
            for i, kind in enumerate(pattern):
                st = s_slice[str(i)] if s_slice is not None else None
                x, ns, a = apply_one(p_slice[str(i)], kind, x, st)
                if ns is not None:
                    out_states[str(i)] = ns
                aux = aux + a
            return (x, aux), (out_states if out_states else None)

        if policy.scan_layers:
            fn = body
            if policy.remat == "block" and states is None:
                fn = jax.checkpoint(body, prevent_cse=False)
            xs = (layer_params,) if states is None else (layer_params, slot_states)
            (x, aux), ys = jax.lax.scan(fn, (x, aux0), xs)
            if states is not None:
                new_states["slots"] = ys
        else:
            fn = body
            if policy.remat == "block" and states is None:
                fn = jax.checkpoint(body, prevent_cse=False)
            aux = aux0
            acc = []
            for r in range(reps):
                p_slice = jax.tree.map(lambda a, r=r: a[r], layer_params)
                s_slice = (jax.tree.map(lambda a, r=r: a[r], slot_states)
                           if states is not None else None)
                (x, aux), ns = fn((x, aux), (p_slice,) if states is None
                                  else (p_slice, s_slice))
                acc.append(ns)
            if states is not None:
                new_states["slots"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *acc)
    else:
        aux = aux0

    for i in sorted(tail_params, key=int):
        kind = pattern[int(i)]
        st = states["tail"][i] if states is not None else None
        x, ns, a = apply_one(tail_params[i], kind, x, st)
        if states is not None:
            new_states["tail"][i] = ns
        aux = aux + a
    return x, new_states, aux


# ----------------------------------------------------------------------------
# Full forward passes
# ----------------------------------------------------------------------------

def _embed(params, cfg: ModelConfig, tokens):
    h = params["embed"][tokens]
    if cfg.scale_embeddings:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return h


def logits_from_hidden(params, cfg: ModelConfig, h) -> jax.Array:
    if cfg.tie_embeddings:
        w = params["embed"].T
    else:
        w = params["unembed"]
    return jnp.einsum("bsd,dv->bsv", h, w).astype(dtype_of(cfg.logit_dtype))


def encode(params, cfg: ModelConfig, policy: ExecPolicy, *,
           frontend_embeds=None, encoder_tokens=None) -> jax.Array:
    """Encoder pass (enc-dec models). Returns (B, M, D) memory."""
    enc = params["encoder"]
    if frontend_embeds is not None:
        h = jnp.einsum("bmf,fd->bmd", frontend_embeds, params["frontend_proj"])
    else:
        h = _embed(params, cfg, encoder_tokens)
    pos = jnp.broadcast_to(jnp.arange(h.shape[1], dtype=jnp.int32)[None],
                           h.shape[:2])
    h, _, _ = _run_stack(enc["layers"], {}, (MIX_ATTN,), h, pos, cfg, policy,
                         causal=False)
    return rms_norm(h, enc["final_norm"], cfg.norm_eps)


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,                    # (B, S)
    positions: Optional[jax.Array] = None,
    *,
    policy: ExecPolicy = ExecPolicy(),
    frontend_embeds: Optional[jax.Array] = None,
    states: Optional[dict] = None,
    page_table: Optional[jax.Array] = None,
    return_hidden: bool = False,
) -> Tuple[jax.Array, Optional[dict], jax.Array]:
    """Returns (logits | hidden, new_states, aux_loss).

    Train / prefill: states=None / states=fresh; decode: S == 1 with states.
    ``page_table`` (B, M) routes attention-cache reads/writes through the
    paged pool (states from ``init_paged_decode_state``).
    """
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    memory = None
    if cfg.is_encoder_decoder:
        if states is not None and frontend_embeds is None:
            memory = states["enc_out"]
        else:
            memory = encode(params, cfg, policy,
                            frontend_embeds=frontend_embeds)
    elif cfg.frontend != "none" and frontend_embeds is not None:
        memory = jnp.einsum("bmf,fd->bmd", frontend_embeds,
                            params["frontend_proj"])

    h = _embed(params, cfg, tokens)
    h, new_states, aux = _run_stack(
        params["layers"], params["tail"], cfg.pattern, h, positions, cfg,
        policy, memory=memory, states=states, causal=True,
        page_table=page_table)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)

    if states is not None and new_states is not None:
        new_states["pos"] = positions[0, -1].astype(jnp.int32) + 1
        if cfg.is_encoder_decoder:
            new_states["enc_out"] = memory
    if return_hidden:
        return h, new_states, aux
    return logits_from_hidden(params, cfg, h), new_states, aux
