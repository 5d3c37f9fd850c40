"""Attention: GQA/MQA/MHA with RoPE, sliding windows, cross-attention, caches.

Two execution paths (paper G1 — accelerator vs general-purpose):
  * ``attend`` — memory-efficient flash-style pure-jnp attention (scan over
    q/kv chunks with running max/denominator).  This is simultaneously the
    Pallas kernel's numerical oracle and the XLA lowering used by the dry-run.
  * ``repro.kernels.flash_attention.ops.flash_attention`` — the Pallas TPU
    kernel (BlockSpec VMEM tiling), selected through the accelerator registry
    when shapes are supported.

Cache layout (per self-attention layer):
  {"k": (B, C, J, N), "v": (B, C, J, N), "pos": (B, C) int32}
``C`` is the cache capacity: full context for global attention, the window
size for sliding-window layers (ring buffer — this is what makes ``long_500k``
run with constant memory).  ``pos`` holds absolute token positions (-1 =
empty) so ring overwrites need no extra bookkeeping.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.model import ModelConfig
from repro.models.common import normal_init, rope, softcap, split_keys

NEG_INF = -1e30


# ----------------------------------------------------------------------------
# Parameters
# ----------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    d, h, j, n = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kk, kv, ko = split_keys(key, 4)
    return {
        "wq": normal_init(kq, (d, h, n), dtype, fan_in=d),
        "wk": normal_init(kk, (d, j, n), dtype, fan_in=d),
        "wv": normal_init(kv, (d, j, n), dtype, fan_in=d),
        "wo": normal_init(ko, (h, n, d), dtype, fan_in=h * n),
    }


# ----------------------------------------------------------------------------
# Core attention math (flash-style oracle / XLA path)
# ----------------------------------------------------------------------------

def _scores(q, k, cap: float):
    # q: (B,S,J,G,N)  k: (B,T,J,N)  ->  (B,J,G,S,T), f32
    s = jnp.einsum("bsjgn,btjn->bjgst", q, k, preferred_element_type=jnp.float32)
    return softcap(s, cap)


def _direct_attend(q, k, v, mask, cap: float):
    s = _scores(q, k, cap)
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bjgst,btjn->bsjgn", p.astype(v.dtype), v)


def attend(
    q: jax.Array,            # (B, S, J, G, N) — pre-scaled by 1/sqrt(N)
    k: jax.Array,            # (B, T, J, N)
    v: jax.Array,            # (B, T, J, N)
    q_pos: jax.Array,        # (B, S) int32
    k_pos: jax.Array,        # (B, T) int32; -1 marks invalid (empty cache slot)
    *,
    causal: bool = True,
    window: int = 0,
    cap: float = 0.0,
    q_chunk: int = 0,
    kv_chunk: int = 0,
) -> jax.Array:              # (B, S, J, G, N)
    """Masked attention; chunked (memory-O(chunk²)) when chunk sizes given."""
    B, S = q.shape[0], q.shape[1]
    T = k.shape[1]

    def mask_for(qp, kp):
        m = kp[:, None, :] >= 0
        if causal:
            m &= kp[:, None, :] <= qp[:, :, None]
        if window > 0:
            m &= kp[:, None, :] > (qp[:, :, None] - window)
        return m  # (B, s, t)

    if not q_chunk or not kv_chunk or (S <= q_chunk and T <= kv_chunk) \
            or S % q_chunk or T % kv_chunk:
        # decode (S==1) and odd shapes: direct — scores stay (B,·,S,T) small
        return _direct_attend(q, k, v, mask_for(q_pos, k_pos), cap)

    nq, nkv = S // q_chunk, T // kv_chunk
    kc = k.reshape(B, nkv, kv_chunk, *k.shape[2:]).swapaxes(0, 1)
    vc = v.reshape(B, nkv, kv_chunk, *v.shape[2:]).swapaxes(0, 1)
    kpc = k_pos.reshape(B, nkv, kv_chunk).swapaxes(0, 1)

    def q_block(qi, _):
        qb = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, axis=1)
        qpb = jax.lax.dynamic_slice_in_dim(q_pos, qi * q_chunk, q_chunk, axis=1)

        def kv_block(carry, xs):
            m_run, l_run, acc = carry
            kb, vb, kpb = xs
            s = _scores(qb, kb, cap)                       # (B,J,G,s,t) f32
            msk = mask_for(qpb, kpb)[:, None, None]
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            corr = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_run * corr + p.sum(axis=-1)
            pv = jnp.einsum("bjgst,btjn->bjgsn", p.astype(vb.dtype), vb)
            acc = acc * corr[..., None] + pv.astype(jnp.float32)
            return (m_new, l_new, acc), None

        J, G, N = q.shape[2], q.shape[3], q.shape[4]
        init = (
            jnp.full((B, J, G, q_chunk), NEG_INF, jnp.float32),
            jnp.zeros((B, J, G, q_chunk), jnp.float32),
            jnp.zeros((B, J, G, q_chunk, N), jnp.float32),
        )
        (m_f, l_f, acc), _ = jax.lax.scan(kv_block, init, (kc, vc, kpc))
        out = acc / jnp.maximum(l_f[..., None], 1e-30)
        return qi + 1, out.astype(q.dtype)                 # (B,J,G,s,N)

    _, outs = jax.lax.scan(q_block, 0, None, length=nq)    # (nq,B,J,G,s,N)
    out = jnp.moveaxis(outs, 0, 3)                         # (B,J,G,nq,s,N)
    B_, J, G = out.shape[0], out.shape[1], out.shape[2]
    out = out.reshape(B_, J, G, S, q.shape[4])
    return out.transpose(0, 3, 1, 2, 4)                    # (B,S,J,G,N)


# ----------------------------------------------------------------------------
# Self-attention layer op (projections + rope + cache + attend)
# ----------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, capacity: int, dtype) -> dict:
    j, n = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, capacity, j, n), dtype),
        "v": jnp.zeros((batch, capacity, j, n), dtype),
        "pos": jnp.full((batch, capacity), -1, jnp.int32),
    }


def _project_qkv(params, x, positions, cfg: ModelConfig, use_rope: bool = True):
    h, j, n = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // j if j else 1
    q = jnp.einsum("bsd,dhn->bshn", x, params["wq"])
    k = jnp.einsum("bsd,djn->bsjn", x, params["wk"])
    v = jnp.einsum("bsd,djn->bsjn", x, params["wv"])
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = q.reshape(*q.shape[:2], j, g, n) * (n ** -0.5)
    return q, k, v


def self_attention(
    params: dict,
    x: jax.Array,                 # (B, S, D)
    positions: jax.Array,         # (B, S)
    cfg: ModelConfig,
    *,
    window: int = 0,
    causal: bool = True,
    cache: Optional[dict] = None,
    page_table: Optional[jax.Array] = None,   # (B, M) int32 — paged decode
    layer=None,                   # index into stacked page pools, or None
    q_chunk: int = 0,
    kv_chunk: int = 0,
    use_kernel: bool = False,
) -> Tuple[jax.Array, Optional[dict]]:
    """Returns (output (B,S,D), updated cache or None)."""
    q, k, v = _project_qkv(params, x, positions, cfg)
    if cache is not None and "kp" in cache:
        # Paged decode (S == 1): K/V live in a shared physical page pool and
        # are addressed through the block table instead of a per-slot buffer.
        cache = paged_cache_write(cache, k, v, positions, page_table, layer)
        out = paged_attend(q, cache, positions, page_table, layer,
                           cap=cfg.attn_logit_softcap, use_kernel=use_kernel)
        o = jnp.einsum("bsjgn,jgnd->bsd", out,
                       params["wo"].reshape(cfg.num_kv_heads, -1,
                                            cfg.head_dim, cfg.d_model))
        return o, cache
    if cache is None:
        if use_kernel:
            from repro.kernels.flash_attention import ops as fa_ops
            out = fa_ops.flash_attention(
                q, k, v, q_pos=positions, k_pos=positions,
                causal=causal, window=window, cap=cfg.attn_logit_softcap)
        else:
            out = attend(q, k, v, positions, positions, causal=causal,
                         window=window, cap=cfg.attn_logit_softcap,
                         q_chunk=q_chunk, kv_chunk=kv_chunk)
        new_cache = None
    else:
        cache = cache_write(cache, k, v, positions)
        out = attend(q, cache["k"], cache["v"], positions, cache["pos"],
                     causal=causal, window=window, cap=cfg.attn_logit_softcap,
                     q_chunk=q_chunk, kv_chunk=kv_chunk)
        new_cache = cache
    o = jnp.einsum("bsjgn,jgnd->bsd", out,
                   params["wo"].reshape(cfg.num_kv_heads, -1, cfg.head_dim,
                                        cfg.d_model))
    return o, new_cache


def cache_write(cache: dict, k: jax.Array, v: jax.Array, positions: jax.Array) -> dict:
    """Write S new kv entries at slots ``pos % C`` (ring for SWA caches).

    Every S < C write is a per-row scatter, so a continuously-batched step
    (S == 1) or a speculative verify chunk (S == k+1) may hold rows at
    different absolute positions.  Only the ring-truncation path (S >= C)
    still assumes batch-aligned positions (all rows share positions[0]) —
    that shape only occurs on the single-request admission plane.
    """
    C = cache["k"].shape[1]
    S = k.shape[1]
    B = k.shape[0]
    if S < C:
        rows = jnp.arange(B)[:, None]
        row_slots = positions % C                # (B, S) — per-row ring slots
        new_k = cache["k"].at[rows, row_slots].set(k)
        new_v = cache["v"].at[rows, row_slots].set(v)
        new_p = cache["pos"].at[rows, row_slots].set(
            positions.astype(jnp.int32))
    else:
        # prefill ring wrap: keep only the last C tokens (ring semantics)
        k, v = k[:, -C:], v[:, -C:]
        positions = positions[:, -C:]
        slots = positions[0] % C                 # (C,) batch-aligned
        new_k = cache["k"].at[:, slots].set(k)
        new_v = cache["v"].at[:, slots].set(v)
        new_p = cache["pos"].at[:, slots].set(positions.astype(jnp.int32))
    return {"k": new_k, "v": new_v, "pos": new_p}


# ----------------------------------------------------------------------------
# Paged cache (block-table addressed physical page pool; serve.kvpool is the
# host-side allocator, physical page 0 is its reserved scratch page)
# ----------------------------------------------------------------------------

KV_QUANT_MODES = ("none", "int8")
# Guards jnp.round against all-zero entries (fresh pages, padded rows): the
# dequantized value is exactly 0 either way, so the floor only avoids 0/0.
_KV_SCALE_FLOOR = 1e-8


def kv_quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-(entry, head) int8 quantization over the head dim.

    ``x (..., N) -> (int8 values (..., N), f32 scales (...))`` with
    ``scale = max|x| / 127``; dequant is ``values * scale`` (see
    ``kv_dequantize``).  One scale per cache entry per KV head keeps the
    error bounded by the entry's own dynamic range — a per-page scale would
    let one outlier token flatten its whole page."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / 127.0, _KV_SCALE_FLOOR)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def kv_dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of ``kv_quantize``: ``(..., N) int8 x (...) f32 -> (..., N)``
    f32."""
    return q.astype(jnp.float32) * scale[..., None]


# A pool leaf stores whole pages along its rows.  Value rows hold one page
# (``page*J*N`` wide); a page's ``page*J`` f32 scales are too few for a row
# of their own (80 at smollm widths would pad to 128 lanes), so a scale row
# holds this many pages side by side.
_SCALE_LEAVES = ("ksc", "vsc")
_SCALE_PAGES_PER_ROW = 8
# A pool of a multiple of this many pages fills whole (8, 128) tiles of
# rows in every leaf (scale rows too: 64 pages are 8 rows).  Given a ragged
# count, the TPU compiler stores a stacked leaf with the layer axis inside
# the page axis, and every program relayouts the pool in and out.
POOL_PAGE_MULTIPLE = 64


def _pages_per_row(key: str) -> int:
    return _SCALE_PAGES_PER_ROW if key in _SCALE_LEAVES else 1


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype, kv_quant: str = "none") -> dict:
    """Physical K/V page pool shared by every slot (one per layer).  There is
    no per-entry ``pos`` array: validity is positional — entry ``t`` of a
    row's logical view is live iff ``t < length`` — because pages are written
    densely from position 0 and never ring-wrap.

    A page is a row: ``kp``/``vp`` are ``(P, page*J*N)``, entry ``o`` of
    page ``p`` at columns ``[o*J*N, (o+1)*J*N)``; ``P`` should be a
    multiple of ``POOL_PAGE_MULTIPLE``.  With the page axis major
    and a wide minor axis, TPU's default layout tiles the pool without
    padding, and every page mover (:func:`take_pages`, :func:`put_pages`,
    the decode write) reads and writes it in that layout, by windows of
    rows, so no program relayouts a pool.

    ``kv_quant="int8"`` stores pages as int8 with per-(entry, head) f32
    scales in sibling ``ksc``/``vsc`` leaves of ``ceil(P/8)`` rows of 8
    pages each, so a
    page costs ``J*(N + 4)`` bytes per entry instead of ``4*J*N`` — ~3.5x
    more pages per byte at N=32.  The scale leaves ride the same generic
    page movers (``read_page``/``write_page``) as the values, so spill,
    fault-in and handoff carry them automatically."""
    if kv_quant not in KV_QUANT_MODES:
        raise ValueError(f"kv_quant must be one of {KV_QUANT_MODES}, "
                         f"got {kv_quant!r}")
    j, n = cfg.num_kv_heads, cfg.head_dim
    if kv_quant == "int8":
        rows = -(-num_pages // _SCALE_PAGES_PER_ROW)
        width = _SCALE_PAGES_PER_ROW * page_size * j
        return {
            "kp": jnp.zeros((num_pages, page_size * j * n), jnp.int8),
            "vp": jnp.zeros((num_pages, page_size * j * n), jnp.int8),
            "ksc": jnp.zeros((rows, width), jnp.float32),
            "vsc": jnp.zeros((rows, width), jnp.float32),
        }
    return {
        "kp": jnp.zeros((num_pages, page_size * j * n), dtype),
        "vp": jnp.zeros((num_pages, page_size * j * n), dtype),
    }


def _page_starts(key: str, leaf: jax.Array, pages, layer=None,
                 offset=0) -> jax.Array:
    """Start indices, ``(..., leaf.ndim)``, of the windows of pool leaf
    ``key`` that begin ``offset`` values into each page of ``pages``.  A
    stacked leaf (``(L, rows, width)``) takes ``layer``, or with ``layer``
    None gets a leading axis over all its layers; an unstacked leaf
    (``(rows, width)``) ignores ``layer``."""
    per_row = _pages_per_row(key)
    pages = jnp.asarray(pages, jnp.int32)
    width = leaf.shape[-1] // per_row
    starts = [pages // per_row, (pages % per_row) * width + offset]
    if leaf.ndim == 3:
        if layer is None:
            shape = (leaf.shape[0],) + (1,) * pages.ndim
            starts = [jnp.arange(leaf.shape[0], dtype=jnp.int32)
                      .reshape(shape)] + [a[None] for a in starts]
        else:
            starts = [jnp.asarray(layer, jnp.int32)] + starts
    return jnp.stack(jnp.broadcast_arrays(*starts), axis=-1)


def _read_windows(leaf: jax.Array, starts: jax.Array, size: int
                  ) -> jax.Array:
    dims = jax.lax.GatherDimensionNumbers(
        offset_dims=(starts.ndim - 1,),
        collapsed_slice_dims=tuple(range(leaf.ndim - 1)),
        start_index_map=tuple(range(leaf.ndim)))
    return jax.lax.gather(leaf, starts, dims,
                          (1,) * (leaf.ndim - 1) + (size,),
                          mode=jax.lax.GatherScatterMode.CLIP)


def _write_windows(leaf: jax.Array, starts: jax.Array, new: jax.Array
                   ) -> jax.Array:
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(new.ndim - 1,),
        inserted_window_dims=tuple(range(leaf.ndim - 1)),
        scatter_dims_to_operand_dims=tuple(range(leaf.ndim)))
    return jax.lax.scatter(leaf, starts, new.astype(leaf.dtype), dims)


def take_pages(key: str, leaf: jax.Array, pages, layer=None) -> jax.Array:
    """Pages ``pages`` (any int shape) of pool leaf ``key``, each as its
    flat ``page * entry`` values: ``pages.shape + (width,)``, with a leading
    layer axis for a stacked leaf read at every layer (``layer`` None).
    One gather of row windows — no slice of a layer's whole pool is built
    first."""
    width = leaf.shape[-1] // _pages_per_row(key)
    return _read_windows(leaf, _page_starts(key, leaf, pages, layer), width)


def _rows(leaf: jax.Array, pages, layer=None) -> tuple:
    """Index of whole rows ``pages`` of a value leaf: in layer ``layer`` of
    a stacked leaf, in all its layers (``layer`` None, a leading axis), or
    in an unstacked leaf.  Gathers and scatters of whole rows run natively
    on TPU; a scatter of part-row windows runs as one update per window."""
    pages = jnp.asarray(pages, jnp.int32)
    if leaf.ndim == 2:
        return (pages,)
    if layer is None:
        layers = jnp.arange(leaf.shape[0], dtype=jnp.int32)
        return (layers.reshape((-1,) + (1,) * pages.ndim), pages[None])
    return (layer, pages)


def put_pages(key: str, leaf: jax.Array, pages, new: jax.Array
              ) -> jax.Array:
    """Inverse of :func:`take_pages` at every layer, into the (donated)
    leaf, touching only those pages.  Value pages are whole rows: one
    scatter.  Scale pages share rows, so each page's window of every layer
    is updated in turn (a window spanning the layers in one scatter makes
    the compiler relayout the leaf).  Duplicate targets (unused logical
    pages all aimed at the scratch page) are harmless."""
    new = new.astype(leaf.dtype)
    if key not in _SCALE_LEAVES:
        return leaf.at[_rows(leaf, pages)].set(new)
    width = leaf.shape[-1] // _SCALE_PAGES_PER_ROW
    pages = jnp.asarray(pages, jnp.int32).reshape(-1)
    lead = leaf.shape[:-2]
    new = new.reshape(lead + (pages.shape[0], width))

    def put(i, leaf):
        p = pages[i]
        start = (0,) * len(lead) + (p // _SCALE_PAGES_PER_ROW,
                                    (p % _SCALE_PAGES_PER_ROW) * width)
        return jax.lax.dynamic_update_slice(
            leaf, jax.lax.dynamic_slice_in_dim(new, i, 1, len(lead)), start)
    return jax.lax.fori_loop(0, pages.shape[0], put, leaf)


def paged_cache_write(cache: dict, k: jax.Array, v: jax.Array,
                      positions: jax.Array, table: jax.Array,
                      layer=None) -> dict:
    """Decode/verify write: row ``b``'s token at position ``p`` lands in
    physical page ``table[b, p // page]`` at offset ``p % page``.  S > 1
    (a speculative verify chunk) writes the chunk's positions in order; each
    entry resolves its own page through the row's block table, so a chunk
    may straddle a page boundary.  ``layer`` picks the layer of a stacked
    pool (``(L, P, row)`` leaves); the write updates only the new entries,
    so a pool carried through the layer loop is updated in place.

    Rows whose slot was released have their table row pointed at the scratch
    page (0) by the admission plane, so their garbage writes never touch a
    live page; duplicate scratch indices in the scatter are harmless."""
    B, S, J, N = k.shape
    page = cache["kp"].shape[-1] // (J * N)
    M = table.shape[1]
    rows = jnp.arange(B)[:, None]                           # (B, 1)
    logical = jnp.minimum(positions // page, M - 1)         # clamp dead rows
    phys = table[rows, logical]                             # (B, S)
    off = positions % page                                  # (B, S)
    kf = k.reshape(B * S, J, N)
    vf = v.reshape(B * S, J, N)

    def put(key, new):
        pool = cache[key]
        new = new.reshape(B, S, -1)                         # one entry each
        if key in _SCALE_LEAVES:
            starts = _page_starts(key, pool, phys, layer,
                                  off * new.shape[-1])
            return _write_windows(pool, starts, new)
        # Value pages are whole rows: read each row's page, set its entry,
        # write the page back, one chunk position at a time.  A row's pages
        # are its own, so only released rows (all on the scratch page)
        # collide within a round.
        entry = (jnp.arange(pool.shape[-1]) // new.shape[-1])[None]
        tiled = jnp.tile(new, (1, 1, page)).astype(pool.dtype)
        for s in range(S):
            idx = _rows(pool, phys[:, s], layer)
            pages = jnp.where(entry == off[:, s, None], tiled[:, s],
                              pool[idx])
            pool = pool.at[idx].set(pages)
        return pool
    if "ksc" in cache:
        # Quantize-on-write: the new tokens' K/V rows land as int8 values
        # plus their per-(row, head) scales, so decode appends cost the same
        # bytes as prefilled pages and attention dequantizes uniformly.
        kq, ks = kv_quantize(kf)
        vq, vs = kv_quantize(vf)
        return {"kp": put("kp", kq), "vp": put("vp", vq),
                "ksc": put("ksc", ks), "vsc": put("vsc", vs)}
    return {"kp": put("kp", kf), "vp": put("vp", vf)}


def paged_attend(q: jax.Array, cache: dict, positions: jax.Array,
                 table: jax.Array, layer=None, *, cap: float = 0.0,
                 use_kernel: bool = False) -> jax.Array:
    """Decode attention over the page pool.  q (B, S, J, G, N) pre-scaled;
    S == 1 is the ordinary decode step, S == k+1 a speculative verify chunk
    (each query position masks its own causal horizon, so stale entries
    beyond a row's last write are invisible).  ``layer`` as in
    :func:`paged_cache_write`.

    Kernel path (TPU, S == 1 only): the Pallas kernel DMAs K/V page-by-page
    through the block table — the quantized variant dequantizes inside the
    kernel, so f32 pages are never materialized.  It takes the
    ``(P, page, J, N)`` view of one layer's pool, which this path rebuilds
    at the call.  Oracle path: gather the logical view (dequantizing to the
    query's dtype if the pool carries scale leaves) and reuse ``attend`` —
    bit-identical to the dense-cache decode for f32 pools."""
    lengths = positions[:, -1] + 1                          # just wrote up to
    quant = "ksc" in cache
    B, M = table.shape
    J, N = q.shape[2], q.shape[4]
    page = cache["kp"].shape[-1] // (J * N)
    if use_kernel and q.shape[1] == 1:
        from repro.kernels.paged_attention import ops as pa_ops

        def view(key, *entry):
            pool = cache[key] if layer is None else cache[key][layer]
            return pool.reshape(-1, page, *entry)
        kp, vp = view("kp", J, N), view("vp", J, N)
        if pa_ops.supported(q[:, 0], kp, cap=cap):
            if quant:
                return pa_ops.paged_attention_quant(
                    q[:, 0], kp, vp, view("ksc", J), view("vsc", J),
                    table, lengths)[:, None]
            return pa_ops.paged_attention(
                q[:, 0], kp, vp, table, lengths)[:, None]
    T = M * page

    def gather(key, *entry):
        return take_pages(key, cache[key], table, layer).reshape(
            B, T, *entry)
    kg, vg = gather("kp", J, N), gather("vp", J, N)
    if quant:
        kg = kv_dequantize(kg, gather("ksc", J)).astype(q.dtype)
        vg = kv_dequantize(vg, gather("vsc", J)).astype(q.dtype)
    t = jnp.arange(T, dtype=jnp.int32)[None, :]
    k_pos = jnp.where(t < lengths[:, None], t, -1)
    return attend(q, kg, vg, positions, k_pos, causal=True, cap=cap)


# ----------------------------------------------------------------------------
# Cross-attention (VLM layers / enc-dec decoder): kv from a memory sequence
# ----------------------------------------------------------------------------

def init_cross_attention(key, cfg: ModelConfig, dtype) -> dict:
    return init_attention(key, cfg, dtype)


def cross_attention(
    params: dict,
    x: jax.Array,          # (B, S, D)
    memory: jax.Array,     # (B, M, D) — patch/frame embeddings or enc output
    cfg: ModelConfig,
    *,
    memory_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Non-causal attention over memory. memory_kv short-circuits projection
    (decode: kv computed once at prefill and carried in serve state)."""
    h, j, n = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // j if j else 1
    q = jnp.einsum("bsd,dhn->bshn", x, params["wq"])
    q = q.reshape(*q.shape[:2], j, g, n) * (n ** -0.5)
    if memory_kv is None:
        k = jnp.einsum("bmd,djn->bmjn", memory, params["wk"])
        v = jnp.einsum("bmd,djn->bmjn", memory, params["wv"])
    else:
        k, v = memory_kv
    B, S = x.shape[0], x.shape[1]
    M = k.shape[1]
    qp = jnp.zeros((B, S), jnp.int32)
    kp = jnp.zeros((B, M), jnp.int32)
    out = attend(q, k, v, qp, kp, causal=False, cap=cfg.attn_logit_softcap)
    o = jnp.einsum("bsjgn,jgnd->bsd", out,
                   params["wo"].reshape(j, g, n, cfg.d_model))
    return o, (k, v)
