"""Pallas TPU paged-attention decode kernel.

Per batch row the kernel walks the row's block table: the minor grid axis
iterates logical pages, and ``PrefetchScalarGridSpec`` makes the block table
available to the *index maps*, so each K/V block is DMA'd straight from its
physical page in the pool — decode reads through the block table without
ever materializing a contiguous (B, T) cache view (that materialization is
exactly what the pure-JAX reference does, and what this kernel exists to
avoid).

A K/V block is one whole physical page, all kv heads: ``(1, page, J, N)``.
Its last two dims equal the pool's, which is what Mosaic requires of a block
whose dims are not (8, 128)-aligned — a per-head ``(1, page, 1, N)`` block is
refused for every J > 1.  The kernel then indexes each head inside VMEM, so
one DMA per page serves all J heads.

Same TPU shape as the flash kernel (see flash_attention/kernel.py): the
online-softmax running (m, l, acc) live in VMEM scratch across the
sequentially-executed minor grid axis, and pages past a row's length are
predicated off with ``pl.when`` so dead pages cost no MXU work.

Layouts: q (B, J, G, N) one token per row; kp/vp (P, page, J, N), int8 pools
add per-(entry, head) scales ksc/vsc (P, page, J); table (B*M,) flattened +
lengths (B,) as scalar-prefetch operands.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, *refs,
                  page: int, n_pages: int, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    n_heads = k_ref.shape[2]
    b = pl.program_id(0)
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    t0 = m * page

    @pl.when(t0 < length)                 # pages past the row's length: dead
    def _compute():
        tpos = t0 + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        for j in range(n_heads):
            q = q_ref[0, j].astype(jnp.float32)          # (G, N), pre-scaled
            k = k_ref[0, :, j].astype(jnp.float32)       # (page, N)
            v = v_ref[0, :, j].astype(jnp.float32)
            if quant:
                # Fused dequant: int8 entries scale to f32 in VMEM, so no f32
                # page is ever materialized in HBM.
                k = k * ks_ref[0, :, pl.ds(j, 1)]        # (page, 1) scales
                v = v * vs_ref[0, :, pl.ds(j, 1)]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(tpos < length, s, NEG_INF)     # partial tail page

            m_prev = m_scr[j]                            # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                       # (G, page)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            m_scr[j] = m_new
            l_scr[j] = l_scr[j] * corr + p.sum(axis=1, keepdims=True)
            acc_scr[j] = acc_scr[j] * corr + pv

    @pl.when(m == n_pages - 1)
    def _finalize():
        for j in range(n_heads):
            l = jnp.maximum(l_scr[j], 1e-30)
            o_ref[0, j] = (acc_scr[j] / l).astype(o_ref.dtype)


def _paged_call(q, kp, vp, scales: Optional[tuple], table, lengths, *,
                interpret: bool) -> jax.Array:
    B, J, G, N = q.shape
    page = kp.shape[1]
    M = table.shape[1]

    # Index maps see the scalar-prefetch refs after the grid indices; the kv
    # map reads the block table to pick the physical page for (row b, page m).
    def q_map(b, m, table_ref, len_ref):
        return (b, 0, 0, 0)

    def kv_map(b, m, table_ref, len_ref):
        return (table_ref[b * M + m], 0, 0, 0)

    def sc_map(b, m, table_ref, len_ref):
        return (table_ref[b * M + m], 0, 0)

    in_specs = [pl.BlockSpec((1, J, G, N), q_map),
                pl.BlockSpec((1, page, J, N), kv_map),
                pl.BlockSpec((1, page, J, N), kv_map)]
    operands = [q, kp, vp]
    if scales is not None:
        in_specs += [pl.BlockSpec((1, page, J), sc_map)] * 2
        operands += [s.astype(jnp.float32) for s in scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, J, G, N), q_map),
        scratch_shapes=[
            pltpu.VMEM((J, G, 1), jnp.float32),
            pltpu.VMEM((J, G, 1), jnp.float32),
            pltpu.VMEM((J, G, N), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, page=page, n_pages=M,
                               quant=scales is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, J, G, N), q.dtype),
        interpret=interpret,
    )(table.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)


def paged_attention_bjgn(
    q: jax.Array,          # (B, J, G, N)
    kp: jax.Array,         # (P, page, J, N)
    vp: jax.Array,         # (P, page, J, N)
    table: jax.Array,      # (B, M) int32
    lengths: jax.Array,    # (B,) int32
    *,
    interpret: bool = False,
) -> jax.Array:            # (B, J, G, N)
    return _paged_call(q, kp, vp, None, table, lengths, interpret=interpret)


def paged_attention_quant_bjgn(
    q: jax.Array,          # (B, J, G, N)
    kp: jax.Array,         # (P, page, J, N) int8
    vp: jax.Array,         # (P, page, J, N) int8
    ksc: jax.Array,        # (P, page, J) f32 per-(entry, head) scales
    vsc: jax.Array,        # (P, page, J) f32
    table: jax.Array,      # (B, M) int32
    lengths: jax.Array,    # (B,) int32
    *,
    interpret: bool = False,
) -> jax.Array:            # (B, J, G, N)
    """Quantized-page variant of ``paged_attention_bjgn``: same grid, same
    block-table prefetch, plus two scale operands riding the same page
    index (a scale block is the whole physical page's (page, J) slab)."""
    return _paged_call(q, kp, vp, (ksc, vsc), table, lengths,
                       interpret=interpret)
