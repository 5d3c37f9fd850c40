"""Pallas TPU RWKV6 chunked recurrence.

Per (batch, head), state S ∈ R^{NxN} lives in VMEM scratch across the
sequential chunk axis.  Within a chunk of c timesteps the contribution is
computed in parallel form (three (c x N)/(N x N) MXU matmuls + a masked
(c x c) intra-chunk product) — the same math as
``repro.models.rwkv6.rwkv6_chunked`` (the oracle), but with the state kept
resident in VMEM instead of bouncing through HBM each chunk.

Grid: (B*H, T/c) with the chunk axis minor (sequential) so the scratch-
carried state is legal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *,
                  chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    rc = r_ref[0].astype(jnp.float32)       # (c, N)
    kc = k_ref[0].astype(jnp.float32)
    vc = v_ref[0].astype(jnp.float32)
    wc = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)        # (1, 1, N) block -> (1, N)

    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    logw = jnp.log(jnp.maximum(wc, 1e-30))
    # Prefix sum over the chunk as a lower-triangular matmul: Mosaic has no
    # cumsum lowering.
    cum = jax.lax.dot_general(jnp.where(si <= ti, 1.0, 0.0), logw,
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    winc = jnp.exp(cum)                     # decay incl. t
    wexc = jnp.exp(cum - logw)              # decay up to t-1

    S = s_scr[...]                          # (N, N)
    rw = rc * wexc
    kw = kc / jnp.maximum(winc, 1e-30)
    # inter-chunk
    y = jax.lax.dot_general(rw, S, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (c, N)
    # intra-chunk, strictly-lower-triangular pairs
    A = jax.lax.dot_general(rw, kw, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (c, c)
    A = jnp.where(si < ti, A, 0.0)
    y = y + jax.lax.dot_general(A, vc, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # diagonal bonus
    diag = jnp.sum(rc * (u * kc), axis=1, keepdims=True)         # (c, 1)
    y = y + diag * vc
    o_ref[0] = y.astype(o_ref.dtype)
    # state update
    wlast = winc[chunk - 1:chunk]           # (1, N)
    kdec = kw * wlast
    S_new = wlast.T * S + jax.lax.dot_general(
        kdec, vc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scr[...] = S_new


def rwkv6_pallas(r, k, v, w, u, *, chunk: int = 64,
                 interpret: bool = False) -> jax.Array:
    """r,k,v,w: (BH, T, N) f32; u: per-head (H, N) expanded to (BH, N) by the
    wrapper. Returns y (BH, T, N).

    ``u`` rides as (BH, 1, N) with (1, 1, N) blocks: Mosaic wants a block's
    last two dims (8, 128)-aligned or equal to the array's, which a (1, N)
    block of a (BH, N) array is not."""
    BH, T, N = r.shape
    chunk = min(chunk, T)
    nc = T // chunk

    def imap(bh, ic):
        return (bh, ic, 0)

    def umap(bh, ic):
        return (bh, 0, 0)

    spec = pl.BlockSpec((1, chunk, N), imap)
    return pl.pallas_call(
        functools.partial(_rwkv6_kernel, chunk=chunk),
        grid=(BH, nc),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, 1, N), umap)],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((BH, T, N), r.dtype),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(BH, 1, N))
