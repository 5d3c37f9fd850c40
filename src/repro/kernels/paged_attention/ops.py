"""jit'd wrapper: paged decode attention, kernel-or-oracle dispatch (G1)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.paged_attention.kernel import (
    paged_attention_bjgn, paged_attention_quant_bjgn)
from repro.kernels.paged_attention.ref import (
    paged_attention_quant_ref, paged_attention_ref)


# Bytes of VMEM the double-buffered K and V page blocks (plus int8 scale
# blocks) may take.  v5e's default scoped VMEM is 16 MiB: its compiler
# accepts 8 MiB of K/V buffers (f32, page=128, J=16, N=256) and refuses
# 16 MiB (page=256), the rest going to q, the output and the scratch.
_KV_VMEM_BUDGET = 8 * 2**20


def supported(q, kp, *, cap: float = 0.0) -> bool:
    """Shape/dtype predicate (narrow-interface contract, like flash): the
    kernel takes whole-page blocks, so any page size and head dim tile
    legally; what bounds it is the VMEM the page buffers need."""
    if cap and cap > 0.0:
        return False
    if q.ndim != 4 or kp.ndim != 4:
        return False
    _, page, J, N = kp.shape
    if q.shape[1] != J or q.shape[3] != N:
        return False
    itemsize = jnp.dtype(kp.dtype).itemsize
    entry = J * N * itemsize + (4 * J if itemsize == 1 else 0)
    return 2 * 2 * page * entry <= _KV_VMEM_BUDGET


@functools.partial(jax.jit, static_argnames=("cap",))
def paged_attention(q, kp, vp, table, lengths, *, cap: float = 0.0):
    """q (B,J,G,N) pre-scaled; pool (P,page,J,N); table (B,M); lengths (B,).

    Kernel path reads K/V page-by-page through the block table (no contiguous
    materialization); callers gate on ``supported`` and fall back to
    ``paged_attention_ref`` — the oracle the parity tests diff against."""
    del cap  # kernel path requires cap == 0 (see supported())
    return paged_attention_bjgn(q, kp, vp, table, lengths,
                                interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("cap",))
def paged_attention_quant(q, kp, vp, ksc, vsc, table, lengths, *,
                          cap: float = 0.0):
    """Quantized-pool variant: int8 kp/vp + per-(entry, head) f32 ksc/vsc.
    Dequantizes inside the kernel; same ``supported`` gate as f32 (the pool
    layouts match, only the element type differs)."""
    del cap  # kernel path requires cap == 0 (see supported())
    return paged_attention_quant_bjgn(q, kp, vp, ksc, vsc, table, lengths,
                                      interpret=interpret_mode())


__all__ = ["paged_attention", "paged_attention_quant",
           "paged_attention_quant_ref", "paged_attention_ref", "supported"]
