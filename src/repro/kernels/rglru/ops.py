"""jit'd wrapper for the RG-LRU linear-scan kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.rglru.kernel import linear_scan_pallas
from repro.kernels.rglru.ref import linear_scan_ref


def supported(a, b=None, *, bs: int = 128, bw: int = 128) -> bool:
    B, S, W = a.shape
    return S % min(bs, S) == 0 and W % min(bw, W) == 0 and W % 8 == 0


@functools.partial(jax.jit, static_argnames=("bs", "bw"))
def linear_scan(a: jax.Array, b: jax.Array, *, bs: int = 128,
                bw: int = 512) -> jax.Array:
    while a.shape[2] % bw:
        bw //= 2
    while a.shape[1] % bs:
        bs //= 2
    return linear_scan_pallas(a, b, bs=bs, bw=bw, interpret=interpret_mode())
