"""jit'd wrapper for the fused RMSNorm kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def supported(x, scale=None, *, eps: float = 1e-6, br: int = 128) -> bool:
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    return rows % min(br, rows) == 0 and x.shape[-1] % 8 == 0


@functools.partial(jax.jit, static_argnames=("eps", "br"))
def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            br: int = 128) -> jax.Array:
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    br = min(br, x2.shape[0])
    out = rmsnorm_pallas(x2, scale, eps=eps, br=br, interpret=interpret_mode())
    return out.reshape(shape)
