"""Pallas TPU kernels — the G1 "dedicated accelerators" of this framework.

Each kernel directory has kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd layout adapter + support predicate) and ref.py (pure-jnp
oracle).  ``register_all`` populates the core.accelerators registry.
"""
from __future__ import annotations


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: on the CPU backend
    only.  Any other backend compiles them for real, so an accelerator that
    failed to come up cannot turn into a silently interpreted run."""
    import jax
    return jax.default_backend() == "cpu"


def register_all() -> None:
    from repro.core.accelerators import AcceleratedOp, register_op
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.rglru import ops as rg
    from repro.kernels.rwkv6 import ops as rk
    from repro.kernels.rmsnorm import ops as rn

    register_op(AcceleratedOp(
        "flash_attention", fa.flash_attention, fa.flash_attention_ref,
        fa.supported,
        "GQA flash attention, causal/SWA, VMEM online-softmax"))
    register_op(AcceleratedOp(
        "rglru_scan", rg.linear_scan, rg.linear_scan_ref, rg.supported,
        "blocked linear recurrence (RG-LRU), VMEM-carried state"))
    register_op(AcceleratedOp(
        "rwkv6", rk.rwkv6, rk.rwkv6_ref, rk.supported,
        "RWKV6 chunked recurrence, VMEM-resident NxN state"))
    register_op(AcceleratedOp(
        "rmsnorm", rn.rmsnorm, rn.rmsnorm_ref, rn.supported,
        "fused single-pass RMSNorm"))
