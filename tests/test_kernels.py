"""Per-kernel shape/dtype sweeps vs the pure-jnp oracle (deliverable c).

Pallas kernels run in interpret mode on CPU (TPU is the lowering target);
every sweep asserts allclose against ref.py.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import ops as fa
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru import ops as rg
from repro.kernels.rglru.ref import linear_scan_ref
from repro.kernels.rmsnorm import ops as rn
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rwkv6 import ops as rk
from repro.kernels.rwkv6.ref import rwkv6_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ----------------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    # (B, S, J, G, N, window)
    (1, 128, 1, 1, 64, 0),
    (2, 256, 2, 2, 64, 0),
    (1, 256, 1, 4, 128, 0),     # GQA group 4
    (2, 256, 2, 1, 32, 96),     # sliding window
    (1, 512, 1, 2, 16, 0),
])
def test_flash_attention_sweep(shape, dtype, rng):
    B, S, J, G, N, window = shape
    ks = jax.random.split(rng, 3)
    q = (jax.random.normal(ks[0], (B, S, J, G, N)) * 0.4).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, J, N)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, J, N)).astype(dtype)
    out = fa.flash_attention(q, k, v, causal=True, window=window)
    ref = fa.flash_attention_ref(q, k, v, causal=True, window=window)
    tol = TOL[dtype]
    assert out.shape == q.shape
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < tol


def test_flash_attention_noncausal(rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (2, 128, 2, 2, 64)) * 0.4
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    out = fa.flash_attention(q, k, v, causal=False)
    ref = fa.flash_attention_ref(q, k, v, causal=False)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_flash_attention_support_predicate(rng):
    q = jnp.zeros((1, 100, 1, 1, 64))   # S not divisible by block
    k = jnp.zeros((1, 100, 1, 64))
    assert not fa.supported(q, k, k)
    q = jnp.zeros((1, 128, 1, 1, 64))
    k = jnp.zeros((1, 128, 1, 64))
    assert fa.supported(q, k, k)
    assert not fa.supported(q, k, k, cap=30.0)   # softcap unsupported


# ----------------------------------------------------------------------------
# rglru linear scan
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 128, 128), (2, 256, 256), (3, 64, 512),
                                   (2, 512, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_rglru_scan_sweep(shape, dtype, rng):
    B, S, W = shape
    ks = jax.random.split(rng, 2)
    a = jax.random.uniform(ks[0], (B, S, W), dtype, 0.5, 0.999)
    b = jax.random.normal(ks[1], (B, S, W), dtype)
    out = rg.linear_scan(a, b)
    ref = linear_scan_ref(a, b)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4


# ----------------------------------------------------------------------------
# rwkv6
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 64, 1, 16), (2, 128, 2, 16),
                                   (1, 128, 3, 32), (2, 256, 2, 64)])
def test_rwkv6_sweep(shape, rng):
    B, T, H, N = shape
    ks = jax.random.split(rng, 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, N)) for i in range(3))
    wd = jax.random.uniform(ks[3], (B, T, H, N), minval=-6.0, maxval=-0.5)
    w = jnp.exp(-jnp.exp(wd))
    u = jax.random.normal(ks[4], (H, N)) * 0.1
    out = rk.rwkv6(r, k, v, w, u)
    ref = rwkv6_ref(r, k, v, w, u)
    assert float(jnp.max(jnp.abs(out - ref))) < 5e-4


# ----------------------------------------------------------------------------
# rmsnorm
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 64, 128), (2, 128, 512), (8, 8, 960)])
def test_rmsnorm_sweep(shape, dtype, rng):
    x = jax.random.normal(rng, shape).astype(dtype)
    s = jax.random.normal(rng, shape[-1:]).astype(dtype)
    out = rn.rmsnorm(x, s)
    ref = rmsnorm_ref(x, s)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < TOL[dtype]


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", False)])
def test_interpret_mode_only_on_cpu(backend, interpret, monkeypatch):
    """Kernels fall back to the Pallas interpreter on the CPU backend only;
    any other backend compiles them for real (a TPU that failed to come up
    must not turn into a silently interpreted run)."""
    from repro.kernels import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert interpret_mode() is interpret


# ----------------------------------------------------------------------------
# accelerator registry (G1 dispatch)
# ----------------------------------------------------------------------------

def test_registry_selects_kernel_when_supported(rng):
    from repro.core.accelerators import get_op, select
    q = jnp.zeros((1, 128, 1, 1, 64))
    k = jnp.zeros((1, 128, 1, 64))
    op = get_op("flash_attention")
    assert select("flash_attention", q, k, k) is op.kernel
    qbad = jnp.zeros((1, 100, 1, 1, 64))
    kbad = jnp.zeros((1, 100, 1, 64))
    assert select("flash_attention", qbad, kbad, kbad) is op.reference
    assert select("flash_attention", q, k, k,
                  use_accelerators=False) is op.reference
