"""v5e ahead-of-time compiles of the Pallas kernels at served widths.

Each test compiles one kernel for a described, not attached, TPU v5e and
asserts the executable holds a Mosaic kernel (``tpu_custom_call``).  The
interpret-mode parity tests (``test_kernels.py``, ``test_serve_paged.py``)
cannot show this: the TPU compiler refuses block shapes and VMEM footprints
the interpreter runs happily.  The kernels are called with
``interpret=False`` directly, because the ``ops`` wrappers see the CPU
backend here and would pick the interpreter.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_bhsn
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.paged_attention.kernel import (
    paged_attention_bjgn, paged_attention_quant_bjgn)
from repro.kernels.rglru.kernel import linear_scan_pallas
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.rwkv6.kernel import rwkv6_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# (head_dim, kv heads J, group G) of the registered paged-KV configs:
# smollm-360m, command-r-35b / phi-3.5-moe, gemma-7b.
PAGED_HEADS = [(64, 5, 3), (128, 8, 8), (256, 16, 1)]


@pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("page", [8, 16, 32])
@pytest.mark.parametrize("N,J,G", PAGED_HEADS)
def test_paged_attention_compiles(one_chip, N, J, G, page, pool):
    B, M = 8, 512 // page
    P = B * M + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    q = sds((B, J, G, N), jnp.bfloat16)
    kv = sds((P, page, J, N), jnp.dtype(pool))
    table, lengths = sds((B, M), jnp.int32), sds((B,), jnp.int32)
    assert pa_ops.supported(q, kv)
    if pool == "int8":
        sc = sds((P, page, J), jnp.float32)
        text = _compile(paged_attention_quant_bjgn, q, kv, kv, sc, sc,
                        table, lengths)
    else:
        text = _compile(paged_attention_bjgn, q, kv, kv, table, lengths)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page", [128, 256])
def test_paged_supported_matches_vmem_limit(one_chip, page):
    """supported() admits exactly the page blocks the compiler fits in
    VMEM: 8 MiB of double-buffered f32 K/V blocks compile, 16 MiB do not."""
    B, J, G, N, M = 2, 16, 1, 256, 4
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    q = sds((B, J, G, N), jnp.float32)
    kv = sds((16, page, J, N), jnp.float32)
    args = (q, kv, kv, sds((B, M), jnp.int32), sds((B,), jnp.int32))
    if pa_ops.supported(q, kv):
        assert "tpu_custom_call" in _compile(paged_attention_bjgn, *args)
    else:
        with pytest.raises(Exception, match="vmem"):
            _compile(paged_attention_bjgn, *args)
    assert pa_ops.supported(q, kv) == (page == 128)


@pytest.mark.parametrize("N,J,G", [(64, 5, 3), (128, 8, 8)])
def test_flash_attention_compiles(one_chip, N, J, G):
    B, S = 2, 256
    q = jax.ShapeDtypeStruct((B * J * G, S, N), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B * J, S, N), jnp.bfloat16, sharding=one_chip)
    fn = lambda q, k, v: flash_attention_bhsn(q, k, v, group=G, causal=True,
                                              scale=1.0, bq=128, bk=128)
    assert "tpu_custom_call" in _compile(fn, q, kv, kv)


def test_rwkv6_compiles(one_chip):
    BH, T, N = 40, 128, 64                   # rwkv6-3b: 2560 / 64 heads
    x = jax.ShapeDtypeStruct((BH, T, N), jnp.float32, sharding=one_chip)
    u = jax.ShapeDtypeStruct((BH, N), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compile(rwkv6_pallas, x, x, x, x, u)


@pytest.mark.parametrize("d", [960, 2560])
def test_rmsnorm_compiles(one_chip, d):
    x = jax.ShapeDtypeStruct((256, d), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((d,), jnp.bfloat16, sharding=one_chip)
    fn = lambda x, s: rmsnorm_pallas(x, s, br=128)
    assert "tpu_custom_call" in _compile(fn, x, s)


def test_rglru_compiles(one_chip):
    a = jax.ShapeDtypeStruct((2, 256, 4096), jnp.float32, sharding=one_chip)
    fn = lambda a, b: linear_scan_pallas(a, b, bs=128, bw=512)
    assert "tpu_custom_call" in _compile(fn, a, a)
