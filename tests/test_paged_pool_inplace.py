"""v5e ahead-of-time compiles of the paged decode, admit and verify programs:
the K/V page pool is updated in place, in the layout it is stored in.

Each test compiles one program for a described, not attached, TPU v5e at
smollm-360m's widths (8192 pages of 16 tokens, 32 slots, ``max_seq_len``
2304) and reads the executable: no copy or transpose the size of a layer's
pool, the donated pool aliased to the output, the pool stored without
padding, and no pool-sized scratch.  Four layers, not fewer: a stacked leaf
must exceed the v5e's 128 MiB of VMEM, as a served pool does, or the
compiler stages the whole pool through VMEM and back.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import ServeConfig, get_config
from repro.launch.hlo_analysis import pool_relayouts
from repro.models.transformer import (
    ExecPolicy, init_paged_decode_state, init_params)
from repro.serve import programs
from repro.serve.backends import PagedKVBackend

LAYERS, PAGES, PAGE, SLOTS, MAX_SEQ = 4, 8192, 16, 32, 2304
BUCKET, DRAFT_K = 16, 4             # admit's suffix bucket, verify's k


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


def _program_args(one_chip, program, kv_quant, pages):
    """(cfg, jitted program, abstract arguments); the pool is argument 1."""
    cfg = dataclasses.replace(get_config("smollm-360m"), num_layers=LAYERS)
    policy = ExecPolicy()
    M = MAX_SEQ // PAGE
    if pages == "default":
        pages = PagedKVBackend(cfg, ServeConfig(
            max_batch=SLOTS, max_seq_len=MAX_SEQ,
            page_size=PAGE)).pool.num_pages

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = sds(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    pstate = sds(jax.eval_shape(lambda: init_paged_decode_state(
        cfg, pages, PAGE, kv_quant=kv_quant)))
    key = sds(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    i32, f32 = jnp.int32, jnp.float32
    mirrors = {"tok": arr(i32, SLOTS), "pos": arr(i32, SLOTS),
               "temp": arr(f32, SLOTS), "top_k": arr(i32, SLOTS),
               "top_p": arr(f32, SLOTS)}
    table = arr(i32, SLOTS, M)
    if program == "decode":
        return cfg, programs.paged_decode_program(cfg, policy), (
            params, pstate, key, mirrors, table)
    if program == "verify":
        return cfg, programs.paged_verify_program(cfg, policy, DRAFT_K), (
            params, pstate, key, mirrors, table, arr(i32, SLOTS, DRAFT_K),
            arr(i32, SLOTS))
    batch = {"tokens": arr(i32, 1, BUCKET), "positions": arr(i32, 1, BUCKET),
             "length": arr(i32), "hit_len": arr(i32), "table": arr(i32, M),
             "assign": arr(i32, M), "slot": arr(i32), "temp": arr(f32),
             "top_k": arr(i32), "top_p": arr(f32)}
    return cfg, programs.paged_admit_program(cfg, policy, MAX_SEQ), (
        params, pstate, batch, key, mirrors)


def _stored_bytes(aval, fmt) -> int:
    """Bytes of an argument as the executable stores it: its two minor-most
    dims (in layout order) rounded up to the layout's first tile."""
    layout = getattr(fmt, "layout", None)
    dims = list(aval.shape)
    if layout is not None and dims and layout.tiling:
        dims = [dims[i] for i in layout.major_to_minor]
        tile = layout.tiling[0]
        for j in range(1, min(len(tile), len(dims)) + 1):
            dims[-j] = -(-dims[-j] // tile[-j]) * tile[-j]
    return math.prod(dims) * jnp.dtype(aval.dtype).itemsize


# Every program at 8192 pages, and decode at the page count an engine
# picks by default (full residency plus the scratch page, 4609 here, which
# unrounded makes the compiler relayout the pool).
CASES = [(program, kv_quant, PAGES) for kv_quant in ("none", "int8")
         for program in ("decode", "admit", "verify")]
CASES.append(("decode", "none", "default"))


@pytest.mark.parametrize("program,kv_quant,pages", CASES)
def test_paged_program_updates_pool_in_place(one_chip, program, kv_quant,
                                             pages):
    cfg, fn, args = _program_args(one_chip, program, kv_quant, pages)
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()

    pool = jax.tree_util.tree_flatten_with_path(args[1])[0]
    leaves = {path[-1].key: a for path, a in pool if a.ndim > 0}
    assert set(leaves) == ({"kp", "vp", "ksc", "vsc"} if kv_quant == "int8"
                           else {"kp", "vp"})

    moves = pool_relayouts(hlo, leaves.values())
    assert not moves, "pool-sized relayouts:\n" + "\n".join(
        line[:160] for line in moves)

    # Stored bytes of every argument, from the executable's own layouts;
    # they must add up to what the executable reports.
    formats = jax.tree.leaves(compiled.input_formats[0],
                              is_leaf=lambda x: hasattr(x, "layout"))
    avals = jax.tree.leaves(args)
    assert len(formats) == len(avals)
    stored = [_stored_bytes(a, f) for a, f in zip(avals, formats)]
    assert abs(sum(stored) - mem.argument_size_in_bytes) \
        <= 0.01 * mem.argument_size_in_bytes
    first = len(jax.tree.leaves(args[0]))
    pool_stored = dict(zip((path[-1].key for path, _ in pool),
                           stored[first:first + len(pool)]))
    for key, leaf in leaves.items():
        logical = leaf.size * jnp.dtype(leaf.dtype).itemsize
        if key in ("kp", "vp"):
            assert pool_stored[key] <= 1.02 * logical, (key, pool_stored)
        else:
            # Scale rows hold 8 pages' scales each: stored unpadded, as
            # the page-minor scales were before; rows of one page's 80
            # scales would pad to 128 lanes.
            assert pool_stored[key] <= logical, (key, pool_stored)

    # Donation honoured: the whole pool is aliased to the program's output.
    pool_bytes = sum(pool_stored[k] for k in leaves)
    assert mem.alias_size_in_bytes >= pool_bytes

    # Scratch holds no pool-sized buffer.  What a step must hold besides
    # the pool is the attention's gathered K and V views of one layer
    # (B*M pages each): gathered rows, dequantized f32 for int8 pools, and
    # the dot's relayouted operand, each at most an f32 view.
    j, n = cfg.num_kv_heads, cfg.head_dim
    k_layer = math.prod(leaves["kp"].shape[1:]) * jnp.dtype(
        leaves["kp"].dtype).itemsize
    view = SLOTS * (MAX_SEQ // PAGE) * PAGE * j * n * 4
    assert mem.temp_size_in_bytes < k_layer + 3 * 2 * view, (
        mem.temp_size_in_bytes, k_layer, view)
