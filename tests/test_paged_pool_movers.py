"""The paged K/V pool against a plain NumPy model of it.

The model keeps one logical array per pool leaf: values ``(L, P, page, J,
N)``, int8 scales ``(L, P, page, J)``.  Every page mover must agree with it,
and the decode and admit programs must leave every entry they do not write
bitwise unchanged: they update the pool in place.  Tier-1, CPU, bf16 model.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.config import get_config
from repro.models.attention import kv_dequantize, kv_quantize
from repro.models.transformer import (
    ExecPolicy, init_decode_state, init_paged_decode_state, init_params,
    load_prefix_pages, read_page, read_pages, scatter_solo_pages,
    write_page)
from repro.serve import programs

PAGES, PAGE, SLOTS = 13, 4, 3           # 13 pages: a part-used scale row
CAPACITY = 8 * PAGE                     # solo cache / max_seq_len: 8 pages


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_config("repro-tiny"), dtype="bfloat16")


def _model_of(pstate, cfg) -> dict:
    """NumPy model of a device pool: leaf key -> logical array."""
    cache = pstate["slots"]["0"]["cache"]
    L, J = cfg.num_layers, cfg.num_kv_heads
    out = {}
    for key, leaf in cache.items():
        a = np.asarray(leaf)
        if key in ("kp", "vp"):          # a page per row
            out[key] = a.reshape(L, PAGES, PAGE, J, cfg.head_dim)
        else:                            # rows of 8 pages
            out[key] = a.reshape(L, -1, PAGE, J)[:, :PAGES]
    return out


def _random_pool(cfg, kv_quant, seed):
    """A pool filled with random content through its own movers."""
    rng = np.random.default_rng(seed)
    pstate = init_paged_decode_state(cfg, PAGES, PAGE, kv_quant=kv_quant)
    for p in range(PAGES):
        blob = jax.tree.map(
            lambda a: (rng.integers(-127, 128, a.shape).astype(a.dtype)
                       if a.dtype == jnp.int8 else
                       rng.uniform(0.5, 2.0, a.shape).astype(a.dtype)),
            read_page(pstate, p))
        pstate = write_page(pstate, p, blob)
    return pstate


def _assert_model_equal(got: dict, want: dict, skip_pages=()):
    keep = [p for p in range(PAGES) if p not in skip_pages]
    for key in want:
        np.testing.assert_array_equal(got[key][:, keep], want[key][:, keep],
                                      err_msg=key)


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_page_movers_match_numpy_model(cfg, kv_quant):
    pstate = _random_pool(cfg, kv_quant, seed=1)
    model = _model_of(pstate, cfg)
    assert all(np.unique(a).size > 1 for a in model.values())
    L = cfg.num_layers

    def flat(a):                         # one page -> its blob row per layer
        return a.reshape(L, -1)

    for p in (0, 5, PAGES - 1):
        blob = read_page(pstate, p)["slots"]["0"]["cache"]
        for key in model:
            np.testing.assert_array_equal(np.asarray(blob[key]),
                                          flat(model[key][:, p]))
    pages = jnp.asarray([3, 12, 3, 7], jnp.int32)
    stacked = read_pages(pstate, pages)["slots"]["0"]["cache"]
    for key in model:
        for i, p in enumerate(np.asarray(pages)):
            np.testing.assert_array_equal(np.asarray(stacked[key][i]),
                                          flat(model[key][:, p]))

    # write_page: page 9 takes page 2's content, nothing else moves.
    moved = write_page(pstate, 9, read_page(pstate, 2))
    want = {k: a.copy() for k, a in model.items()}
    for a in want.values():
        a[:, 9] = a[:, 2]
    _assert_model_equal(_model_of(moved, cfg), want)

    # load_prefix_pages: the row's pages, dequantized, as a dense cache.
    table_row = jnp.asarray([4, 11, 0, 6, 0, 0, 0, 0], jnp.int32)
    solo = init_decode_state(cfg, 1, CAPACITY)
    loaded = load_prefix_pages(solo, pstate, table_row, 10)
    rows = np.asarray(table_row)
    for dense_key, key, skey in (("k", "kp", "ksc"), ("v", "vp", "vsc")):
        want = model[key][:, rows]
        if kv_quant == "int8":
            want = np.asarray(kv_dequantize(jnp.asarray(want),
                                            jnp.asarray(model[skey][:, rows])))
        dense = loaded["slots"]["0"]["cache"][dense_key]
        np.testing.assert_array_equal(
            np.asarray(dense),
            want.reshape(dense.shape).astype(dense.dtype))
    pos = np.asarray(loaded["slots"]["0"]["cache"]["pos"])
    assert (pos[..., :10] == np.arange(10)).all()
    assert (pos[..., 10:] == -1).all()

    # scatter_solo_pages: logical page m lands on page assign[m]; logical
    # pages aimed at the scratch page touch no other page.
    rng = np.random.default_rng(2)
    dense = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype), solo)
    assign = np.asarray([0, 8, 0, 1, 10, 0, 0, 0], np.int32)
    out = _model_of(
        scatter_solo_pages(pstate, dense, jnp.asarray(assign)), cfg)
    want = {k: a.copy() for k, a in model.items()}
    for dense_key, key, skey in (("k", "kp", "ksc"), ("v", "vp", "vsc")):
        x = dense["slots"]["0"]["cache"][dense_key]
        x = x.reshape(L, -1, PAGE, *x.shape[-2:])          # (L, M, page, J, N)
        if kv_quant == "int8":
            x, sc = kv_quantize(x)
            for m, p in enumerate(assign):
                if p:
                    want[skey][:, p] = np.asarray(sc[:, m])
        for m, p in enumerate(assign):
            if p:
                want[key][:, p] = np.asarray(x[:, m]).astype(want[key].dtype)
    _assert_model_equal(out, want, skip_pages=(0,))


def _written(table, positions):
    """(page, offset) entries a decode step writes: every row's own, the
    released rows' on the scratch page."""
    return {(int(table[b, p // PAGE]), int(p % PAGE))
            for b, p in enumerate(positions)}


@pytest.mark.parametrize("scan_layers", [True, False])
@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_decode_and_admit_touch_only_their_entries(cfg, kv_quant,
                                                   scan_layers):
    policy = ExecPolicy(scan_layers=scan_layers)
    params = init_params(jax.random.PRNGKey(0), cfg)
    pstate = _random_pool(cfg, kv_quant, seed=3)
    before = _model_of(pstate, cfg)
    M = CAPACITY // PAGE
    table = np.zeros((SLOTS, M), np.int32)
    table[0, :3] = [5, 2, 9]             # live, mid page
    table[1, :3] = [1, 7, 3]             # live, first entry of a page
    positions = np.asarray([6, 8, 3], np.int32)    # row 2 released: scratch
    mirrors = {"tok": jnp.asarray([3, 4, 5], jnp.int32),
               "pos": jnp.asarray(positions),
               "temp": jnp.zeros(SLOTS, jnp.float32),
               "top_k": jnp.zeros(SLOTS, jnp.int32),
               "top_p": jnp.ones(SLOTS, jnp.float32)}
    step = programs.paged_decode_program(cfg, policy)
    pstate, _, key, mirrors = step(params, pstate, jax.random.PRNGKey(1),
                                   mirrors, jnp.asarray(table))
    after = _model_of(pstate, cfg)
    written = _written(table, positions)
    assert written == {(2, 2), (3, 0), (0, 3)}
    for k in before:
        changed = np.argwhere(
            (after[k] != before[k]).reshape(*after[k].shape[:3], -1).any(-1))
        assert {(int(p), int(o)) for _, p, o in changed} <= written, k
        for p, o in written - {(0, 3)}:
            assert (after[k][:, p, o] != before[k][:, p, o]).any(), (k, p, o)

    # Admit a 6-token prompt whose first page is a prefix hit (page 5):
    # only the pages ``assign`` names change, page 0 aside.
    prompt = np.asarray([7, 8, 9, 10, 11, 12], np.int32)
    hit, S = PAGE, 8
    toks = np.zeros((1, S), np.int32)
    toks[0, :len(prompt) - hit] = prompt[hit:]
    row = np.zeros(M, np.int32)
    row[:3] = [5, 11, 12]
    assign = np.zeros(M, np.int32)
    assign[1:3] = [11, 12]
    batch = {"tokens": jnp.asarray(toks),
             "positions": jnp.asarray(hit + np.arange(S)[None], jnp.int32),
             "length": jnp.asarray(len(prompt), jnp.int32),
             "hit_len": jnp.asarray(hit, jnp.int32),
             "table": jnp.asarray(row), "assign": jnp.asarray(assign),
             "slot": jnp.asarray(2, jnp.int32),
             "temp": jnp.asarray(0.0, jnp.float32),
             "top_k": jnp.asarray(0, jnp.int32),
             "top_p": jnp.asarray(1.0, jnp.float32)}
    admit = programs.paged_admit_program(cfg, policy, CAPACITY)
    pstate, _, _, _ = admit(params, pstate, batch, key, mirrors)
    _assert_model_equal(_model_of(pstate, cfg), after, skip_pages=(0, 11, 12))
    changed = _model_of(pstate, cfg)
    for k in after:
        assert (changed[k][:, 11] != after[k][:, 11]).any(), k
