"""bench/reference against the program at a tiny size on the CPU.

In float32 the served tokens of the paged engine (prefill through the admit
programs, with prefix pages reused, then decode through the page pool) are
the reference's own greedy choices, and the program's logits equal the
reference's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import serve_config
from bench.reference import decoder
from bench.tests.conftest import CELLS, tiny_cell

SEED = 2**31 + 77


@pytest.fixture(scope="module", params=CELLS)
def f32_model(request):
    cell = tiny_cell(request.param, dtype="float32")
    s = cell.arch.shapes(cell.config)
    cfg = cell.arch.model_config("tiny", cell.config)
    return cell, s, cfg, cell.arch.make_params(SEED, s)


def test_forward_logits_match(f32_model):
    from repro.models.transformer import forward
    cell, s, cfg, params = f32_model
    toks = np.random.default_rng(0).integers(0, s.vocab, (2, 40))
    with jax.default_matmul_precision("highest"):
        prog, _, _ = forward(params, cfg, jnp.asarray(toks, jnp.int32))
    ref = decoder.logits_at(SEED, s, list(toks),
                            [list(range(40)), list(range(40))],
                            cell.arch.reference_layer,
                            cell.arch.global_weights)
    np.testing.assert_allclose(np.asarray(prog).reshape(-1, s.vocab), ref,
                               atol=2e-5, rtol=1e-5)


def test_served_tokens_are_reference_greedy(f32_model):
    from repro.serve import make_engine
    cell, s, cfg, params = f32_model
    scfg = serve_config(cell.config)
    rng = np.random.default_rng(1)
    system = rng.integers(0, s.vocab, 40).astype(np.int32)
    prompts = [np.concatenate([system, rng.integers(0, s.vocab, n)
                               ]).astype(np.int32) for n in (5, 17, 30)]
    with jax.default_matmul_precision("highest"):
        eng = make_engine(cfg, params, scfg)
        outs = {}
        for i, p in enumerate(prompts):
            rid = eng.submit(p, 12)
            eng.run()        # one at a time: later prompts reuse pages
            outs[i] = (p, list(eng.result(rid)["tokens"]),
                       eng.request(rid).prefix_hit_tokens)
        eng.close()
    assert outs[2][2] > 0, "the shared system prompt was not reused"
    gaps = cell.arch.served_gaps(SEED, s,
                                 [(p, t) for p, t, _ in outs.values()])
    assert gaps.max() < 1e-4


def test_control_departs_from_reference(f32_model):
    cell, s, _, _ = f32_model
    toks = np.random.default_rng(2).integers(0, s.vocab, 64)
    model = (cell.arch.reference_layer, cell.arch.global_weights)
    ref = decoder.logits_at(SEED, s, [toks], [list(range(64))], *model)
    low = decoder.logits_at(SEED, s, [toks], [list(range(64))], *model,
                            fp8=True)
    err = np.abs(low - ref).max() / np.abs(ref).max()
    assert 1e-3 < err < 0.5


@pytest.mark.parametrize("name", CELLS)
def test_params_have_the_programs_layout(name):
    """The seeded weights fill exactly the tree the program's own
    ``init_params`` makes, leaf for leaf, at the configuration's sizes."""
    from bench.tests.conftest import any_cell
    from repro.models.transformer import init_params
    cell = any_cell(name)
    s = cell.arch.shapes(cell.config)
    cfg = cell.arch.model_config(name, cell.config)
    ours = jax.eval_shape(lambda: cell.arch.make_params(SEED, s))
    theirs = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
